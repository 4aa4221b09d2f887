"""Trace reduction: busy union, idle share, search-program time and the
attribution of idle gaps, on a hand-made trace and on one recorded on a
v5e during a run of ``sift1m-exact.steady`` (its traced-window span, one
search call's span and module, and the first 40 ms of that search's ops,
with op names cut to the name of the HLO instruction)."""
import json
from pathlib import Path

import pytest

from chipbench_tiny import ROOT  # noqa: F401  (puts the benchmark on the path)

from chipbench.trace import (
    GAP_HOST, GAP_IDLE, GAP_SEARCH, MODULE_LINE, OP_LINE, Event, covered, reduce_trace, union,
)

RECORDED = Path(__file__).with_name("data") / "v5e_trace_sample.json"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def test_union_and_covered():
    merged = union([(5, 10), (0, 2), (8, 12), (20, 30)])
    assert merged == [(0, 2), (5, 12), (20, 30)]
    assert covered(merged, 1, 25) == 1 + 7 + 5
    assert covered(merged, 12, 20) == 0
    assert covered(merged, -5, 100) == 2 + 7 + 10


def _hand_made():
    ms = 1e6
    return [
        Event(HOST, "python", "chipbench.traced", 0, 100 * ms),
        Event(HOST, "pump", "chipbench.search", 5 * ms, 30 * ms, seq=7),
        Event(HOST, "pump", "chipbench.search", 45 * ms, 20 * ms, seq=8),
        Event(HOST, "client", "chipbench.request", 70 * ms, 10 * ms),
        Event(DEV, OP_LINE, "fusion.1", 10 * ms, 10 * ms),
        Event(DEV, OP_LINE, "fusion.2", 15 * ms, 15 * ms),  # overlaps fusion.1
        Event(DEV, OP_LINE, "fusion.1", 50 * ms, 10 * ms),
        Event(DEV, MODULE_LINE, "jit_impl", 10 * ms, 50 * ms),  # ops take precedence
        Event(DEV, OP_LINE, "copy", 99 * ms, 5 * ms),  # clipped at the window's end
        Event(DEV, OP_LINE, "before", -20 * ms, 5 * ms),  # outside the window
    ]


def test_hand_made_trace():
    s = reduce_trace(_hand_made())
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.020 + 0.010 + 0.001)
    assert s["idle_share"] == pytest.approx(1 - 0.031 / 0.1)
    assert s["searches"] == {7: pytest.approx(0.020), 8: pytest.approx(0.010)}
    ops = dict((n, t) for n, t in s["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.020) and "before" not in ops
    gaps = s["breakdown"]["idle_gaps"]
    # [60, 99] ms splits into the end of search 8, a wait, a request, a wait
    assert gaps[0] == [GAP_IDLE, pytest.approx(0.019)]
    assert sorted(g[0] for g in gaps[1:3]) == [GAP_HOST, GAP_IDLE]
    assert [g[1] for g in gaps[1:3]] == pytest.approx([0.010, 0.010])
    assert {g[0] for g in gaps[3:]} == {GAP_SEARCH, GAP_IDLE}
    assert sum(g[1] for g in gaps) == pytest.approx(0.1 - s["busy_s"])


def test_gaps_with_nothing_on_the_host_are_waits_for_arrivals():
    ms = 1e6
    s = reduce_trace([Event(HOST, "python", "chipbench.traced", 0, 10 * ms),
                      Event(DEV, OP_LINE, "fusion", 2 * ms, 1 * ms)])
    assert {g[0] for g in s["breakdown"]["idle_gaps"]} == {GAP_IDLE}


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(ValueError):
        reduce_trace([Event(HOST, "python", "chipbench.traced", 0, 10.0)])


def test_recorded_v5e_trace():
    events = [Event(**e) for e in json.loads(RECORDED.read_text())]
    s = reduce_trace(events)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert 0 <= s["idle_share"] < 1
    assert s["searches"], "the recorded window holds a search call"
    assert all(0 < t <= s["window_s"] for t in s["searches"].values())
    assert s["breakdown"]["device_ops"] and len(s["breakdown"]["device_ops"]) <= 10
    assert sum(g[1] for g in s["breakdown"]["idle_gaps"]) <= s["window_s"] - s["busy_s"] + 1e-9
