"""In-program spans and host-time counters of the serving path
(DESIGN.md §12).

Each stage of a served search is a ``jax.profiler.TraceAnnotation`` on the
trace's host plane, with the request or batch id it belongs to, and adds
its host time to ``Telemetry.counters``. These tests serve a tiny runtime
over HTTP under a CPU profiler session and read the ``.xplane.pb`` back;
the counter tests replay on a ``VirtualClock`` where the arithmetic is
exact.
"""
import json
import threading
import urllib.request
from collections import defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.data.synthetic import make_labeled_corpus
from repro.graph.index import build_index
from repro.obs import span
from repro.obs.http import ServingFrontend
from repro.serving import (
    LocalExecutor,
    ServingRuntime,
    VirtualClock,
    label_words_row,
    make_tier_ladder,
    mixed_workload,
    replay_poisson,
    wall_clock,
)

N, D, L = 800, 8, 4
PUMP_STAGES = ("assemble", "dispatch", "device_wait", "readback", "complete")
HTTP_SPANS = {"repro.http.search", "repro.http.parse", "repro.http.admit",
              "repro.http.await", "repro.http.reply"}
PUMP_SPANS = {"repro.runtime.step", "repro.runtime.assemble",
              "repro.runtime.complete", "repro.search.dispatch",
              "repro.search.device", "repro.search.readback"}


@pytest.fixture(scope="module")
def world():
    corpus = make_labeled_corpus(jax.random.PRNGKey(3), n=N, d=D, n_labels=L)
    corpus = corpus.replace(
        attrs=jax.random.uniform(jax.random.PRNGKey(4), (N, 1))
    )
    graph = build_index(jax.random.PRNGKey(5), corpus, degree=8,
                        sample_size=64)
    return corpus, graph


def _runtime(world, **kw):
    corpus, graph = world
    kw.setdefault("tiers", make_tier_ladder(k_cap=8, base_ef=16,
                                            base_iters=32, n_tiers=2))
    kw.setdefault("ladder", (4,))
    kw.setdefault("families", ("label", "range"))
    kw.setdefault("max_wait", 0.002)
    rt = ServingRuntime(LocalExecutor(corpus, graph), n_labels=L, **kw)
    rt.warmup()
    return rt


def _host_events(trace_dir):
    """repro.* host events: (line index, name, start, end, args)."""
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert files, "the profiler wrote no xplane"
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((i, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _post(address, payload):
    req = urllib.request.Request(
        address + "/v1/search", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def test_served_search_spans_land_on_the_profiler_timeline(world, tmp_path):
    corpus, _ = world
    rt = _runtime(world)  # wall clock: the production timeline
    fe = ServingFrontend(rt)
    addr = fe.start()
    vectors = np.asarray(corpus.vectors)
    bodies = []
    try:
        with jax.profiler.trace(str(tmp_path)):
            def one(i):
                bodies.append(_post(addr, {
                    "query": vectors[i].tolist(), "k": 4,
                    "family": "label", "labels": [i % L],
                }))

            threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    finally:
        fe.close(drain=True)
    assert len(bodies) == 6 and all(b["error"] is None for b in bodies)
    events = _host_events(tmp_path)
    names = {e[1] for e in events}
    assert HTTP_SPANS | PUMP_SPANS <= names, names

    # Front-end spans carry the runtime's request id, the whole request
    # span its replica too.
    req_ids = {b["req_id"] for b in bodies}
    for name in HTTP_SPANS - {"repro.http.parse"}:
        got = {e[4]["req_id"] for e in events if e[1] == name}
        assert got == req_ids, name
    assert {e[4]["replica"] for e in events if e[1] == "repro.http.search"} == {0}

    # Pump spans carry the batch id; the dispatch says what it ran.
    batch_ids = {b["batch_id"] for b in bodies}
    for name in PUMP_SPANS - {"repro.runtime.step"}:
        got = {e[4]["batch_id"] for e in events if e[1] == name}
        assert batch_ids <= got, name
    for e in events:
        if e[1] == "repro.search.dispatch":
            assert e[4]["bucket"] == 4 and e[4]["family"] == "label"
            assert e[4]["tier"] in (0, 1) and e[4]["cold"] == 0  # warmed
        if e[1] == "repro.runtime.step":
            assert e[4]["n_batches"] >= 1

    # Every search span nests inside a step span of the same thread.
    steps = defaultdict(list)
    for line, name, s, t, _ in events:
        if name == "repro.runtime.step":
            steps[line].append((s, t))
    for line, name, s, t, _ in events:
        if name.startswith("repro.search."):
            assert any(a <= s and t <= b for a, b in steps[line]), (name, s)

    # The front end's counters: one per answered request, none missing.
    c = rt.telemetry.counters
    assert c["http_requests"] == len(bodies)
    for key in ("http_parse_us", "http_admit_us", "http_reply_lag_us",
                "http_reply_us"):
        assert c[key] > 0, key


def test_idle_pump_steps_record_no_span_and_no_counter(world, tmp_path):
    rt = _runtime(world, clock=VirtualClock())
    rt.submit(np.zeros(D, np.float32), 4, "label", label_words_row([0], L))
    rt.drain()
    before = dict(rt.telemetry.counters)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(200):
            rt.clock.advance(rt.batcher.max_wait)
            assert rt.step() == 0
    assert dict(rt.telemetry.counters) == before
    assert _host_events(tmp_path) == []


def test_stage_counts_match_batches_served(world):
    corpus, _ = world
    rt = _runtime(world, clock=VirtualClock())
    items = mixed_workload(5, corpus, 48, L, k_choices=(4, 8))
    responses, rejected = replay_poisson(rt, items, rate=5000.0, seed=2)
    assert rejected == 0 and all(r is not None for r in responses)
    c = rt.telemetry.counters
    assert c["batches"] > 1
    for stage in PUMP_STAGES:
        assert c[stage + "_n"] == c["batches"], stage
        assert c[stage + "_us"] > 0, stage
    # One host turn between each pair of consecutive query batches.
    assert c["host_turn_n"] == c["batches"] - 1
    assert c["host_turn_us"] > 0


@pytest.mark.parametrize("tracing", [True, False], ids=["traced", "untraced"])
def test_queue_wait_counter_sums_the_traces_queue_waits(world, tracing):
    corpus, _ = world
    rt = _runtime(world, clock=VirtualClock(), tracing=tracing,
                  max_wait=0.004)
    items = mixed_workload(9, corpus, 40, L, k_choices=(4, 8))
    responses, rejected = replay_poisson(rt, items, rate=3000.0, seed=4)
    assert rejected == 0 and all(r is not None for r in responses)
    counted = rt.telemetry.counters["queue_wait_us"]
    assert counted > 0  # kept with tracing off too
    if tracing:
        traced = sum(r.trace["queue_wait"] for r in responses)
        assert counted == pytest.approx(1e6 * traced, rel=1e-9)
    else:
        assert all(r.trace is None for r in responses)


def test_execute_stage_is_stamped_from_the_span_boundaries(world, monkeypatch):
    import repro.serving.runtime as runtime_mod

    opened = {}

    class recorded(span):
        def __enter__(self):
            opened[self.name] = self
            return super().__enter__()

    monkeypatch.setattr(runtime_mod, "span", recorded)
    rt = _runtime(world)  # wall clock
    for i in range(4):
        rt.submit(np.full(D, 0.1 * i, np.float32), 4, "label",
                  label_words_row([i % L], L))
    rt.step(force=True)
    resp = [rt.poll(i) for i in range(4)]
    assert all(r is not None for r in resp)
    start = opened["repro.runtime.assemble"].start
    end = opened["repro.search.readback"].end
    for r in resp:
        # [assemble start, read-back end], the same two clock readings.
        assert r.trace["execute"] == end - start
        assert ("executed", end) in r.trace["events"]


def test_span_counts_even_when_the_block_raises():
    counters = defaultdict(float)
    ticks = iter([1.0, 1.25])
    with pytest.raises(RuntimeError):
        with span("repro.test", counters, "stage", clock=lambda: next(ticks)) as sp:
            raise RuntimeError("boom")
    assert (sp.start, sp.end, sp.elapsed) == (1.0, 1.25, 0.25)
    assert counters == {"stage_us": 250000.0, "stage_n": 1}


def test_span_without_counters_only_reads_the_clock():
    sp = span("repro.test", clock=wall_clock, batch_id=3)
    with sp:
        sp.annotate(req_id=9)
    assert sp.end >= sp.start
