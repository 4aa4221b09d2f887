"""The readers of the program's host-time counters: exact means on
hand-made counters, and nothing where the program keeps no such counter
(a program older than its spans)."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from chipbench_tiny import ROOT  # puts the benchmark on the path

from chipbench import spec


def _read(metric, counters):
    return spec.load_reader(ROOT, metric)(SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters, want", [
    ({"host_turn_us": 291000.0, "host_turn_n": 3}, 97.0),
    ({"host_turn_us": 1500.0, "host_turn_n": 1, "batches": 9}, 1.5),
    ({}, None),
    ({"host_turn_us": 0.0, "host_turn_n": 0}, None),
])
def test_host_turn_ms_is_the_mean_turn(counters, want):
    assert _read("host_turn_ms.bulk", counters) == want


@pytest.mark.parametrize("counters, want", [
    ({"http_requests": 4, "http_parse_us": 1000.0, "http_admit_us": 2000.0,
      "http_reply_lag_us": 13000.0, "http_reply_us": 4000.0}, 5.0),
    ({"http_requests": 2, "http_parse_us": 500.0}, 0.25),
    ({"completed": 5}, None),
    ({}, None),
])
def test_frontend_ms_is_the_mean_front_end_time(counters, want):
    assert _read("frontend_ms.bulk", counters) == want
