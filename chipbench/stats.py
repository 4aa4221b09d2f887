"""Metric arithmetic over what the clients recorded."""
from __future__ import annotations

import math
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The q-quantile (0 < q <= 1) by nearest rank: a value that was read."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[max(math.ceil(q * len(ordered)) - 1, 0)]


def latencies_from_due(records, give_up: float) -> list:
    """Seconds from each request's due time to its answer. A request with
    no answer counts as slower than every answered one: its latency runs to
    ``give_up`` (when the clients stopped waiting), or past the slowest
    answer where that is later."""
    served = [r.done - r.due for r in records if r.answered]
    slowest = max(served, default=0.0)
    return served + [max(give_up - r.due, slowest) + 1e-6
                     for r in records if not r.answered]


def p95_ms(records, give_up: float) -> float:
    return 1e3 * nearest_rank(latencies_from_due(records, give_up), 0.95)


def qps(records, t0: float, seconds: float) -> float:
    """Requests answered inside the window, over the window's length."""
    end = t0 + seconds
    return sum(1 for r in records if r.answered and t0 <= r.done <= end) / seconds


def drain_qps(records, t0: float, give_up: float) -> float:
    """Requests answered over the time from the window's start to the last
    answer: every request sent in the window, and all the time it took to
    answer them. A request with no answer keeps the clock running to
    ``give_up``."""
    ends = [r.done for r in records if r.answered]
    if len(ends) < len(records):
        ends.append(give_up)
    return len([r for r in records if r.answered]) / (max(ends) - t0)


def median(values: Sequence[float]) -> float:
    return nearest_rank(values, 0.5)
