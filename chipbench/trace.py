"""Reduction of a profiler trace to device busy time, search-program time
and the host's part in the device's idle gaps.

The benchmark marks its own host spans with ``jax.profiler.TraceAnnotation``:

* ``chipbench.traced`` -- the traced window itself;
* ``chipbench.search`` (with ``seq``) -- one executor search call, from
  dispatch to the result being ready, so the device work of that batch
  falls inside it;
* ``chipbench.request`` -- one client request, from send to reply.

Device work is read from each TPU plane's ``XLA Ops`` line (or ``XLA
Modules`` where a trace has no op line). Busy time is the union of those
intervals; a search's device time is the part of the union inside its span.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
SEARCH, REQUEST, TRACED = "chipbench.search", "chipbench.request", "chipbench.traced"
GAP_SEARCH = "search dispatch and read-back"
GAP_HOST = "batcher or HTTP host work"
GAP_IDLE = "waiting for arrivals"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    seq: Optional[int] = None

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(path: str) -> List[Event]:
    """The events the reduction reads, from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            if device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            for e in line.events:
                if host and not e.name.startswith("chipbench."):
                    continue
                if not (device or host):
                    continue
                seq = dict(e.stats).get("seq") if host else None
                # an op's event name is its whole HLO instruction; keep the name
                name = e.name.split(" = ", 1)[0]
                events.append(Event(plane.name, line.name, name, float(e.start_ns),
                                    float(e.duration_ns), None if seq is None else int(seq)))
    return events


def union(intervals) -> List[tuple]:
    """Merge (start, end) intervals into sorted disjoint ones."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def covered(merged: List[tuple], s: float, e: float) -> float:
    """Length of [s, e] covered by sorted disjoint ``merged`` intervals."""
    i = max(bisect.bisect_right(merged, (s, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < e:
        total += max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return total


def _device_intervals(events: List[Event], lo: float, hi: float) -> Dict[str, list]:
    by_plane: Dict[str, Dict[str, list]] = defaultdict(lambda: defaultdict(list))
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.end_ns > lo and e.start_ns < hi:
            by_plane[e.plane][e.line].append(e)
    return {p: lines.get(OP_LINE) or lines.get(MODULE_LINE, [])
            for p, lines in sorted(by_plane.items())}


def _inside(merged: List[tuple], t: float) -> bool:
    i = bisect.bisect_right(merged, (t, float("inf"))) - 1
    return i >= 0 and merged[i][0] <= t < merged[i][1]


def _attribute(s: float, e: float, search_u: List[tuple], request_u: List[tuple],
               bounds: List[float]) -> list:
    """Split the idle gap [s, e] by what the host was doing: inside a
    search call, else inside a client request, else nothing. ``bounds``
    are the sorted ends of both span lists."""
    inner = bounds[bisect.bisect_right(bounds, s): bisect.bisect_left(bounds, e)]
    cuts = sorted({s, e, *inner})
    pieces: list = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        name = (GAP_SEARCH if _inside(search_u, mid)
                else GAP_HOST if _inside(request_u, mid) else GAP_IDLE)
        if pieces and pieces[-1][0] == name:
            pieces[-1][1] += (b - a) / 1e9
        else:
            pieces.append([name, (b - a) / 1e9])
    return pieces


def reduce_trace(events: List[Event], top: int = 10) -> dict:
    """-> window_s, busy_s (mean over devices), idle_share, per-search
    device seconds by ``seq``, and the breakdown lists."""
    windows = [e for e in events if e.name == TRACED]
    if not windows:
        raise ValueError("trace holds no chipbench.traced span")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    per_device = _device_intervals(events, lo, hi)
    if not per_device:
        raise ValueError("trace holds no device operations")
    merged = {p: union((max(e.start_ns, lo), min(e.end_ns, hi)) for e in evs)
              for p, evs in per_device.items()}
    busy = [sum(e - s for s, e in m) for m in merged.values()]
    window_ns = hi - lo
    first = next(iter(merged.values()))

    searches = {}
    search_spans, request_spans = [], []
    for e in events:
        if e.start_ns < lo or e.end_ns > hi:
            continue
        if e.name == SEARCH:
            search_spans.append((e.start_ns, e.end_ns))
            searches[e.seq] = sum(covered(m, e.start_ns, e.end_ns)
                                  for m in merged.values()) / len(merged) / 1e9
        elif e.name == REQUEST:
            request_spans.append((e.start_ns, e.end_ns))

    op_time: Dict[str, float] = defaultdict(float)
    for evs in per_device.values():
        for e in evs:
            op_time[e.name] += (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
    device_ops = sorted(([n, t / len(per_device)] for n, t in op_time.items()),
                        key=lambda x: -x[1])[:top]

    search_u, request_u = union(search_spans), union(request_spans)
    bounds = sorted(x for iv in search_u + request_u for x in iv)
    gaps = []
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        gaps.extend(_attribute(s, e, search_u, request_u, bounds))
    gaps.sort(key=lambda x: -x[1])
    busy_s = sum(busy) / len(busy) / 1e9
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9),
        "searches": searches,
        "breakdown": {"device_ops": device_ops, "idle_gaps": gaps[:top]},
    }
