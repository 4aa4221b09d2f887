#!/usr/bin/env python3
"""Sweep of offered load for an open-loop cell: the knee.

    python3 chipbench/knee.py --workload sift1m-exact.steady --seed 7 \\
        --rates 12,16,20,24,28 --seconds 20

Sets the cell up once, then offers each rate for ``--seconds`` (Poisson,
the cell's mix) and reports answered queries per second, latency from the
due time, and whether the backlog grew: the mean latency of the last
quarter of requests against the first, and the slope of latency against
due time (seconds of latency gained per second of window). The knee is the
highest rate that is answered in full with a slope under 0.1; the cell's
mix file keeps it as ``knee_qps`` and offers 0.8 of it. Needs a TPU, like
``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import run, spec, stats  # noqa: E402
from chipbench.traffic import open_loop_times  # noqa: E402


def sweep(dep, cell: spec.Cell, seed: int, rates, seconds: float) -> list:
    mix = cell.traffic
    rows = []
    for i, rate in enumerate(rates):
        m = {**mix, "rate_qps": rate}
        n = len(open_loop_times(seed + i, rate, seconds))
        items = cell.ops.make_items(seed + i, dep.data, n, m, stream=10 + i)
        clients = dep.clients(items, m, seed + i, seconds)
        win = dep.run(clients)
        clients.close()
        done = [r for r in win.records if r.answered]
        lat = [r.done - r.due for r in done]
        q = max(len(lat) // 4, 1)
        slope = (float(np.polyfit([r.due - win.t0 for r in done], lat, 1)[0])
                 if len(done) > 2 else None)
        row = {
            "rate": rate,
            "sent": len(win.records),
            "answered_in_window_qps": stats.qps(win.records, win.t0, seconds),
            "answered": len(lat),
            "p50_ms": 1e3 * float(np.median(lat)) if lat else None,
            "p95_ms": stats.p95_ms(win.records, win.give_up),
            "first_quarter_ms": 1e3 * float(np.mean(lat[:q])) if lat else None,
            "last_quarter_ms": 1e3 * float(np.mean(lat[-q:])) if lat else None,
            "slope": slope,
            "batches": win.counters.get("batches", 0),
            "escalations": win.counters.get("escalations", 0),
        }
        print("knee " + json.dumps(row), flush=True)
        rows.append(row)
        time.sleep(2.0)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma list of offered queries/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 3
    run.enable_cache(ROOT / run.CACHE_DIR)
    dep = run.Deployment(cell, args.seed)
    rows = sweep(dep, cell, args.seed, [float(r) for r in args.rates.split(",")],
                 args.seconds)
    dep.close()
    print(json.dumps({"workload": cell.name, "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
