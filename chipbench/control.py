#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 chipbench/control.py --workload sift1m-exact.steady \\
        --seeds 11,12,13 --seconds 51

For each seed, in one process: the cell's data and requests are made as
``run.py`` makes them, and the control answers those requests in the
program's place: the plain reference itself, computed one precision below
what the configuration states (``reference.py``, ``"high"``). Its answers
go through the same comparison as a run's, and have to fail a limit. The
program's own readings are those of ``run.py``'s runs. Prints one line per
seed; the last line is a JSON object of all readings. Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import reference, run, spec  # noqa: E402
from chipbench.traffic import open_loop_times  # noqa: E402


def control_answers(data, items, k_cap: int) -> list:
    """The control in the program's place: (ids, dists, filled) per item."""
    ids, dists, _ = reference.constrained_topk(data, items, k_cap, precision="high")
    out = []
    for item, i, d in zip(items, ids, dists):
        i, d = i[: item.k], d[: item.k]
        out.append((i.astype(np.int64), d.astype(np.float64), int((i >= 0).sum())))
    return out


def readings(cell: spec.Cell, seed: int, seconds: float, requests: int = 0) -> dict:
    """The control's numbers for one seed, on the requests a run of the
    cell draws: an open loop's whole schedule, or the first ``requests``
    of a closed loop's pool (all of it where ``requests`` is 0), the ones
    its callers send first."""
    from chipbench.data import Dataset

    mix, cfg, serve = cell.traffic, cell.config, run.serve_args(cell)
    data = Dataset(seed, cfg)
    n = (len(open_loop_times(seed, mix["rate_qps"], seconds)) if mix["loop"] == "open"
         else requests or mix["pool"])
    items = cell.ops.make_items(seed, data, n, mix)
    answers = control_answers(data, items, serve.k_cap)
    return {"seed": seed, "requests": n,
            "control": cell.ops.compare(data, items, answers, cfg["checks"], serve)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--requests", type=int, default=0,
                    help="closed loop: how many of the pool's requests to answer")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    run.enable_cache(ROOT / run.CACHE_DIR)
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, seed, args.seconds, args.requests)
        print(f"readings seed {seed} control: "
              + json.dumps({k: v["value"] for k, v in r["control"].items()}), flush=True)
        out.append(r)
    print(json.dumps({"workload": cell.name, "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
