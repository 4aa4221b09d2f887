"""Benchmark entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Figures covered:
Fig. 1 (pipeline under-fill), Fig. 3 (constraint families), Fig. 4
(alter_ratio estimation), Fig. 5 (cluster counts), Fig. 6 (MNIST-style
cross-class), plus kernel micro-benches.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default="",
        help="comma list: pipeline,constraints,alter_ratio,clusters,mnist,"
        "kernels,beam,fused,serving,streaming,hybrid,slo,autotune,obs,"
        "replicas",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny shapes + interpret-mode kernels for the suites that "
        "support it (currently: fused, serving, streaming) — the CI mode "
        "exercising the fused pipeline incl. BOTH Pallas kernels (exact "
        "rows and PQ/ADC code rows), the serving runtime's acceptance row "
        "and the streaming churn acceptance row in seconds, without "
        "writing BENCH_*.json artifacts; other suites ignore the flag",
    )
    ap.add_argument(
        "--json-out",
        default="",
        help="also append every suite output line to this file — the "
        "JSON lines are what benchmarks/check_regression.py diffs "
        "against the committed BENCH_*.json smoke references",
    )
    args = ap.parse_args()
    selected = set(filter(None, args.only.split(",")))
    if args.smoke:
        import os

        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from repro.common.jaxcache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_alter_ratio,
        bench_autotune,
        bench_beam,
        bench_clusters,
        bench_constraints,
        bench_fused,
        bench_hybrid,
        bench_kernels,
        bench_mnist_like,
        bench_obs,
        bench_pipeline,
        bench_replicas,
        bench_serving,
        bench_slo,
        bench_streaming,
    )

    suites = {
        "pipeline": bench_pipeline.main,
        "constraints": bench_constraints.main,
        "alter_ratio": bench_alter_ratio.main,
        "clusters": bench_clusters.main,
        "mnist": bench_mnist_like.main,
        "kernels": bench_kernels.main,
        # bench_beam emits one JSON line per (constraint, mode, beam_width)
        # config — machine-readable for BENCH_*.json speedup trajectories.
        "beam": bench_beam.main,
        # bench_fused compares the fused candidate pipeline (ISSUE 2/3)
        # against the unfused path and writes top-level BENCH_PR2.json
        # (exact backend; `--backend pq` standalone writes BENCH_PR3.json).
        # In smoke mode it exercises both interpret kernels regardless.
        "fused": bench_fused.main,
        # bench_serving replays one Poisson mixed workload through the
        # serving runtime vs per-request (batch=1) dispatch and asserts the
        # acceptance row (>=2x QPS, escalation-tier fill, bounded traces);
        # full mode writes top-level BENCH_PR4.json.
        "serving": bench_serving.main,
        # bench_streaming replays a churn stream (inserts/deletes/queries)
        # through the streaming mutable index vs a periodically rebuilt
        # static oracle and asserts the acceptance row (recall gap <= 5
        # pts, ZERO tombstoned ids returned); full mode writes BENCH_PR5.json.
        "streaming": bench_streaming.main,
        # bench_hybrid sweeps constraint selectivity 0.1%-50% and times
        # graph walk vs posting scan vs label overlay vs the strategy
        # router; asserts router within 10% of the best lattice-admissible
        # strategy everywhere, >= 2x over
        # pure graph at <= 1% selectivity at equal recall, bit-exact ids
        # vs the dispatched strategy; full mode writes BENCH_PR6.json.
        "hybrid": bench_hybrid.main,
        # bench_slo replays a burst + fault-schedule workload through the
        # fault-tolerant runtime vs the pre-PR7 no-shedding baseline and
        # asserts the acceptance row (slo goodput > baseline under the
        # burst, zero unmarked late completions, zero lost/hung requests);
        # full mode writes BENCH_PR7.json.
        "slo": bench_slo.main,
        # bench_autotune sweeps the kernel block-shape lattice (PR8): full
        # mode writes the committed tuning table (src/repro/tune/table.json)
        # + BENCH_PR8.json; smoke mode re-times a tiny per-kernel sweep
        # (achieved roofline_fraction, gated vs the committed floor) and
        # re-validates the table's schema/lattice/loader reproducibility.
        "autotune": bench_autotune.main,
        # bench_obs measures the observability layer (PR9): tracing+logging
        # overhead on host wall time vs the untraced runtime, trace
        # completeness (every response's stage breakdown tiles its latency
        # within 1%), and an HTTP replay through ServingFrontend whose
        # scraped /metrics must parse BIT-identical to the in-process
        # Telemetry; full mode writes BENCH_PR9.json.
        "obs": bench_obs.main,
        # bench_replicas boots N shared-nothing streaming replicas behind
        # one HTTP front-end (PR10) and measures goodput/p99/fill scaling
        # vs the 1-replica baseline SOLELY from parsed /metrics scrapes
        # (per-replica virtual execute seconds as the busy denominator);
        # asserts zero lost/hung requests, replica-label cumulativity and
        # one streaming epoch across replicas; full mode (sizes 1/2/4,
        # >= 2.5x at 4 replicas) writes BENCH_PR10.json.
        "replicas": bench_replicas.main,
    }
    print("name,us_per_call,derived")

    json_fh = open(args.json_out, "a") if args.json_out else None

    def out(line: str) -> None:
        print(line, flush=True)
        if json_fh is not None:
            json_fh.write(line + "\n")
            json_fh.flush()

    failed = []
    for name, fn in suites.items():
        if selected and name not in selected:
            continue
        t0 = time.time()
        try:
            fn(out)
        except Exception as e:  # noqa: BLE001 — keep the suite running
            out(f"{name}/ERROR,0,{type(e).__name__}:{str(e)[:120]}")
            failed.append(name)
        print(f"# suite {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if json_fh is not None:
        json_fh.close()
    if failed:
        # Later suites still ran, but the process must fail so CI's smoke
        # step actually gates on the benchmarked code paths.
        print(f"# FAILED suites: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
