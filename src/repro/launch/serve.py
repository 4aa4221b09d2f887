"""Online serving driver: Poisson-arrival mixed constrained workload.

Thin front over the serving runtime (repro.serving, DESIGN.md §7): builds
an index, then streams individual constrained queries — each with its own
k, constraint family/operand (equal / unequal-X% label sets and numeric
ranges in one stream), and Poisson arrival time — through the dynamic
batcher, shape-bucketed compile cache, and adaptive escalation controller,
and prints the telemetry summary (QPS, latency percentiles, fill, cache
hit rate).

Reduced CPU run:
    PYTHONPATH=src python -m repro.launch.serve --n 20000 --requests 256

Distributed path (scatter-search-merge over the mesh) and PQ/ADC traversal:
    PYTHONPATH=src python -m repro.launch.serve --distributed --approx pq

HTTP front-end (DESIGN.md §12) — wall-clock runtime behind a real socket:
    PYTHONPATH=src python -m repro.launch.serve --serve-http 8080 \
        --log-json serve_log.jsonl
    curl -s localhost:8080/metrics | head

Multi-replica tier (DESIGN.md §13) — N shared-nothing runtimes behind one
front-end, hash- or load-routed, with /metrics labeled per replica:
    PYTHONPATH=src python -m repro.launch.serve --serve-http 8080 \
        --replicas 4 --router hash
"""
from __future__ import annotations

import argparse
import json
import threading

import jax

from repro.common.jaxcache import enable_compile_cache
from repro.data.synthetic import make_labeled_corpus
from repro.graph.index import build_index, build_partitioned_index
from repro.serving import (
    LocalExecutor,
    ServingRuntime,
    VirtualClock,
    make_tier_ladder,
    mixed_workload,
    replay_poisson,
)


def build_runtime(args, corpus, clock, prebuilt_graph=None, replica_id=None):
    """Executor + runtime for either the local or the distributed path.

    ``prebuilt_graph`` shares one (read-only) static graph build across
    replicas; each replica still gets its OWN executor, compile cache and
    (for churn) its own mutable ``StreamingIndex`` slot pool —
    shared-nothing everywhere state can change."""

    def train_pq(vectors):
        # Codes are row-aligned with the corpus the executor serves, so the
        # distributed path trains on the PARTITIONED (padded) corpus.
        from repro.core import pq_train
        from repro.core.pq import default_m_sub

        m_sub = default_m_sub(args.d)
        print(f"training PQ codebooks (m_sub={m_sub})...")
        return pq_train(jax.random.PRNGKey(4), vectors, m_sub=m_sub, n_cent=256)

    if args.churn > 0:
        if args.distributed or args.approx == "pq":
            raise SystemExit(
                "--churn serves through the streaming local executor "
                "(exact backend); drop --distributed/--approx pq"
            )
        from repro.serving import StreamingLocalExecutor
        from repro.streaming import StreamingIndex

        print("building streaming index (slot pool)...")
        graph = prebuilt_graph if prebuilt_graph is not None else build_index(
            jax.random.PRNGKey(1), corpus, degree=args.degree,
            sample_size=args.sample_size,
        )
        index = StreamingIndex.from_static(
            corpus, graph, ef_insert=args.base_ef
        )
        executor = StreamingLocalExecutor(
            index, consolidate_after=args.consolidate_after
        )
    elif args.distributed:
        from repro.core import shard_corpus_for_mesh
        from repro.serving import DistributedExecutor

        n_dev = jax.device_count()
        model = min(4, n_dev)
        data = n_dev // model
        mesh = jax.make_mesh((data, model), ("data", "model"))
        print(f"mesh: {dict(mesh.shape)}")
        print("building partitioned index...")
        corpus_p, graph_p = build_partitioned_index(
            jax.random.PRNGKey(1), corpus, n_shards=model, degree=args.degree,
            sample_size_per_shard=128,
        )
        corpus_s, graph_s = shard_corpus_for_mesh(corpus_p, graph_p, mesh)
        pq_index = train_pq(corpus_p.vectors) if args.approx == "pq" else None
        executor = DistributedExecutor(mesh, corpus_s, graph_s, pq_index)
    else:
        if prebuilt_graph is not None:
            graph = prebuilt_graph
        else:
            print("building index...")
            graph = build_index(
                jax.random.PRNGKey(1), corpus, degree=args.degree,
                sample_size=args.sample_size,
            )
        pq_index = train_pq(corpus.vectors) if args.approx == "pq" else None
        executor = LocalExecutor(corpus, graph, pq_index)

    tiers = make_tier_ladder(
        k_cap=args.k_cap,
        base_ef=args.base_ef,
        base_iters=args.base_iters,
        n_tiers=2,
    )
    if args.approx == "pq" or args.fuse != "auto":
        import dataclasses

        tiers = tuple(
            dataclasses.replace(t, approx=args.approx, fuse_expand=args.fuse)
            for t in tiers
        )
    if args.inject_faults > 0:
        from repro.serving import (
            FaultClock,
            FaultConfig,
            FaultSchedule,
            FaultyExecutor,
        )

        fault_clock = FaultClock(clock)
        schedule = FaultSchedule(FaultConfig(
            seed=21,
            error_rate=args.inject_faults,
            spike_rate=args.inject_faults,
            spike_s=(args.deadline_ms / 2000.0) if args.deadline_ms > 0
            else 0.05,
            stale_epoch_rate=args.inject_faults if args.churn > 0 else 0.0,
        ))
        executor = FaultyExecutor(executor, schedule, fault_clock)
        clock = fault_clock

    slo_cfg = None
    if args.slo:
        from repro.serving import SLOConfig

        slo_cfg = SLOConfig(
            target_latency=(args.deadline_ms / 1000.0)
            if args.deadline_ms > 0 else 0.05,
            queue_high=max(8, args.max_pending // 4),
            queue_low=max(4, args.max_pending // 16),
        )

    ladder = tuple(int(b) for b in args.ladder.split(","))
    runtime = ServingRuntime(
        executor,
        n_labels=args.labels,
        tiers=tiers,
        ladder=ladder,
        families=("label", "range"),
        max_wait=args.max_wait,
        max_pending=args.max_pending,
        clock=clock,
        slo=slo_cfg,
        shed_expired=args.slo,
        replica_id=replica_id,
    )
    if args.hybrid:
        if args.distributed:
            raise SystemExit(
                "--hybrid needs host-side posting lists; the distributed "
                "executor is graph-only for now (drop --distributed)"
            )
        from repro.serving import make_serving_router

        runtime.router = make_serving_router(
            executor, n_labels=args.labels, controller=runtime.controller
        )
    return runtime


def make_corpus(args):
    """The served corpus: ``args.n`` clustered ``args.d``-dim vectors with
    k-means labels (the paper's protocol) and two uniform attribute columns
    for range constraints."""
    corpus = make_labeled_corpus(
        jax.random.PRNGKey(0), n=args.n, d=args.d, n_labels=args.labels
    )
    return corpus.replace(
        attrs=jax.random.uniform(jax.random.PRNGKey(5), (args.n, 2))
    )


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--labels", type=int, default=10)
    ap.add_argument("--degree", type=int, default=16,
                    help="proximity-graph out-degree")
    ap.add_argument("--sample-size", type=int, default=512,
                    help="pre-drawn start-point sample (AIRSHIP-Start)")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="Poisson arrival rate (requests/s of virtual time)")
    ap.add_argument("--k-cap", type=int, default=16)
    ap.add_argument("--ladder", default="8,32,128",
                    help="comma batch-bucket ladder")
    ap.add_argument("--base-ef", type=int, default=64)
    ap.add_argument("--base-iters", type=int, default=128,
                    help="tier-0 max_iters (escalation tier gets 4x)")
    ap.add_argument("--max-wait", type=float, default=0.005,
                    help="batcher flush timeout (s)")
    ap.add_argument("--max-pending", type=int, default=1024,
                    help="admission-queue bound (backpressure)")
    ap.add_argument("--distributed", action="store_true",
                    help="serve through the scatter-search-merge mesh path")
    ap.add_argument("--churn", type=float, default=0.0,
                    help="fraction of the stream that is upsert/delete "
                    "traffic against the streaming mutable index (0 = "
                    "static index; try 0.3 to replay the churn workload)")
    ap.add_argument("--consolidate-after", type=int, default=64,
                    help="pending tombstones that trigger a background "
                    "consolidation pass at the next flush boundary")
    ap.add_argument(
        "--approx", default="exact", choices=("exact", "pq"),
        help="distance backend for the walk: exact rows or PQ/ADC codes "
        "(trains a PQ index on the corpus; exact re-rank post-loop)",
    )
    ap.add_argument(
        "--hybrid", action="store_true",
        help="selectivity-adaptive execution (DESIGN.md §9): a per-query "
        "strategy router estimates constraint selectivity from incremental "
        "histograms and dispatches each request to the graph walk, a "
        "brute-force posting-set scan, or a cached label-subgraph overlay",
    )
    ap.add_argument(
        "--slo", action="store_true",
        help="fault-tolerant serving under SLO (DESIGN.md §10): expired "
        "requests are shed at flush time instead of served late, and a "
        "hysteretic degradation ladder caps tiers / prefers cheap "
        "strategies / predictively sheds as overload deepens",
    )
    ap.add_argument(
        "--deadline-ms", type=float, default=0.0,
        help="per-query deadline in virtual-time milliseconds (0 = no "
        "deadline); with --slo, expired requests are shed with a pollable "
        "shed_reason instead of completing late",
    )
    ap.add_argument(
        "--inject-faults", type=float, default=0.0,
        help="seeded fault-injection rate (per compiled dispatch: this "
        "probability each of an executor error and a latency spike; with "
        "--churn also a stale-epoch rate per refresh). Exercises the "
        "retry-within-budget and failed-Response recovery paths",
    )
    ap.add_argument(
        "--fuse", default="auto", choices=("auto", "on", "off"),
        help="fused candidate pipeline (kernels/fused_expand; 'on' forces "
        "the one-pass gather+distance+constraint+visited kernel for either "
        "backend, applied to every serving tier)",
    )
    ap.add_argument(
        "--serve-http", type=int, default=None, metavar="PORT",
        help="instead of replaying a synthetic stream, serve over HTTP "
        "(DESIGN.md §12): POST /v1/search /v1/upsert /v1/delete, GET "
        "/metrics (Prometheus text), /healthz, /varz. Runs on the wall "
        "clock; Ctrl-C drains in-flight work and exits. Port 0 picks a "
        "free port",
    )
    ap.add_argument(
        "--replicas", type=int, default=1, metavar="N",
        help="shared-nothing runtime replicas behind the HTTP front-end "
        "(DESIGN.md §13): each gets its own compile cache, controller, "
        "batcher, pump thread, and (with --churn) slot pool; mutations "
        "broadcast to all at one enqueue boundary. Needs --serve-http",
    )
    ap.add_argument(
        "--router", default="hash", choices=("hash", "least-loaded"),
        help="replica router: consistent-hash by request key (compile-"
        "cache affinity, deterministic) or least-loaded by pending depth",
    )
    ap.add_argument(
        "--log-json", default=None, metavar="PATH",
        help="structured JSON request logs (admit/dispatch/complete/shed "
        "records with req_id/batch_id/epoch) buffered in a bounded ring "
        "and flushed to PATH at shutdown",
    )
    return ap


def main():
    args = make_parser().parse_args()
    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    if args.replicas > 1 and args.serve_http is None:
        raise SystemExit("--replicas N needs --serve-http (the replica "
                         "tier lives behind the HTTP front-end)")
    if args.replicas > 1 and args.distributed:
        raise SystemExit("--replicas replicates the local executor; the "
                         "mesh path is single-tier (drop --distributed)")

    enable_compile_cache()
    corpus = make_corpus(args)

    # HTTP mode serves real clients, so it runs on the wall clock; replay
    # mode keeps the deterministic virtual timeline.
    if args.serve_http is not None:
        from repro.serving import wall_clock

        clock = wall_clock
    else:
        clock = VirtualClock()
    if args.replicas > 1:
        from repro.serving import ReplicaSet, make_replica_router

        print(f"building index (shared across {args.replicas} replicas)...")
        shared_graph = build_index(
            jax.random.PRNGKey(1), corpus, degree=args.degree,
            sample_size=args.sample_size,
        )
        runtime = ReplicaSet(
            [
                build_runtime(
                    args, corpus, clock,
                    prebuilt_graph=shared_graph, replica_id=i,
                )
                for i in range(args.replicas)
            ],
            router=make_replica_router(args.router, args.replicas),
        )
        trace_budget = runtime.replicas[0].trace_budget
    else:
        runtime = build_runtime(args, corpus, clock)
        trace_budget = runtime.trace_budget
    logger = None
    if args.log_json is not None:
        from repro.obs import JsonLogger

        # Single-runtime path keeps the runtime's own clock (build_runtime
        # may have wrapped it in a FaultClock); tier children bind their
        # replica's clock in attach_logger.
        logger = JsonLogger(
            clock=clock if args.replicas > 1 else runtime.clock
        )
        if args.replicas > 1:
            runtime.attach_logger(logger)
        else:
            runtime.logger = logger
    print(f"warming compile cache ({trace_budget} bucket shapes"
          + (f" x {args.replicas} replicas" if args.replicas > 1 else "")
          + ")...")
    compiled = runtime.warmup()

    if args.serve_http is not None:
        import signal

        from repro.obs.http import ServingFrontend

        frontend = ServingFrontend(runtime, logger=logger, port=args.serve_http)
        addr = frontend.start()
        print(f"compiled {compiled} closures; serving on {addr}")
        print(f"replicas: {frontend.n_replicas} (router "
              f"{runtime.router.name if args.replicas > 1 else 'n/a'})")
        print("routes: POST /v1/search /v1/upsert /v1/delete | "
              "GET /metrics /healthz /varz "
              "(SIGINT/SIGTERM drains and exits)")
        # Explicit handlers: a supervisor (or a non-interactive shell that
        # spawned us with SIGINT ignored) sends SIGTERM — both signals must
        # take the same graceful drain-and-flush path as a TTY Ctrl-C.
        stop = threading.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            signal.signal(sig, lambda *_: stop.set())
        stop.wait()
        print("draining...")
        report = frontend.close(drain=True, log_path=args.log_json)
        print(json.dumps({"shutdown": report}, indent=2))
        return

    print(f"compiled {compiled} closures; serving {args.requests} requests "
          f"at Poisson rate {args.rate}/s...")

    k_choices = tuple(sorted({min(4, args.k_cap), min(8, args.k_cap),
                              args.k_cap}))
    deadline_s = args.deadline_ms / 1000.0 if args.deadline_ms > 0 else None
    retry = None
    if args.slo:
        from repro.serving import RetryPolicy

        retry = RetryPolicy()  # backpressure rejections retry with backoff
    if args.churn > 0:
        from repro.serving import churn_workload, replay_churn

        items = churn_workload(
            7, corpus, args.requests, args.labels,
            mutation_frac=args.churn, k_choices=k_choices,
        )
        responses, rejected = replay_churn(
            runtime, items, rate=args.rate, seed=11,
            deadline_s=deadline_s, retry=retry,
        )
    else:
        items = mixed_workload(
            7, corpus, args.requests, args.labels, k_choices=k_choices,
        )
        responses, rejected = replay_poisson(
            runtime, items, rate=args.rate, seed=11,
            deadline_s=deadline_s, retry=retry,
        )

    report = runtime.report()
    print(json.dumps(report, indent=2, default=str))
    served = [r for r in responses if r is not None]
    mean_fill = (
        sum(r.fill_frac for r in served) / len(served) if served else 0.0
    )
    print(
        f"served {len(served)}/{len(items)} requests "
        f"({rejected} rejected by backpressure) | "
        f"qps {report['telemetry'].get('qps', 0)} | mean fill {mean_fill:.3f} "
        f"| cache hit rate {report['cache']['hit_rate']} "
        f"(single-core host; see EXPERIMENTS.md §Roofline for TPU projection)"
    )
    if args.slo or args.inject_faults > 0:
        counters = report["telemetry"]  # summary() flattens the counters
        goodput = sum(
            1 for r in served
            if r.ok and not r.deadline_missed and r.filled > 0
        )
        print(
            f"slo: goodput {goodput} | shed {counters.get('shed_total', 0)} "
            f"(expired {counters.get('shed_expired', 0)}, overload "
            f"{counters.get('shed_overload', 0)}) | "
            f"failed {counters.get('failed', 0)} | "
            f"fault retries {counters.get('fault_retries', 0)} | "
            f"degradation level {runtime.controller.degradation_level}"
        )
    if logger is not None:
        n = logger.flush_to_path(args.log_json)
        print(f"flushed {n} structured log records to {args.log_json}")


if __name__ == "__main__":
    main()
