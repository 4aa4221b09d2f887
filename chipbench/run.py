#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data from the seed on the device, builds the index
and the serving runtime through the program's own entry points
(``graph.index.build_index``, ``launch/serve.py``'s ``build_runtime``, with
the serve flags that the configuration and the mix hold), warms up its
compiled closures, starts ``obs.http.ServingFrontend`` on a local socket
in this process and sends a few requests through it. The window then sends
the cell's traffic over HTTP for ``--seconds``, and after it every answer
is compared with the plain reference by the mix's operations module.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a few seconds of the window are profiled and it carries the
per-layer metrics, the device's busy and window seconds and a breakdown.
Earlier lines report set-up phases, compiles in the window and how late
the clients sent; the last lines on standard error give each number
compared with its limit, and the last line on standard output is the
result. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import check, spec  # noqa: E402

GRACE_S = 60.0  # how long past the window's close a reply is awaited
CACHE_DIR = Path("chipbench") / ".jax_cache"
TRACE_DIR = Path("chipbench") / ".trace"


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class SearchCall:
    """One executor search call in the window."""

    seq: int
    t0: float
    t1: float
    bucket: int
    rerank_rows: int
    stats: object  # the program's SearchStats, on the device until collected
    n_real: Optional[int] = None
    iters: int = 0
    dist_evals: np.ndarray = None
    hops: np.ndarray = None

    def collect(self) -> None:
        self.iters = int(self.stats.iters)
        self.dist_evals = np.asarray(self.stats.dist_evals)
        self.hops = np.asarray(self.stats.hops)
        self.n_real = self.bucket if self.n_real is None else self.n_real
        self.stats = None


class Recorder:
    """Wraps each runtime's executor. With ``record`` (traced runs only):
    a ``chipbench.search`` span from dispatch to ready around each search
    call, and the batch's ``SearchStats`` while ``on``. ``fault`` (tests
    only) breaks each result where it is produced. A timed run installs
    none of this: its path is the program's own."""

    def __init__(self, runtimes, record: bool, fault: Optional[Callable] = None):
        self.on = False
        self.calls: List[SearchCall] = []
        self._seq = 0
        for rt in runtimes:
            self._wrap(rt, record, fault)

    def _wrap(self, runtime, record: bool, fault: Optional[Callable]) -> None:
        import jax

        executor, build = runtime.executor, runtime.executor.build

        def wrapped_build(bucket, family, params):
            fn = build(bucket, family, params)
            rerank = params.result_capacity if params.approx == "pq" else 0

            def search(queries, constraint):
                if not record:
                    return fault(fn(queries, constraint))
                seq, self._seq = self._seq, self._seq + 1
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("chipbench.search", seq=seq):
                    res = jax.block_until_ready(fn(queries, constraint))
                t1 = time.perf_counter()
                if fault is not None:
                    res = fault(res)
                if self.on:
                    self.calls.append(SearchCall(seq, t0, t1, bucket, rerank, res.stats))
                return res

            return search

        executor.build = wrapped_build
        if not record:
            return
        on_dispatch = runtime.telemetry.on_dispatch

        def dispatched(bucket, n_real):
            on_dispatch(bucket, n_real)
            if self.on and self.calls and self.calls[-1].n_real is None:
                self.calls[-1].n_real = n_real

        runtime.telemetry.on_dispatch = dispatched


class CompileCounter:
    """Counts programs lowered and compiled while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.lowered = 0
        self.compiled = 0

        def listen(event, duration, **kwargs):
            if not self.on:
                return
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lowered += 1
            elif event == "/jax/core/compile/backend_compile_duration":
                self.compiled += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


@dataclasses.dataclass
class RunContext:
    """What a metric's reader reads."""

    config: dict
    serve: argparse.Namespace  # the program's serve flags
    phases: dict  # seconds of each set-up phase
    records: list
    t0: float
    seconds: float
    give_up: float
    setup_s: float
    counters: dict
    searches: List[SearchCall]
    checks: dict
    peaks: Optional[dict] = None
    trace: Optional[dict] = None

    def traced_searches(self) -> list:
        """(call, device seconds) of each search inside the traced window."""
        if not self.trace:
            return []
        by_seq = {c.seq: c for c in self.searches}
        return [(by_seq[s], t) for s, t in self.trace["searches"].items() if s in by_seq]


def serve_args(cell: spec.Cell) -> argparse.Namespace:
    """The program's serve flags for this cell, parsed by its own parser."""
    from repro.launch.serve import make_parser

    return make_parser().parse_args(cell.serve_flags())


def build_serving(args: argparse.Namespace, corpus, graph):
    """What ``launch/serve.py``'s main builds for these flags: one runtime,
    or ``--replicas`` of them sharing one graph behind the replica router.
    The flags choose the executor (local, ``--distributed`` over every chip
    of the host, ``--churn``'s streaming one) and the rest."""
    from repro.launch.serve import build_runtime
    from repro.serving import wall_clock

    if args.replicas > 1:
        from repro.serving import ReplicaSet, make_replica_router

        return ReplicaSet(
            [build_runtime(args, corpus, wall_clock, prebuilt_graph=graph, replica_id=i)
             for i in range(args.replicas)],
            router=make_replica_router(args.router, args.replicas))
    return build_runtime(args, corpus, wall_clock, prebuilt_graph=graph)


def enable_cache(cache_dir: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` already places it."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@dataclasses.dataclass
class Window:
    """What one window of traffic left behind."""

    records: list
    t0: float
    give_up: float
    counters: dict
    searches: List[SearchCall]


class Deployment:
    """The cell's system under test, built from the seed through the
    program's entry points and serving over HTTP in this process."""

    def __init__(self, cell: spec.Cell, seed: int, record: bool = False,
                 fault: Optional[Callable] = None):
        import jax

        from chipbench.data import Dataset
        from chipbench.traffic import send_each
        from repro.core.types import Corpus
        from repro.graph.index import build_index
        from repro.obs.http import ServingFrontend

        cfg, mix = cell.config, cell.traffic
        self.args = args = serve_args(cell)
        self.phases = {"start": time.perf_counter() - T_START}

        def phase(name, t):
            self.phases[name] = time.perf_counter() - t
            log(f"setup {name}: {self.phases[name]:.3f} s")

        t = time.perf_counter()
        self.data = Dataset(seed, cfg)
        phase("data", t)
        t = time.perf_counter()
        corpus = Corpus(vectors=self.data.vectors, labels=self.data.labels_dev,
                        attrs=self.data.attrs_dev)
        graph = None
        if not args.distributed:  # the mesh path partitions and builds its own
            graph = jax.block_until_ready(build_index(
                jax.random.fold_in(jax.random.PRNGKey(seed), 1), corpus,
                degree=args.degree, sample_size=args.sample_size,
            ))
            phase("graph", t)
        t = time.perf_counter()
        self.runtime = build_serving(args, corpus, graph)
        self.runtimes = getattr(self.runtime, "replicas", [self.runtime])
        jax.block_until_ready([getattr(rt.executor, "pq_index", None) for rt in self.runtimes])
        phase("runtime", t)
        self.recorder = (Recorder(self.runtimes, record, fault)
                         if record or fault is not None else None)
        self.counter = CompileCounter()
        t = time.perf_counter()
        self.closures = self.runtime.warmup()
        phase("warmup", t)
        t = time.perf_counter()
        self.frontend = ServingFrontend(self.runtime, port=0)
        addr = self.frontend.start()
        self.host, port = addr.rsplit("//", 1)[1].split(":")
        self.port = int(port)
        warm = send_each(self.host, self.port, cell.ops.warm_items(seed, self.data, mix),
                         mix["request_timeout_s"])
        failed = [r.body for r in warm if not r.answered]
        if failed:
            raise RuntimeError(f"warm-up requests failed: {failed}")
        phase("warm_requests", t)

    def counters(self) -> dict:
        """The runtimes' telemetry counters, summed over replicas."""
        total: dict = {}
        for rt in self.runtimes:
            for k, v in rt.telemetry.counters.items():
                total[k] = total.get(k, 0) + v
        return total

    def run(self, clients, trace_path: Optional[Path] = None,
            trace_seconds: float = 0.0) -> Window:
        """Send ``clients``' traffic; profile ``trace_seconds`` of it into
        ``trace_path`` from a third of the way in; wait for the replies."""
        import jax

        counters0 = self.counters()
        if self.recorder:
            self.recorder.calls = []
            self.recorder.on = True
        self.counter.on = True
        t0 = clients.start()
        if trace_path is not None:
            shutil.rmtree(trace_path, ignore_errors=True)
            time.sleep(max(t0 + clients.seconds / 3 - time.perf_counter(), 0.0))
            jax.profiler.start_trace(str(trace_path))
            with jax.profiler.TraceAnnotation("chipbench.traced"):
                time.sleep(trace_seconds)
            jax.profiler.stop_trace()
        all_back = clients.join(GRACE_S)
        give_up = time.perf_counter()
        self.counter.on = False
        calls = []
        if self.recorder:
            self.recorder.on = False
            calls = self.recorder.calls
        counters = {k: v - counters0.get(k, 0) for k, v in self.counters().items()}
        log(f"window: {len(clients.records)} requests, all answered in time: {all_back}")
        log(f"compiles in window: {self.counter.lowered} programs lowered, "
            f"{self.counter.compiled} compiled; closures warmed: {self.closures}")
        if clients.open:
            late = clients.lateness_s()
            if late.size:
                log(f"client lateness: median {1e3 * np.median(late):.3f} ms, p99 "
                    f"{1e3 * np.quantile(late, 0.99):.3f} ms, max {1e3 * late.max():.3f} ms")
        log("telemetry in window: " + json.dumps(counters, sort_keys=True))
        for c in calls:
            c.collect()
        return Window(clients.records, t0, give_up, counters, calls)

    def clients(self, items, mix: dict, seed: int, seconds: float):
        from chipbench.traffic import Clients

        return Clients(self.host, self.port, items, mix, seed, seconds)

    def close(self) -> None:
        """Stop serving and drop the program's state; the data stays."""
        report = self.frontend.close(drain=True)
        if report["in_flight"]:
            log(f"left in flight after drain: {report['in_flight']}")
        del self.runtime, self.runtimes, self.frontend, self.recorder
        gc.collect()


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, devices,
             *, fault: Optional[Callable] = None) -> dict:
    """Set up, run the window, compare, and return the result object."""
    from chipbench import peaks as peak_table
    from chipbench.traffic import open_loop_times

    cfg, mix = cell.config, cell.traffic
    on_tpu = devices[0].platform == "tpu"
    peaks = peak_table.peaks_for(devices[0].device_kind) if on_tpu else None
    log(f"device: {len(devices)} x {devices[0].device_kind} ({devices[0].platform})")
    dep = Deployment(cell, seed, record=trace, fault=fault)
    t = time.perf_counter()
    n_items = (len(open_loop_times(seed, mix["rate_qps"], seconds))
               if mix["loop"] == "open" else mix["pool"])
    items = cell.ops.make_items(seed, dep.data, n_items, mix)
    clients = dep.clients(items, mix, seed, seconds)
    log(f"setup traffic: {time.perf_counter() - t:.3f} s")
    trace_path = cell.root / TRACE_DIR / cell.name if trace and on_tpu else None
    win = dep.run(clients, trace_path, min(mix["trace_seconds"], seconds / 3))
    setup_s = win.t0 - T_START
    clients.close()
    peak = None
    if on_tpu:
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    dep.close()

    t = time.perf_counter()
    rec_items = [items[r.item] for r in win.records]
    checks = cell.ops.compare(dep.data, rec_items, cell.ops.answers_of(win.records),
                              cfg["checks"], dep.args)
    log(f"reference: {time.perf_counter() - t:.3f} s")
    summary = None
    if trace_path is not None:
        from chipbench.trace import load_xplane, reduce_trace

        files = sorted(trace_path.glob("plugins/profile/*/*.xplane.pb"))
        summary = reduce_trace(load_xplane(str(files[-1])))
        shutil.rmtree(trace_path, ignore_errors=True)

    ctx = RunContext(cfg, dep.args, dep.phases, win.records, win.t0, seconds, win.give_up,
                     setup_s, win.counters, win.searches, checks, peaks, summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.load_reader(cell.root, m["name"])(ctx)
        if value is not None and (on_tpu or m["source"] != "device_trace"):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if peak is not None:
        device["memory_peak_bytes"] = peak
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    result = {
        "correct": check.holds(checks),
        "attempted": len(win.records),
        "failed": sum(not r.answered for r in win.records),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = summary["breakdown"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(ROOT, args.workload)
    except (KeyError, OSError, ValueError) as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401  (the system under test, from src/)
    except ImportError as e:
        print(f"run: the program is not in this checkout: {e}", file=sys.stderr)
        return 2
    try:
        serve_args(cell)
    except SystemExit:
        print(f"run: the program refuses the serve flags {cell.serve_flags()}",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run: cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3
    enable_cache(ROOT / CACHE_DIR)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices[: cell.chips])
    sys.stdout.flush()
    for line in check.lines(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
