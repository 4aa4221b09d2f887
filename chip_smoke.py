#!/usr/bin/env python3
"""Bring-up smoke: the serving path once, on one TPU chip, at real size.

Builds the paper's own deployment, airship-sift1m (1,000,000 x 128-d f32
vectors, a degree-32 proximity graph, 10 k-means labels, one uniform
attribute column for range queries), through the entry points a user
calls: ``build_index`` and ``launch/serve.py``'s ``build_runtime``, then
``runtime.warmup()``. It starts ``obs.http.ServingFrontend`` on a free
port in this process and POSTs a few dozen ``/v1/search`` requests over
the socket (40% equal-label, 40% unequal-20%, 20% range; k in {4, 8, 16}),
once with the fused candidate pipeline off (the default TPU path) and once
with it on. Every answer is checked against ``exact_constrained_search``
on the same data: each returned id satisfies its constraint, equal-label
answers are full (fill = k), and recall@k is at least 0.8. It prints how
many ids differ between the two paths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the --distributed
                                     # scatter-search-merge path, sharded
                                     # over four chips, vs the same oracle

The serving runtime uses the one-bucket ladder 32 (a request stream of a
few dozen fills no larger bucket; each bucket costs a compile and a warm-up
search per tier). Earlier lines report each phase; the last
line is one JSON object naming the device. The script runs in one process
(the chip belongs to one process at a time), exits non-zero when JAX finds
no TPU, and lets any failed phase or check end it with an error.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common.jaxcache import enable_compile_cache  # noqa: E402
from repro.core import exact_constrained_search  # noqa: E402
from repro.core.constraints import LabelSetConstraint, RangeConstraint  # noqa: E402
from repro.graph.index import build_index  # noqa: E402
from repro.launch.serve import build_runtime, make_corpus, make_parser  # noqa: E402
from repro.serving import mixed_workload, wall_clock  # noqa: E402

# airship-sift1m (repro.configs: airship_sift1m) as launch/serve.py flags.
DEPLOYMENT = [
    "--n", "1000000", "--d", "128", "--labels", "10", "--degree", "32",
    "--sample-size", "512", "--k-cap", "16", "--ladder", "32",
    # Tier 0 searches with ef 1024 over at most 4096 iterations. The
    # config's own budget (ef 128) finds about 0.58 of the exact top-k on
    # this synthetic 1M corpus on a v5e, ef 1024 about 0.83 (PERF.md).
    "--base-ef", "1024", "--base-iters", "4096",
]
N_REQUESTS = 48
K_CHOICES = (4, 8, 16)
MIN_RECALL = 0.8
CLIENTS = 16  # concurrent HTTP clients, so the batcher forms real batches


class SmokeFailure(RuntimeError):
    """A check on the served answers failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def timed_calls(module, name: str, seconds: dict, label: str):
    """Time every call of ``module.name`` (to the device finishing) under
    ``label`` while the block runs."""
    fn = getattr(module, name)

    def wrapper(*args, **kwargs):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        seconds[label] = seconds.get(label, 0.0) + time.perf_counter() - t0
        return out

    setattr(module, name, wrapper)
    try:
        yield
    finally:
        setattr(module, name, fn)


def smoke_args(extra=()) -> argparse.Namespace:
    return make_parser().parse_args([*DEPLOYMENT, *extra])


def make_deployment(args) -> tuple:
    """Corpus with k-means labels and a uniform attribute column ->
    (corpus, seconds per phase)."""
    import repro.data.synthetic as synthetic

    seconds: dict = {}
    t0 = time.perf_counter()
    with timed_calls(synthetic, "kmeans_labels", seconds, "kmeans"):
        corpus = jax.block_until_ready(make_corpus(args))
    seconds["data"] = time.perf_counter() - t0 - seconds["kmeans"]
    return corpus, seconds


def build_graph(args, corpus) -> tuple:
    """``build_index`` as ``build_runtime`` calls it -> (graph, seconds)."""
    import repro.graph.index as index

    seconds: dict = {}
    with timed_calls(index, "build_knn_graph", seconds, "graph_build"), \
            timed_calls(index, "add_reverse_edges", seconds, "reverse_edges"):
        graph = jax.block_until_ready(build_index(
            jax.random.PRNGKey(1), corpus, degree=args.degree,
            sample_size=args.sample_size,
        ))
    return graph, seconds


def start_runtime(args, corpus, graph=None) -> tuple:
    """``build_runtime`` + ``warmup`` -> (runtime, closures, seconds)."""
    runtime = build_runtime(args, corpus, wall_clock, prebuilt_graph=graph)
    t0 = time.perf_counter()
    compiled = runtime.warmup()
    return runtime, compiled, time.perf_counter() - t0


def workload(corpus, args) -> list:
    return mixed_workload(
        7, corpus, N_REQUESTS, args.labels, k_choices=K_CHOICES
    )


def _payload(item) -> dict:
    body = {"query": item.query.tolist(), "k": item.k, "family": item.family,
            "timeout_s": 120}
    if item.family == "label":
        row = np.asarray(item.operand, np.uint32)
        body["labels"] = [
            w * 32 + b for w in range(row.size) for b in range(32)
            if (int(row[w]) >> b) & 1
        ]
    else:
        body["range"] = list(item.operand)
    return body


def serve_requests(runtime, items) -> list:
    """POST every item to a ServingFrontend over the socket; drain."""
    from repro.obs.http import ServingFrontend

    frontend = ServingFrontend(runtime, port=0)
    addr = frontend.start()

    def post(item):
        req = urllib.request.Request(
            addr + "/v1/search", data=json.dumps(_payload(item)).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=180) as r:
            return json.loads(r.read())

    try:
        with concurrent.futures.ThreadPoolExecutor(CLIENTS) as pool:
            answers = list(pool.map(post, items))
    finally:
        report = frontend.close(drain=True)
    if report["in_flight"]:
        raise SmokeFailure(f"requests left in flight after drain: {report}")
    return answers


def exact_answers(corpus, items, k: int) -> list:
    """(k,) exact constrained top-k ids per item, batched per family."""
    truth: list = [None] * len(items)
    for family in ("label", "range"):
        idx = [i for i, it in enumerate(items) if it.family == family]
        if not idx:
            continue
        queries = jnp.asarray(np.stack([items[i].query for i in idx]))
        if family == "label":
            cons = LabelSetConstraint(words=jnp.asarray(
                np.stack([np.asarray(items[i].operand, np.uint32) for i in idx])
            ))
        else:
            ops = [items[i].operand for i in idx]
            cons = RangeConstraint(
                lo=jnp.asarray([o[0] for o in ops], np.float32),
                hi=jnp.asarray([o[1] for o in ops], np.float32),
                col=jnp.int32(ops[0][2]),
            )
        _, ids = exact_constrained_search(corpus, queries, cons, k=k)
        for row, i in zip(np.asarray(ids), idx):
            truth[i] = row
    return truth


def check_answers(corpus, items, answers, truth) -> dict:
    """Constraint, fill and recall checks -> per-slice recall and fill."""
    labels = np.asarray(corpus.labels)
    attrs = np.asarray(corpus.attrs)
    stats: dict = {}
    for item, ans, true in zip(items, answers, truth):
        if ans.get("error"):
            raise SmokeFailure(f"request failed: {ans['error']}")
        ids = np.asarray(ans["ids"][: item.k])
        found = ids[ids >= 0]
        if item.family == "label":
            row = np.asarray(item.operand, np.uint32)
            labs = labels[found]
            ok = (row[labs // 32] >> (labs % 32).astype(np.uint32)) & 1
        else:
            lo, hi, col = item.operand
            vals = attrs[found, col]
            ok = (vals >= lo) & (vals <= hi)
        if not np.all(ok):
            raise SmokeFailure(f"{item.kind}: returned ids violate the "
                               f"constraint: {found[~np.asarray(ok, bool)]}")
        if item.kind == "equal" and ans["filled"] != item.k:
            raise SmokeFailure(f"equal-label fill {ans['filled']} != k {item.k}")
        want = true[: item.k]
        want = want[want >= 0]
        hit = len(set(found.tolist()) & set(want.tolist()))
        s = stats.setdefault(item.kind, {"recall": [], "fill": []})
        s["recall"].append(hit / max(len(want), 1))
        s["fill"].append(ans["filled"] / item.k)
    out = {kind: {m: float(np.mean(v)) for m, v in s.items()}
           for kind, s in stats.items()}
    out["all"] = {"recall": float(np.mean(
        [r for s in stats.values() for r in s["recall"]]))}
    return out


def _ids_differ(a: list, b: list) -> tuple:
    """(ids that differ position by position, requests with any)."""
    diff = [sum(x != y for x, y in zip(p["ids"], q["ids"])) for p, q in zip(a, b)]
    return int(sum(diff)), int(sum(d > 0 for d in diff))


def _report(path: str, quality: dict) -> None:
    for kind, q in quality.items():
        log(f"{path}: {kind:8s} recall@k {q['recall']:.4f}"
            + (f"  fill {q['fill']:.4f}" if "fill" in q else ""))


def _require_recall(path: str, quality: dict) -> None:
    if quality["all"]["recall"] < MIN_RECALL:
        raise SmokeFailure(
            f"{path}: recall@k {quality['all']['recall']:.4f} < {MIN_RECALL}"
        )


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def one_chip(extra=()) -> None:
    args = smoke_args(["--fuse", "off", *extra])
    corpus, seconds = make_deployment(args)
    log(f"corpus: {corpus.n} rows x d={corpus.dim}, {args.labels} labels, "
        f"degree {args.degree}")
    graph, build_s = build_graph(args, corpus)
    seconds.update(build_s)
    items = workload(corpus, args)
    truth = exact_answers(corpus, items, args.k_cap)

    answers, quality = {}, {}
    for fuse in ("off", "on"):
        args.fuse = fuse
        runtime, compiled, warm_s = start_runtime(args, corpus, graph)
        seconds[f"warmup_fuse_{fuse}"] = warm_s
        log(f"fuse {fuse}: compiled {compiled} closures in {warm_s:.1f}s "
            f"(ladder {args.ladder})")
        answers[fuse] = serve_requests(runtime, items)
        quality[fuse] = check_answers(corpus, items, answers[fuse], truth)
        _report(f"fuse {fuse}", quality[fuse])
    for phase, s in seconds.items():
        log(f"seconds {phase}: {s:.2f}")
    n_diff, n_req = _ids_differ(answers["off"], answers["on"])
    log(f"fused vs unfused: {n_diff} ids differ, in {n_req} of {len(items)} "
        "requests")
    log(f"peak_bytes_in_use: {_peak_bytes()}")
    for fuse, q in quality.items():
        _require_recall(f"fuse {fuse}", q)


def four_chips(extra=()) -> None:
    if len(jax.devices()) != 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found {jax.devices()}")
    args = smoke_args(["--distributed", "--fuse", "off", *extra])
    corpus, seconds = make_deployment(args)
    log(f"corpus: {corpus.n} rows x d={corpus.dim}, {args.labels} labels, "
        f"degree {args.degree}")
    t0 = time.perf_counter()
    runtime = build_runtime(args, corpus, wall_clock)
    seconds["partitioned_build"] = time.perf_counter() - t0
    executor = runtime.executor
    log(f"mesh axis types: {executor.mesh.axis_types}")
    shards = executor.corpus_s.vectors.addressable_shards
    devices = {s.device.id for s in shards}
    for s in shards:
        log(f"corpus shard rows {s.index[0]} on {s.device}")
    if len(devices) != 4:
        raise SmokeFailure(f"corpus shards on {len(devices)} devices, not 4")
    t0 = time.perf_counter()
    compiled = runtime.warmup()
    seconds["warmup"] = time.perf_counter() - t0
    log(f"compiled {compiled} closures (ladder {args.ladder})")
    items = workload(corpus, args)
    truth = exact_answers(corpus, items, args.k_cap)
    answers = serve_requests(runtime, items)
    quality = check_answers(corpus, items, answers, truth)
    _report("distributed", quality)
    for phase, s in seconds.items():
        log(f"seconds {phase}: {s:.2f}")
    log(f"peak_bytes_in_use (device 0): {_peak_bytes()}")
    _require_recall("distributed", quality)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded scatter-search-merge path")
    opts = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    log(f"devices: {len(jax.devices())} x {dev.device_kind}")
    (four_chips if opts.chips == 4 else one_chip)()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
