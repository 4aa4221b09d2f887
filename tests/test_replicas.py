"""Replica-tier tests (DESIGN.md §13): router properties, epoch-consistent
mutation broadcast, the per-replica lock split behind the HTTP front-end,
and graceful drain with zero in-flight loss.

Kept deliberately small/fast: CI replays this file 20x back-to-back to
flush nondeterministic races in the pump/front-end threading.
"""
import json
import threading
import time
import urllib.request
from collections import Counter

import jax
import numpy as np
import pytest

from repro.data.synthetic import make_labeled_corpus
from repro.graph.index import build_index
from repro.obs import JsonLogger, parse_exposition
from repro.obs.http import ServingFrontend
from repro.serving import (
    AdmissionError,
    ConsistentHashRouter,
    LeastLoadedRouter,
    LocalExecutor,
    ReplicaSet,
    ServingRuntime,
    StreamingLocalExecutor,
    VirtualClock,
    label_words_row,
    make_replica_router,
    make_tier_ladder,
)
from repro.streaming import StreamingIndex

N, D, L = 900, 8, 4


@pytest.fixture(scope="module")
def world():
    corpus = make_labeled_corpus(jax.random.PRNGKey(0), n=N, d=D, n_labels=L)
    corpus = corpus.replace(
        attrs=jax.random.uniform(jax.random.PRNGKey(50), (N, 2))
    )
    graph = build_index(jax.random.PRNGKey(1), corpus, degree=8, sample_size=64)
    return corpus, graph


def _runtime(corpus, graph, *, streaming=False, max_pending=256, **kw):
    tiers = make_tier_ladder(k_cap=4, base_ef=16, base_iters=32, n_tiers=1)
    if streaming:
        index = StreamingIndex.from_static(corpus, graph, ef_insert=16)
        executor = StreamingLocalExecutor(index)
    else:
        executor = LocalExecutor(corpus, graph)
    rt = ServingRuntime(
        executor,
        n_labels=L,
        tiers=tiers,
        ladder=(4,),
        families=("label", "range"),
        max_wait=0.002,
        max_pending=max_pending,
        clock=VirtualClock(),
        **kw,
    )
    rt.warmup()
    return rt


def _tier(corpus, graph, n=2, *, streaming=False, router=None, **kw):
    return ReplicaSet(
        [_runtime(corpus, graph, streaming=streaming, **kw) for _ in range(n)],
        router=router,
    )


def _post(addr, route, payload, timeout=30):
    req = urllib.request.Request(
        addr + route,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(addr, route, timeout=30):
    with urllib.request.urlopen(addr + route, timeout=timeout) as r:
        body = r.read().decode()
        try:
            return r.status, json.loads(body)
        except json.JSONDecodeError:
            return r.status, body


# --- routers --------------------------------------------------------------

def test_hash_router_deterministic():
    a = ConsistentHashRouter(4)
    b = ConsistentHashRouter(4)
    keys = list(range(500)) + ["req-%d" % i for i in range(100)]
    assert [a.route(k) for k in keys] == [b.route(k) for k in keys]
    # loads are ignored: same verdicts whatever the gauge says
    assert a.route(7, loads=[100, 0, 0, 0]) == a.route(7)
    # every replica owns a nonempty share of a modest keyspace
    owners = Counter(a.route(k) for k in range(1000))
    assert set(owners) == {0, 1, 2, 3}


def test_hash_router_redistribution_bound():
    before = ConsistentHashRouter(4)
    after = ConsistentHashRouter(5)
    keys = range(2000)
    moved = sum(1 for k in keys if before.route(k) != after.route(k))
    # Ideal move fraction is 1/5; the vnode ring keeps it near that, and
    # categorically below a rehash-everything shuffle (which would move
    # ~4/5 of keys).
    assert moved / 2000 <= 0.35


def test_least_loaded_router():
    r = LeastLoadedRouter(3)
    assert r.route(None, [5, 2, 9]) == 1
    # ties break to the lowest index, deterministically
    assert r.route(None, [4, 4, 4]) == 0
    assert r.route(None, [7, 3, 3]) == 1
    with pytest.raises(ValueError):
        r.route(None, [1, 2])


def test_make_replica_router():
    assert isinstance(make_replica_router("hash", 2), ConsistentHashRouter)
    assert isinstance(
        make_replica_router("least-loaded", 2), LeastLoadedRouter
    )
    with pytest.raises(ValueError):
        make_replica_router("round-robin", 2)


# --- tier submit/poll/drain ----------------------------------------------

def test_tier_submit_poll_drain(world):
    corpus, graph = world
    tier = _tier(corpus, graph, n=2, router=LeastLoadedRouter(2))
    vectors = np.asarray(corpus.vectors)
    handles = []
    for i in range(24):
        handles.append(tier.submit(
            vectors[i], 4, "label", label_words_row([i % L], L)
        ))
    assert tier.in_flight == 24
    assert tier.drain() == 24
    assert tier.in_flight == 0
    by_replica = Counter(i for i, _ in handles)
    # least-loaded must spread the stream across both replicas
    assert set(by_replica) == {0, 1}
    for i, rid in handles:
        resp = tier.poll(i, rid)
        assert resp is not None and resp.error is None
        assert resp.trace is not None and resp.trace["replica"] == i


def test_trace_replica_stamp(world):
    corpus, graph = world
    rt = _runtime(corpus, graph)
    rid = rt.submit(
        np.asarray(corpus.vectors)[0], 4, "label", label_words_row([0], L)
    )
    rt.drain()
    resp = rt.poll(rid)
    # standalone runtimes (replica_id=None) keep the PR 9 trace shape
    assert "replica" not in resp.trace


# --- mutation broadcast ---------------------------------------------------

def test_mutation_broadcast_epoch_consistent(world):
    corpus, graph = world
    tier = _tier(corpus, graph, n=2, streaming=True)
    vec = np.asarray(corpus.vectors)[3] + 0.01
    # the new vector joins its neighbourhood's label, so a search for that
    # label starts next to it
    label = int(np.asarray(corpus.labels)[3])

    handles = tier.submit_upsert(vec, label=label)
    assert [i for i, _ in handles] == [0, 1]
    tier.step_all(force=True)
    responses = tier.poll_all(handles)
    assert all(r is not None and r.filled == 1 for r in responses)
    slots = {int(np.asarray(r.ids)[0]) for r in responses}
    assert len(slots) == 1, f"replicas assigned different slots: {slots}"
    assert len({r.epoch for r in responses}) == 1
    assert len(set(tier.epochs())) == 1

    # identical post-mutation state: the same query answers identically
    # on every replica
    slot = slots.pop()
    queries = [
        rt.submit(vec, 4, "label", label_words_row([label], L))
        for rt in tier.replicas
    ]
    tier.drain()
    answers = [
        tuple(np.asarray(rt.poll(rid).ids).tolist())
        for rt, rid in zip(tier.replicas, queries)
    ]
    assert answers[0] == answers[1]
    assert slot in answers[0]  # the new vector is its own nearest neighbor

    # delete broadcast: NO replica may keep serving the dead slot
    handles = tier.submit_delete(slot)
    tier.step_all(force=True)
    responses = tier.poll_all(handles)
    assert all(r is not None and r.filled == 1 for r in responses)
    assert len(set(tier.epochs())) == 1
    queries = [
        rt.submit(vec, 4, "label", label_words_row([label], L))
        for rt in tier.replicas
    ]
    tier.drain()
    answers = [
        tuple(np.asarray(rt.poll(rid).ids).tolist())
        for rt, rid in zip(tier.replicas, queries)
    ]
    assert answers[0] == answers[1]
    assert slot not in answers[0]


def test_broadcast_admission_is_atomic(world):
    corpus, graph = world
    tier = _tier(corpus, graph, n=2, streaming=True, max_pending=4)
    vectors = np.asarray(corpus.vectors)
    # fill replica 1 to its admission bound without stepping
    for i in range(4):
        tier.replicas[1].submit(
            vectors[i], 4, "label", label_words_row([0], L)
        )
    with pytest.raises(AdmissionError):
        tier.submit_upsert(vectors[5], label=0)
    # nothing was enqueued anywhere: replica 0 untouched, replica 1 still
    # holds exactly its queries
    assert tier.replicas[0].in_flight == 0
    assert tier.replicas[1].in_flight == 4
    tier.drain()


# --- HTTP front-end over the tier ----------------------------------------

def test_frontend_tier_http_roundtrip(world):
    corpus, graph = world
    logger = JsonLogger()
    tier = _tier(corpus, graph, n=2, streaming=True)
    fe = ServingFrontend(tier, logger=logger)
    addr = fe.start()
    vectors = np.asarray(corpus.vectors)
    try:
        replicas_seen = set()
        for i in range(12):
            status, body = _post(addr, "/v1/search", {
                "query": vectors[i].tolist(), "k": 4,
                "family": "label", "labels": [i % L],
            })
            assert status == 200 and body["error"] is None
            assert body["replica"] in (0, 1)
            assert body["trace"]["replica"] == body["replica"]
            replicas_seen.add(body["replica"])

        status, body = _post(addr, "/v1/upsert", {
            "vector": (vectors[0] + 0.02).tolist(), "label": 2,
        })
        assert status == 200 and body["ok"] and body["slot_consistent"]
        assert {r["replica"] for r in body["replicas"]} == {0, 1}
        assert len({r["epoch"] for r in body["replicas"]}) == 1
        slot = body["slot"]

        status, body = _post(addr, "/v1/delete", {"slot": slot})
        assert status == 200 and body["ok"] and body["slot_consistent"]

        status, health = _get(addr, "/healthz")
        assert status == 200 and health["status"] == "ok"
        assert [r["replica"] for r in health["replicas"]] == [0, 1]

        status, text = _get(addr, "/metrics")
        assert status == 200
        fams = parse_exposition(text)
        events = fams["repro_serving_events_total"]
        assert set(events.label_values("replica")) >= {"0", "1", "all"}
        # replica-label cumulativity: per-replica counters sum to the
        # rollup, for every event key
        for key in events.label_values("event"):
            total = sum(
                events.value(event=key, replica=str(i)) for i in (0, 1)
            )
            assert events.value(event=key, replica="all") == total
        lat = fams["repro_serving_latency_seconds"]
        per_replica = [
            dict(lat.buckets(replica=str(i))) for i in (0, 1)
        ]
        for edge, cum in lat.buckets(replica="all"):
            assert cum == sum(pr[edge] for pr in per_replica)
        assert fams["repro_tier_replicas"].value() == 2.0
        epochs = fams["repro_streaming_epoch"]
        assert (
            epochs.value(replica="0") == epochs.value(replica="1")
        )
    finally:
        report = fe.close(drain=True)
    assert report["in_flight"] == 0
    assert not any(
        t.is_alive() for t in fe._threads if t.name.startswith("obs-http-pump")
    )
    records = logger.sink.records()
    assert {r.get("replica") for r in records if "replica" in r} >= {0, 1}


def test_healthz_and_metrics_responsive_while_replica_locked(world):
    corpus, graph = world
    tier = _tier(corpus, graph, n=2)
    fe = ServingFrontend(tier)
    addr = fe.start()
    try:
        release = threading.Event()

        def hog():
            with tier.locks[1]:
                release.wait(10.0)

        t = threading.Thread(target=hog, daemon=True)
        t.start()
        time.sleep(0.05)  # let the hog take the lock
        t0 = time.monotonic()
        status, health = _get(addr, "/healthz")
        assert status == 200 and health["status"] == "ok"
        status, text = _get(addr, "/metrics")
        assert status == 200
        parse_exposition(text)  # still a valid exposition
        elapsed = time.monotonic() - t0
        # both surfaces answered from timeout-acquire fallbacks instead of
        # waiting out the 10s the lock is held
        assert elapsed < 5.0
        release.set()
        t.join()
    finally:
        fe.close(drain=True)


def test_frontend_graceful_close_zero_loss(world):
    corpus, graph = world
    tier = _tier(corpus, graph, n=2, streaming=True)
    fe = ServingFrontend(tier)
    addr = fe.start()
    vectors = np.asarray(corpus.vectors)
    statuses = []

    def one(i):
        statuses.append(_post(addr, "/v1/search", {
            "query": vectors[i].tolist(), "k": 4,
            "family": "range", "range": [0.1, 0.9, 0],
        })[0])

    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    report = fe.close(drain=True)
    assert statuses == [200] * 8
    assert report["in_flight"] == 0
    # accounting identity over both replicas: everything submitted either
    # completed or was shed — nothing lost in shutdown
    for rt in tier.replicas:
        c = rt.telemetry.counters
        assert c["submitted"] == c["completed"] + c["shed_total"]
    # a closed frontend refuses new work
    status, _ = fe.handle_search({
        "query": vectors[0].tolist(), "k": 4,
        "family": "label", "labels": [0],
    })
    assert status == 503


def test_single_runtime_frontend_unchanged(world):
    # PR 9 contract: a bare runtime behind the frontend still works, with
    # fe.lock coordinating against the (single) pump thread.
    corpus, graph = world
    rt = _runtime(corpus, graph)
    fe = ServingFrontend(rt)
    addr = fe.start()
    vectors = np.asarray(corpus.vectors)
    try:
        status, body = _post(addr, "/v1/search", {
            "query": vectors[0].tolist(), "k": 4,
            "family": "label", "labels": [1],
        })
        assert status == 200 and body["error"] is None
        assert body["replica"] is None
        with fe.lock:
            assert rt.in_flight == 0
        # mutations against a non-streaming executor are a client error
        status, body = _post(addr, "/v1/upsert", {
            "vector": vectors[0].tolist(),
        })
        assert status == 400
        status, health = _get(addr, "/healthz")
        assert status == 200 and "replicas" not in health
    finally:
        fe.close(drain=True)
