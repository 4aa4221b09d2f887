"""Graph builder invariants + constraint families + alter_ratio estimator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.common.distances import squared_l2  # noqa: E402
from repro.core import (  # noqa: E402
    Corpus,
    RangeConstraint,
    equal_constraint,
    estimate_alter_ratio,
    label_set_from_lists,
    make_satisfied_fn,
    unequal_pct_constraint,
)
from repro.data.synthetic import make_labeled_corpus  # noqa: E402
from repro.graph import build  # noqa: E402
from repro.graph.build import (  # noqa: E402
    add_reverse_edges,
    build_knn_graph,
    medoid,
    nn_descent,
    smallest_k,
)
from repro.graph.index import build_index  # noqa: E402


def _rand_vectors(n=200, d=8, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d))


def test_exact_knn_graph_matches_bruteforce():
    x = _rand_vectors(120, 6)
    g = build_knn_graph(x, degree=5, block=32)
    d = np.array(squared_l2(x, x))
    np.fill_diagonal(d, np.inf)
    for i in range(0, 120, 17):
        # compare by distance (top_k and argsort may break ties differently)
        expect = np.sort(d[i])[:5]
        got = np.sort(d[i][np.asarray(g[i])])
        np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def test_graph_rows_sorted_self_free_unique():
    x = _rand_vectors(150, 5, seed=1)
    g = np.asarray(build_knn_graph(x, degree=8))
    d = np.asarray(squared_l2(x, x))
    for i, row in enumerate(g):
        live = row[row >= 0]
        assert i not in live
        assert len(live) == len(set(live.tolist()))
        dist = d[i][live]
        assert np.all(np.diff(dist) >= -1e-5)  # ascending by distance


def test_nn_descent_recall_reasonable():
    x = _rand_vectors(400, 8, seed=2)
    exact = np.asarray(build_knn_graph(x, degree=8))
    approx = np.asarray(nn_descent(jax.random.PRNGKey(3), x, degree=8, iters=10))
    hits = total = 0
    for e_row, a_row in zip(exact, approx):
        hits += len(set(e_row.tolist()) & set(a_row[a_row >= 0].tolist()))
        total += len(e_row)
    assert hits / total > 0.6, hits / total


@pytest.mark.parametrize("chunk,group", [(20, 2), (33, 3), (64, 5), (16, 7)])
def test_smallest_k_chunked_equals_top_k(chunk, group):
    # Integer-valued scores force heavy ties: the grouped reduction must
    # break them exactly like one top_k (lower column first), at every
    # depth of its recursion.
    d = jax.random.randint(jax.random.PRNGKey(chunk), (5, 3000), 0, 20)
    d = d.astype(jnp.float32).at[:, 3].set(jnp.inf).at[4].set(jnp.inf)
    dist, cols = smallest_k(d, 10, chunk=chunk, group=group)
    neg, want = jax.lax.top_k(-d, 10)
    np.testing.assert_array_equal(np.asarray(cols), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(dist), -np.asarray(neg))


def test_knn_graph_chunked_top_k_matches_one_pass(monkeypatch):
    x = _rand_vectors(300, 6, seed=6)
    want = np.asarray(build_knn_graph(x, degree=8))
    monkeypatch.setattr(build, "TOPK_CHUNK", 16)
    monkeypatch.setattr(build, "TOPK_GROUP", 4)
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(build_knn_graph(x, degree=8)), want)


def _reverse_edges_loop(nbrs, vectors, degree):
    """The per-edge Python loop add_reverse_edges replaced (the reference)."""
    nbrs = np.asarray(nbrs)
    n = nbrs.shape[0]
    rev_lists = [[] for _ in range(n)]
    for u in range(n):
        for v in nbrs[u]:
            if v >= 0:
                rev_lists[v].append(u)
    max_rev = max(1, max(len(r) for r in rev_lists))
    rev = np.full((n, max_rev), -1, dtype=np.int32)
    for u, lst in enumerate(rev_lists):
        rev[u, : len(lst)] = lst
    cand = jnp.concatenate([jnp.asarray(nbrs), jnp.asarray(rev)], axis=-1)
    rows = jnp.asarray(vectors)[jnp.maximum(cand, 0)]
    d = jnp.sum((rows - jnp.asarray(vectors)[:, None, :]) ** 2, axis=-1)
    d = jnp.where((cand < 0) | (cand == jnp.arange(n)[:, None]), jnp.inf, d)
    out, _ = build._dedup_sorted_by_dist(cand, d, degree)
    return np.asarray(out)


# 6 * 8 * 4 * 64: 64-row edge-distance blocks, several chunks per class
@pytest.mark.parametrize("chunk_bytes", [None, 6 * 8 * 4 * 64])
def test_reverse_edges_match_loop_reference(monkeypatch, chunk_bytes):
    x = _rand_vectors(300, 8, seed=5)
    g = build_knn_graph(x, degree=6)
    want = _reverse_edges_loop(g, x, 6)
    if chunk_bytes is not None:  # several chunks per width class
        monkeypatch.setattr(build, "REVERSE_CHUNK_BYTES", chunk_bytes)
    np.testing.assert_array_equal(np.asarray(add_reverse_edges(g, x, 6)), want)


def test_nn_descent_row_blocks_match_one_block(monkeypatch):
    x = _rand_vectors(300, 8, seed=7)
    want = np.asarray(nn_descent(jax.random.PRNGKey(3), x, degree=6, iters=3))
    monkeypatch.setattr(build, "NND_ROW_BLOCK", 64)
    jax.clear_caches()
    got = np.asarray(nn_descent(jax.random.PRNGKey(3), x, degree=6, iters=3))
    np.testing.assert_array_equal(got, want)


def test_medoid_is_central():
    x = _rand_vectors(300, 4, seed=4)
    m = int(medoid(x))
    dm = float(jnp.sum(squared_l2(x[m : m + 1], x)))
    rand = float(jnp.sum(squared_l2(x[:1], x)))
    assert dm <= rand * 1.1


@settings(deadline=None, max_examples=20)
@given(st.integers(2, 40), st.data())
def test_label_set_constraint_matches_membership(n_labels, data):
    allowed = data.draw(
        st.lists(st.integers(0, n_labels - 1), min_size=1, max_size=n_labels, unique=True)
    )
    cons = label_set_from_lists([allowed], n_labels)
    labels = jnp.arange(n_labels, dtype=jnp.int32)
    corpus = Corpus(
        vectors=jnp.zeros((n_labels, 2)), labels=labels
    )
    sat = make_satisfied_fn(cons, corpus)
    ids = jnp.arange(n_labels, dtype=jnp.int32)[None]
    got = np.asarray(sat(ids))[0]
    expect = np.isin(np.arange(n_labels), allowed)
    np.testing.assert_array_equal(got, expect)


def test_unequal_pct_never_includes_query_label():
    qlab = jnp.arange(10, dtype=jnp.int32) % 7
    cons = unequal_pct_constraint(jax.random.PRNGKey(0), qlab, 7, 40.0)
    corpus = Corpus(vectors=jnp.zeros((7, 2)), labels=jnp.arange(7, dtype=jnp.int32))
    sat = make_satisfied_fn(cons, corpus)
    own = sat(qlab[:, None])  # query's own label id as candidate
    assert not bool(jnp.any(own))


def test_range_constraint():
    corpus = Corpus(
        vectors=jnp.zeros((5, 2)),
        labels=jnp.zeros((5,), jnp.int32),
        attrs=jnp.asarray([[0.0], [1.0], [2.0], [3.0], [4.0]]),
    )
    cons = RangeConstraint(
        lo=jnp.asarray([1.0]), hi=jnp.asarray([3.0]), col=jnp.int32(0)
    )
    sat = make_satisfied_fn(cons, corpus)
    got = np.asarray(sat(jnp.arange(5, dtype=jnp.int32)[None]))[0]
    np.testing.assert_array_equal(got, [False, True, True, True, False])


def test_alter_ratio_clustered_vs_random():
    """§2.4: clustered labels -> ratio near 1; random labels -> ratio ~ p."""
    rng = jax.random.PRNGKey(0)
    clustered = make_labeled_corpus(rng, n=2000, d=16, n_labels=5, pct_random=0.0)
    random_lab = make_labeled_corpus(rng, n=2000, d=16, n_labels=5, pct_random=100.0)
    out = {}
    for name, corpus in [("clustered", clustered), ("random", random_lab)]:
        graph = build_index(jax.random.PRNGKey(1), corpus, degree=8, sample_size=128)
        qlab = corpus.labels[:8]
        cons = equal_constraint(qlab, 5)
        sat = make_satisfied_fn(cons, corpus)
        sample_ids = jnp.broadcast_to(graph.sample_ids[None], (8, 128))
        ratio = estimate_alter_ratio(graph, sat, sat(sample_ids), k=8)
        out[name] = float(jnp.mean(ratio))
    assert out["clustered"] > 0.7
    assert out["random"] < 0.45
    assert 0.0 <= out["random"] and out["clustered"] <= 1.0
