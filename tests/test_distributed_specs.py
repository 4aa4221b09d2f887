"""Sharded-path spec regressions (PR3) that run on a single host device.

A 1x1 mesh exercises the full shard_map spec machinery — pytree structure
matching between args and in_specs is validated at trace time regardless of
device count — so these catch the historical failure modes cheaply:
``shard_corpus_for_mesh`` silently dropping ``corpus.attrs`` and
``make_distributed_search`` hard-coding the LabelSet constraint spec (both
of which made Range constraints impossible to run distributed). Real
multi-shard semantics live in test_distributed_multidev.py (slow).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    RangeConstraint,
    SearchParams,
    constrained_search,
    equal_constraint,
    exact_constrained_search,
    make_distributed_search,
    pq_train,
    recall,
    shard_corpus_for_mesh,
)
from repro.data.synthetic import make_labeled_corpus, make_queries
from repro.graph.index import build_partitioned_index


@pytest.fixture(scope="module")
def world():
    corpus = make_labeled_corpus(jax.random.PRNGKey(0), n=1500, d=16, n_labels=5)
    attrs = jax.random.uniform(jax.random.PRNGKey(50), (1500, 2))
    corpus = corpus.replace(attrs=attrs)
    corpus_p, graph_p = build_partitioned_index(
        jax.random.PRNGKey(1), corpus, n_shards=1, degree=12,
        sample_size_per_shard=64,
    )
    queries, qlab = make_queries(jax.random.PRNGKey(2), corpus, 8)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return corpus_p, graph_p, queries, qlab, mesh


PARAMS = SearchParams(
    mode="prefer", k=10, ef_result=64, ef_sat=64, ef_other=64,
    n_start=8, max_iters=300,
)


def test_partitioned_index_and_sharding_preserve_attrs(world):
    corpus_p, graph_p, _, _, mesh = world
    assert corpus_p.attrs is not None  # build_partitioned_index carries attrs
    corpus_s, _ = shard_corpus_for_mesh(corpus_p, graph_p, mesh)
    assert corpus_s.attrs is not None  # shard_corpus_for_mesh keeps them
    np.testing.assert_array_equal(
        np.asarray(corpus_s.attrs), np.asarray(corpus_p.attrs)
    )


def test_range_constraint_through_sharded_path(world):
    corpus_p, graph_p, queries, _, mesh = world
    corpus_s, graph_s = shard_corpus_for_mesh(corpus_p, graph_p, mesh)
    b = queries.shape[0]
    cons = RangeConstraint(
        lo=jnp.full((b,), 0.3), hi=jnp.full((b,), 0.9), col=jnp.int32(0)
    )
    search = make_distributed_search(mesh, PARAMS, constraint_type=RangeConstraint)
    with jax.set_mesh(mesh):
        res = search(corpus_s, graph_s, queries, cons)
    ids = np.asarray(res.ids)
    vals = np.asarray(corpus_p.attrs)[np.maximum(ids, 0), 0]
    assert np.all(((vals >= 0.3) & (vals <= 0.9)) | (ids < 0))
    # one shard == the local search: full recall against the exact oracle
    _, ti = exact_constrained_search(corpus_p, queries, cons, k=10)
    assert float(recall(res.ids, ti)) == 1.0


def test_unknown_constraint_type_rejected(world):
    *_, mesh = world
    with pytest.raises(TypeError, match="constraint type"):
        make_distributed_search(mesh, PARAMS, constraint_type=dict)


def test_pq_backend_payload_derived_from_params(world):
    """params.approx — not a separate with_pq flag — decides the backend
    payload specs; fused ADC stays bit-identical through the sharded path."""
    corpus_p, graph_p, queries, qlab, mesh = world
    corpus_s, graph_s = shard_corpus_for_mesh(corpus_p, graph_p, mesh)
    cons = equal_constraint(qlab, 5)
    pq = pq_train(jax.random.PRNGKey(11), corpus_p.vectors, m_sub=4, n_cent=16)
    params_pq = dataclasses.replace(PARAMS, approx="pq")
    with jax.set_mesh(mesh):
        res = make_distributed_search(mesh, params_pq)(
            corpus_s, graph_s, queries, cons, pq
        )
        res_f = make_distributed_search(
            mesh, dataclasses.replace(params_pq, fuse_expand="on")
        )(corpus_s, graph_s, queries, cons, pq)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(res_f.ids))
    np.testing.assert_array_equal(np.asarray(res.dists), np.asarray(res_f.dists))
    # the single-shard distributed result equals the plain local search
    local = constrained_search(
        corpus_p, graph_p, queries, cons, params_pq, pq_index=pq
    )
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(local.ids))


def test_uniform_pq_index_signature(world):
    """The distributed search takes pq_index uniformly (None for exact) so
    callers never branch per backend; mismatched payloads fail loudly."""
    corpus_p, graph_p, queries, qlab, mesh = world
    corpus_s, graph_s = shard_corpus_for_mesh(corpus_p, graph_p, mesh)
    cons = equal_constraint(qlab, 5)
    search = make_distributed_search(mesh, PARAMS)
    with jax.set_mesh(mesh):
        res4 = search(corpus_s, graph_s, queries, cons)
        res5 = search(corpus_s, graph_s, queries, cons, None)  # uniform call
    np.testing.assert_array_equal(np.asarray(res4.ids), np.asarray(res5.ids))
    pq = pq_train(jax.random.PRNGKey(11), corpus_p.vectors, m_sub=4, n_cent=16)
    with pytest.raises(ValueError, match="approx"):
        search(corpus_s, graph_s, queries, cons, pq)  # payload w/o approx=pq
    import dataclasses

    search_pq = make_distributed_search(
        mesh, dataclasses.replace(PARAMS, approx="pq")
    )
    with pytest.raises(ValueError, match="requires"):
        search_pq(corpus_s, graph_s, queries, cons)  # approx=pq w/o payload
