"""Multi-device semantics tests (8 virtual host devices via a subprocess —
device count is locked at first jax init, so these cannot run in-process).

Checks:
  * distributed scatter-search-merge == global exact search agreement
  * elastic checkpoint restore onto a different mesh
  * compressed gradient all-reduce == uncompressed within tolerance
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import (SearchParams, equal_constraint, exact_constrained_search,
                            make_distributed_search, recall, shard_corpus_for_mesh)
    from repro.core.types import Corpus
    from jax import set_mesh, shard_map
    from repro.data.synthetic import make_labeled_corpus, make_queries
    from repro.graph.index import build_partitioned_index

    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((2, 4), ("data", "model"))

    corpus = make_labeled_corpus(jax.random.PRNGKey(0), n=2000, d=16, n_labels=5)
    corpus_p, graph_p = build_partitioned_index(
        jax.random.PRNGKey(1), corpus, n_shards=4, degree=12, sample_size_per_shard=64)
    q, qlab = make_queries(jax.random.PRNGKey(2), corpus, 16)
    cons = equal_constraint(qlab, 5)

    params = SearchParams(mode="prefer", k=10, ef_result=64, ef_sat=64,
                          ef_other=64, n_start=8, max_iters=300)
    search = make_distributed_search(mesh, params)
    corpus_s, graph_s = shard_corpus_for_mesh(corpus_p, graph_p, mesh)
    with set_mesh(mesh):
        res = search(corpus_s, graph_s, q, cons)
    td, ti = exact_constrained_search(corpus_p, q, cons, k=10)
    r = float(recall(res.ids, ti))
    print("DIST_RECALL", r)
    assert r > 0.8, r
    # global ids must be valid and satisfy the constraint
    ids = np.asarray(res.ids)
    labs = np.asarray(corpus_p.labels)[np.maximum(ids, 0)]
    ok = (labs == np.asarray(qlab)[:, None]) | (ids < 0)
    assert ok.all()

    # --- elastic checkpoint: save from 8-dev sharded state, restore on 2x2 ---
    from repro.ckpt import checkpoint as ck
    tree = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                                 NamedSharding(mesh, P("data", "model")))}
    d = "/tmp/elastic_ckpt_test"
    ck.save(d, 3, tree)
    mesh2 = jax.make_mesh((4, 2), ("data", "model"))
    sh2 = {"w": NamedSharding(mesh2, P("model", "data"))}
    like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    restored = ck.restore(d, 3, like, shardings=sh2)
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.arange(64.0).reshape(8, 8))
    print("ELASTIC_OK")

    # --- compressed gradient psum vs exact ---
    from repro.train.compression import compressed_tree_psum_mean
    import functools
    mesh1d = jax.make_mesh((8,), ("dp",))
    g = {"w": jax.random.normal(jax.random.PRNGKey(3), (8, 32))}
    def local(gl):
        red, err = compressed_tree_psum_mean(gl, "dp")
        exact = jax.tree.map(lambda x: jax.lax.pmean(x, "dp"), gl)
        return red, exact
    f = shard_map(local, mesh=mesh1d, in_specs=({"w": P("dp")},),
                  out_specs=({"w": P()}, {"w": P()}), check_vma=False)
    red, exact = f(g)
    rel = float(jnp.max(jnp.abs(red["w"] - exact["w"])) /
                (jnp.max(jnp.abs(exact["w"])) + 1e-9))
    print("COMPRESS_RELERR", rel)
    assert rel < 0.02, rel

    # --- PQ distributed search (D4) on 4 corpus shards: the backend payload
    # (codes row-sharded, codebooks replicated) is derived from params.approx;
    # each shard builds its own TraversalContext (PR3) ---
    import dataclasses
    from repro.core import pq_train
    from repro.core.distributed import make_distributed_search as mds
    pq = pq_train(jax.random.PRNGKey(11), corpus_p.vectors, m_sub=4, n_cent=32)
    params_pq = dataclasses.replace(params, approx="pq")
    search_pq = mds(mesh, params_pq)
    with set_mesh(mesh):
        res_pq = search_pq(corpus_s, graph_s, q, cons, pq)
    r_pq = float(recall(res_pq.ids, ti))
    print("DIST_PQ_RECALL", r_pq)
    assert r_pq > 0.7, r_pq
    # fused ADC traversal is bit-identical through the sharded path too
    search_pqf = mds(mesh, dataclasses.replace(params_pq, fuse_expand="on"))
    with set_mesh(mesh):
        res_pqf = search_pqf(corpus_s, graph_s, q, cons, pq)
    np.testing.assert_array_equal(np.asarray(res_pq.ids), np.asarray(res_pqf.ids))
    np.testing.assert_array_equal(np.asarray(res_pq.dists), np.asarray(res_pqf.dists))
    print("DIST_PQ_FUSED_OK")

    # --- Range constraint through the sharded path (PR3 regression: attrs
    # shard with the corpus rows; [lo, hi] shards with the batch) ---
    from repro.core import RangeConstraint
    corpus_a = Corpus(vectors=corpus.vectors, labels=corpus.labels,
                      attrs=jax.random.uniform(jax.random.PRNGKey(20), (2000, 2)))
    corpus_ap, graph_ap = build_partitioned_index(
        jax.random.PRNGKey(1), corpus_a, n_shards=4, degree=12,
        sample_size_per_shard=64)
    assert corpus_ap.attrs is not None  # build_partitioned_index carries attrs
    corpus_as, graph_as = shard_corpus_for_mesh(corpus_ap, graph_ap, mesh)
    assert corpus_as.attrs is not None  # shard_corpus_for_mesh keeps them
    rcons = RangeConstraint(lo=jnp.full((16,), 0.25), hi=jnp.full((16,), 0.85),
                            col=jnp.int32(1))
    search_rng = mds(mesh, params, constraint_type=RangeConstraint)
    with set_mesh(mesh):
        res_rng = search_rng(corpus_as, graph_as, q, rcons)
    ids_r = np.asarray(res_rng.ids)
    vals = np.asarray(corpus_ap.attrs)[np.maximum(ids_r, 0), 1]
    assert (((vals >= 0.25) & (vals <= 0.85)) | (ids_r < 0)).all()
    td_r, ti_r = exact_constrained_search(corpus_ap, q, rcons, k=10)
    r_rng = float(recall(res_rng.ids, ti_r))
    print("DIST_RANGE_RECALL", r_rng)
    assert r_rng > 0.8, r_rng

    # --- two-phase top-k == single-phase on a sharded candidate matrix ---
    from repro.models.recsys import models as rs
    from repro.distributed.meshinfo import MeshInfo
    mi = MeshInfo(mesh=mesh)
    cfg_tt = rs.RecsysConfig(name="tt", model="two_tower", embed_dim=16,
                             tower_mlp=(32, 8), item_vocab=512, user_vocab=256,
                             hist_len=4)
    p_tt = rs.two_tower_init(jax.random.PRNGKey(5), cfg_tt)
    batch_tt = dict(
        user_id=jax.random.randint(jax.random.PRNGKey(6), (8,), 0, 256),
        hist=jax.random.randint(jax.random.PRNGKey(7), (8, 4), -1, 512),
        candidates=jax.random.normal(jax.random.PRNGKey(8), (512, 8)),
    )
    with set_mesh(mesh):
        t1, i1 = jax.jit(lambda p, b: rs.two_tower_score_candidates(
            p, cfg_tt, mi, b, two_phase_topk=False))(p_tt, batch_tt)
        t2, i2 = jax.jit(lambda p, b: rs.two_tower_score_candidates(
            p, cfg_tt, mi, b, two_phase_topk=True))(p_tt, batch_tt)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2), rtol=1e-5, atol=1e-6)
    print("TWO_PHASE_TOPK_OK")
    print("ALL_MULTIDEV_OK")
    """
)


@pytest.mark.slow
def test_multidevice_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ALL_MULTIDEV_OK" in proc.stdout
