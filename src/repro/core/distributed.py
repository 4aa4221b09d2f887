"""Distributed constrained search: scatter-search-merge over the mesh.

Layout (see DESIGN.md §4):
  * corpus rows + their *local* proximity subgraph are sharded over the
    ``model`` axis (each device owns an independent subgraph whose neighbor
    ids are local),
  * the query batch is sharded over the ``data`` (and optionally ``pod``)
    axes and replicated within each model group,
  * every shard builds its own ``TraversalContext`` — the distance backend's
    arrays (corpus rows, or PQ codes + per-query LUT) shard with the corpus
    rows; the per-query constraint operand shards with the batch — runs the
    full AIRSHIP search on its rows via ``search_with_context``, then the
    global top-k is one `all_gather(K)` + local merge per batch — the only
    collective on the serving path.

This is the standard production layout for distributed graph-ANN (per-shard
indexes + result merge); it keeps the graph walk entirely local so no
pointer-chasing ever crosses the interconnect. Backend sharding is generic:
``params.approx`` decides which backend payload rides along (the PQ code
matrix row-shards exactly like the vectors; codebooks replicate), with no
per-backend special cases in the search body.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.constraints import LabelSetConstraint, RangeConstraint
from repro.core.engine.context import build_context
from repro.core.engine.loop import search_with_context
from repro.core.types import Corpus, GraphIndex, SearchParams, SearchResult, SearchStats

Array = jax.Array


def merge_topk(dists: Array, ids: Array, k: int) -> tuple[Array, Array]:
    """Merge per-shard results: (B, P, K) -> (B, k) global best."""
    b = dists.shape[0]
    flat_d = dists.reshape(b, -1)
    flat_i = ids.reshape(b, -1)
    neg, pos = jax.lax.top_k(-flat_d, k)
    out_i = jnp.take_along_axis(flat_i, pos, axis=-1)
    return -neg, jnp.where(jnp.isfinite(-neg), out_i, -1)


def constraint_in_spec(constraint_type: type, batch_axes: Sequence[str]):
    """Per-family shard_map in_spec: per-query operands shard with the batch.

    Registry-style so new data-constraint families extend the sharded path
    by adding one entry (UDF closures are static code, not shardable data —
    they cannot cross shard_map as an argument).
    """
    batch_axes = tuple(batch_axes)
    if constraint_type is LabelSetConstraint:
        return LabelSetConstraint(words=P(batch_axes, None))
    if constraint_type is RangeConstraint:
        return RangeConstraint(lo=P(batch_axes), hi=P(batch_axes), col=P())
    raise TypeError(
        f"no sharded in_spec for constraint type {constraint_type!r}; "
        "register it in core.distributed.constraint_in_spec"
    )


def backend_in_specs(params: SearchParams, corpus_axis: str) -> tuple:
    """Extra in_specs for the distance backend's payload, from params.approx.

    Exact / L2-kernel backends score the corpus rows already sharded by the
    corpus spec — no extra payload. PQ adds the code matrix (row-sharded
    like the vectors) + replicated codebooks; the per-query LUT is built
    per shard inside ``build_context``.
    """
    if params.approx == "pq":
        from repro.core.pq import PQIndex

        return (PQIndex(codebooks=P(), codes=P(corpus_axis)),)
    return ()


def make_distributed_search(
    mesh: Mesh,
    params: SearchParams,
    *,
    corpus_axis: str = "model",
    batch_axes: Sequence[str] = ("data",),
    constraint_type: type = LabelSetConstraint,
    with_attrs: Optional[bool] = None,
):
    """Build a jitted distributed search fn for a given mesh.

    The returned fn takes (corpus, graph, queries, constraint, pq_index=None)
    where corpus / graph hold the *global* arrays (sharded row-wise over
    ``corpus_axis``; neighbor ids are shard-local) and queries / constraint
    are batch-sharded. ``constraint_type`` selects the constraint family's
    in_spec (LabelSet by default; Range shards [lo, hi] with the batch and
    needs the attrs column, so ``with_attrs`` defaults to True for it).
    With ``params.approx == "pq"`` the PQ code matrix shards with the
    corpus rows and codebooks replicate — the trailing ``pq_index`` is then
    required; otherwise it must stay None. The signature is uniform across
    backends so callers never branch on the payload (a None rides through
    shard_map as an empty pytree with a None in_spec).
    """
    batch_axes = tuple(batch_axes)
    if with_attrs is None:
        with_attrs = constraint_type is RangeConstraint
    corpus_spec = P(corpus_axis)

    in_specs = (
        Corpus(
            vectors=corpus_spec,
            labels=corpus_spec,
            attrs=corpus_spec if with_attrs else None,
        ),
        GraphIndex(
            neighbors=corpus_spec, sample_ids=corpus_spec, entry_point=corpus_spec
        ),
        P(batch_axes, None),  # queries
        constraint_in_spec(constraint_type, batch_axes),
    )
    # The backend-payload slot is always present (uniform arity): PQ specs
    # when the backend carries codes, a None spec for the None placeholder
    # otherwise.
    backend_specs = backend_in_specs(params, corpus_axis)
    in_specs = in_specs + (backend_specs if backend_specs else (None,))
    out_specs = SearchResult(
        dists=P(batch_axes, None),
        ids=P(batch_axes, None),
        stats=SearchStats(
            dist_evals=P(batch_axes),
            hops=P(batch_axes),
            visited=P(batch_axes),
            iters=P(),
            beam_expansions=P(batch_axes, None),
        ),
    )

    def shard_fn(corpus, graph, queries, constraint, pq_index):
        n_local = corpus.vectors.shape[0]
        shard = jax.lax.axis_index(corpus_axis)
        # Per-shard context: the backend holds this shard's rows (or codes
        # + the local batch's LUT); the constraint closure closes over this
        # shard's metadata columns.
        ctx = build_context(
            corpus, constraint, queries, params, pq_index,
            degree=graph.neighbors.shape[1],
        )
        res = search_with_context(ctx, corpus, graph, queries, params)
        # Local ids -> global ids (row-sharded partition => offset).
        gids = jnp.where(res.ids >= 0, res.ids + shard * n_local, -1)
        # One collective: gather every shard's K best, merge locally.
        all_d = jax.lax.all_gather(res.dists, corpus_axis, axis=1)  # (B, P, K)
        all_i = jax.lax.all_gather(gids, corpus_axis, axis=1)
        out_d, out_i = merge_topk(all_d, all_i, params.k)
        stats = SearchStats(
            dist_evals=jax.lax.psum(res.stats.dist_evals, corpus_axis),
            hops=jax.lax.pmax(res.stats.hops, corpus_axis),
            visited=jax.lax.psum(res.stats.visited, corpus_axis),
            iters=jax.lax.pmax(res.stats.iters, corpus_axis),
            # Per-slot expansions sum across shards (each shard walks its
            # own subgraph with the full beam).
            beam_expansions=jax.lax.psum(res.stats.beam_expansions, corpus_axis),
        )
        return SearchResult(dists=out_d, ids=out_i, stats=stats)

    sharded = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    jitted = jax.jit(sharded)
    needs_pq = params.approx == "pq"

    def search(corpus, graph, queries, constraint, pq_index=None):
        if needs_pq and pq_index is None:
            raise ValueError("params.approx='pq' requires a pq_index argument")
        if not needs_pq and pq_index is not None:
            raise ValueError(
                "pq_index passed but params.approx != 'pq'; the exact search "
                "would silently ignore it"
            )
        return jitted(corpus, graph, queries, constraint, pq_index)

    return search


def shard_corpus_for_mesh(
    corpus: Corpus, graph: GraphIndex, mesh: Mesh, corpus_axis: str = "model"
):
    """Device-put global arrays with the row-sharded layout expected above."""
    cspec = NamedSharding(mesh, P(corpus_axis))
    corpus_s = Corpus(
        vectors=jax.device_put(corpus.vectors, cspec),
        labels=jax.device_put(corpus.labels, cspec),
        attrs=(
            jax.device_put(corpus.attrs, cspec)
            if corpus.attrs is not None
            else None
        ),
    )
    graph_s = GraphIndex(
        neighbors=jax.device_put(graph.neighbors, cspec),
        sample_ids=jax.device_put(graph.sample_ids, cspec),
        entry_point=jax.device_put(graph.entry_point, cspec),
    )
    return corpus_s, graph_s
