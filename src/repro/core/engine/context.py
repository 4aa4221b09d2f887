"""TraversalContext: one bundle for everything a traversal scores with.

The AIRSHIP walk is distance-backend-agnostic — each iteration only needs
"score this candidate batch against the query" plus the constraint verdicts
and a fuse decision. Before this module those choices travelled through the
engine as a ``(use_kernel, pq_codes, lut)`` positional soup; now they are
resolved ONCE, in ``build_context``, and the engine layers receive a single
``TraversalContext`` argument (DESIGN.md §6).

Distance backends (each a pytree holding exactly the arrays it scores with):

  * ``ExactBackend``    — gathered corpus rows + ``batched_rowwise_sqdist``
                          (the seed computation, golden-tested bit-for-bit).
  * ``L2KernelBackend`` — the Pallas ``gather_distance`` kernel over the same
                          rows (``SearchParams.use_kernel``).
  * ``PQBackend``       — ADC lookups against a per-query LUT: m_sub code
                          words per candidate instead of d floats, exact
                          re-rank post-loop (``SearchParams.approx == "pq"``).

Every backend exposes

  * ``distances(queries, ids) -> (B, M)`` — score a gathered candidate batch;
  * ``sample_distances(queries, sample_ids) -> (B, S)`` — score the pre-drawn
    build-time sample shared by all queries (exact backends use the pairwise
    matmul expansion here, matching the seed bit-for-bit);
  * ``fused_expand(queries, ids, visited, tables)`` — the one-pass
    gather+distance+constraint+visited kernel of ``kernels/fused_expand``
    (exact rows for the L2 backends, code rows + in-kernel LUT sums for PQ);
  * ``fusable`` / ``approximate`` properties — whether the fused pipeline has
    a kernel for this backend, and whether results need an exact re-rank.

New backends (e.g. learned similarity metrics, NANN-style) plug in by
implementing the same surface; the engine never branches on backend type.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.common.distances import batched_rowwise_sqdist, squared_l2
from repro.common.pytree import pytree_dataclass, static_field
from repro.core.constraints import (
    ConstraintTables,
    constraint_tables,
    make_satisfied_fn,
)
from repro.core.types import Corpus, SatisfiedFn, SearchParams
from repro.tune.config import DEFAULT_CONFIGS, KernelConfig
from repro.tune.table import lookup as tune_lookup

Array = jax.Array


# The fused kernels compile under Mosaic and run on a v5e chip
# (chip_smoke.py serves the 1M x 128 deployment with fuse_expand "on" and
# "off" and checks both against the exact oracle). Whether "auto" should
# fuse there is a speed question the first chip benchmark answers; until
# then the fused pipeline stays opt-in via fuse_expand="on".
FUSE_AUTO_ON_TPU = False


def resolve_auto_fuse(fusable: bool, backend: str) -> bool:
    """fuse_expand == "auto" policy: where does fusing actually win?

    Both paths return bit-identical results (system-tested); the choice is
    purely physical. On TPU the fused kernel eliminates the separate
    metadata/visited HBM round trips and the windowed sorted merges are
    plain VPU work — that is where auto is meant to fuse, gated on
    ``FUSE_AUTO_ON_TPU`` until a chip benchmark shows it wins. On XLA:CPU,
    measurement says fusing loses: the native TopK a ``queue_push``
    lowers to is data-dependent (fast on the inf-padded queues real
    traversals carry) and keeps donated-buffer reuse inside
    ``lax.while_loop``, while the merge's compare-exchange chain forces
    per-iteration copies — standalone the merge wins 2–3.5x, in-loop it
    loses ~2x (EXPERIMENTS.md §Perf PR2). So auto only fuses where the
    memory system, not the op dispatcher, is the bottleneck.
    """
    return fusable and backend == "tpu" and FUSE_AUTO_ON_TPU


class _RowBackend:
    """Shared surface for backends that score full (n, d) corpus rows.

    Subclasses hold ``vectors`` and override only ``distances`` — the
    sample scan and the fused kernel dispatch are identical for every
    exact-L2 flavor (the fused kernel gathers and scores rows itself, so
    it subsumes whatever unfused distance path the subclass picks).
    """

    vectors: Array  # (n, d)
    config: KernelConfig  # static: fused-kernel block shapes (tune table)

    @property
    def fusable(self) -> bool:
        return True

    @property
    def approximate(self) -> bool:
        return False

    def sample_distances(self, queries: Array, sample_ids: Array) -> Array:
        # The sample is shared by every query, so one gather + the pairwise
        # matmul expansion beats a per-query gather (and reproduces the
        # seed's seeding distances bit-for-bit).
        return squared_l2(queries, self.vectors[sample_ids])

    def fused_expand(
        self, queries: Array, ids: Array, visited: Array, tables: ConstraintTables
    ) -> Tuple[Array, Array, Array]:
        from repro.kernels.fused_expand.ops import fused_expand

        return fused_expand(
            queries, self.vectors, ids, visited,
            tables.meta, tables.cons, tables.tomb, family=tables.family,
            config=self.config,
        )


@pytree_dataclass
class ExactBackend(_RowBackend):
    """Exact squared-L2 over gathered corpus rows (the seed computation)."""

    vectors: Array  # (n, d)
    # Static aux data: configs select compiled kernel variants, so they
    # ride the treedef (same shapes + same table -> same trace).
    config: KernelConfig = static_field(default=DEFAULT_CONFIGS["fused_exact"])

    def distances(self, queries: Array, ids: Array) -> Array:
        safe = jnp.maximum(ids, 0)
        return batched_rowwise_sqdist(queries, self.vectors[safe])


@pytree_dataclass
class L2KernelBackend(_RowBackend):
    """Pallas ``gather_distance`` kernel over the same corpus rows.

    Identical mathematics to ``ExactBackend`` — the kernel fuses the row
    gather with the VPU distance reduction (one HBM visit per candidate).
    Selected by ``SearchParams.use_kernel``.
    """

    vectors: Array  # (n, d)
    config: KernelConfig = static_field(default=DEFAULT_CONFIGS["fused_exact"])
    # The unfused per-iteration distances go through gather_distance, a
    # separately-tuned kernel (its own tuning-table key).
    gd_config: KernelConfig = static_field(
        default=DEFAULT_CONFIGS["gather_distance"]
    )

    def distances(self, queries: Array, ids: Array) -> Array:
        from repro.kernels.gather_distance.ops import gather_distance

        return gather_distance(queries, self.vectors, ids, config=self.gd_config)


@pytree_dataclass
class PQBackend:
    """PQ/ADC approximate distances: per-candidate code rows + per-query LUT.

    Gathers m_sub code words per candidate instead of d floats (32x fewer
    HBM bytes at d=128, m_sub=16) and sums per-subspace LUT entries. The
    walk ranks by these; the engine re-ranks the surviving candidate list
    exactly after the loop (``approximate`` property).
    """

    codes: Array  # (n, m_sub) int32
    lut: Array  # (B, m_sub, n_cent) f32 — per-query ADC table
    config: KernelConfig = static_field(default=DEFAULT_CONFIGS["fused_adc"])

    @property
    def fusable(self) -> bool:
        return True

    @property
    def approximate(self) -> bool:
        return True

    def distances(self, queries: Array, ids: Array) -> Array:
        del queries  # the LUT already encodes the query side
        safe = jnp.maximum(ids, 0)
        codes = self.codes[safe]  # (B, M, m_sub)
        # d[b,m] = sum_s lut[b, s, codes[b,m,s]]
        gathered = jnp.take_along_axis(
            self.lut[:, None, :, :],  # (B, 1, m_sub, n_cent)
            codes[..., None],  # (B, M, m_sub, 1)
            axis=-1,
        )[..., 0]
        return jnp.sum(gathered, axis=-1)

    def sample_distances(self, queries: Array, sample_ids: Array) -> Array:
        b = self.lut.shape[0]
        ids_b = jnp.broadcast_to(sample_ids[None, :], (b, sample_ids.shape[0]))
        return self.distances(queries, ids_b)

    def scan_all(self) -> Array:
        """ADC distances to every corpus row: (B, n) — the linear-scan
        baseline's hot loop (core/pq.py), sharing this backend's tables."""
        gathered = jnp.take_along_axis(
            self.lut[:, None, :, :],  # (B, 1, m_sub, n_cent)
            self.codes[None, :, :, None],  # (1, n, m_sub, 1)
            axis=-1,
        )[..., 0]
        return jnp.sum(gathered, axis=-1)

    def fused_expand(
        self, queries: Array, ids: Array, visited: Array, tables: ConstraintTables
    ) -> Tuple[Array, Array, Array]:
        del queries
        from repro.kernels.fused_expand.ops import fused_expand_adc

        return fused_expand_adc(
            self.lut, self.codes, ids, visited,
            tables.meta, tables.cons, tables.tomb, family=tables.family,
            config=self.config,
        )


DistanceBackend = Union[ExactBackend, L2KernelBackend, PQBackend]


@pytree_dataclass
class TraversalContext:
    """Everything the engine scores/filters with, resolved once per search.

    backend  — the distance path (arrays it scores with are pytree children,
               so per-shard contexts shard with their corpus rows);
    tables   — the constraint's raw table views for in-kernel evaluation,
               None for UDF closures (which force the unfused path); carries
               the corpus tombstone bitmap (streaming mutable index) so the
               fused kernels mask deleted slots exactly like a failed
               constraint — the unfused path gets the same mask via the
               tombstone-wrapped ``satisfied`` closure;
    satisfied — the (B, M) ids -> bool constraint closure (static: it is
               trace-time code, never crosses a jit boundary as data);
    fuse     — the resolved fuse decision (static: it selects the compiled
               loop body).
    """

    backend: DistanceBackend
    tables: Optional[ConstraintTables]
    satisfied: SatisfiedFn = static_field()
    fuse: bool = static_field(default=False)


def build_context(
    corpus: Corpus,
    constraint,
    queries: Array,
    params: SearchParams,
    pq_index=None,
    degree: int = 0,
) -> TraversalContext:
    """Resolve (params, constraint, corpus) into one TraversalContext.

    Called once per (local or per-shard) search: selects the distance
    backend from ``params.approx`` / ``params.use_kernel``, builds the
    constraint closure and its raw table views (including the precompiled
    UDF predicate column whenever the fused path is reachable — UDFs are
    no longer ``fusable=False``), resolves the kernel block-shape configs
    from the committed tuning table (``repro.tune``, keyed on payload
    width x ``degree`` x beam x platform; nearest-shape fallback, pure
    host-side python at trace time), and fixes the fuse decision. Raises
    for approx="pq" without a pq_index. ``degree`` is the graph degree
    when the caller has one (0 = unknown: the table lookup then matches
    on the remaining key dims).
    """
    satisfied = make_satisfied_fn(constraint, corpus)
    # The UDF predicate table costs an O(n) sweep, so it is only built
    # when the fused path could consume it; label/range views are free.
    tables = constraint_tables(
        constraint, corpus, include_udf=params.fuse_expand != "off"
    )
    platform = jax.default_backend()
    beam = params.beam_width
    if params.approx == "pq":
        if pq_index is None:
            raise ValueError("approx='pq' requires pq_index")
        from repro.core.pq import adc_table

        backend: DistanceBackend = PQBackend(
            codes=pq_index.codes,
            lut=adc_table(pq_index, queries),
            config=tune_lookup(
                "fused_adc", d=int(pq_index.codes.shape[1]),
                deg=degree, beam=beam, platform=platform,
            ),
        )
    elif params.use_kernel:
        backend = L2KernelBackend(
            vectors=corpus.vectors,
            config=tune_lookup(
                "fused_exact", d=corpus.dim, deg=degree, beam=beam,
                platform=platform,
            ),
            gd_config=tune_lookup(
                "gather_distance", d=corpus.dim, deg=degree, beam=beam,
                platform=platform,
            ),
        )
    else:
        backend = ExactBackend(
            vectors=corpus.vectors,
            config=tune_lookup(
                "fused_exact", d=corpus.dim, deg=degree, beam=beam,
                platform=platform,
            ),
        )

    fusable = tables is not None and backend.fusable
    if params.fuse_expand == "on" and not fusable:
        raise ValueError(
            "fuse_expand='on' requires constraint tables (got a "
            "non-constraint object the kernels cannot evaluate)"
        )
    fuse = params.fuse_expand == "on" or (
        params.fuse_expand == "auto"
        and resolve_auto_fuse(fusable, platform)
    )
    return TraversalContext(
        backend=backend, tables=tables, satisfied=satisfied, fuse=fuse
    )
