"""Core value types for the constrained-search system."""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.common.pytree import pytree_dataclass, static_field

Array = jax.Array


@pytree_dataclass
class Corpus:
    """Base vectors plus their attributes.

    vectors: (n, d) float
    labels:  (n,)   int32 — the categorical attribute used by the paper's
             equal / unequal-X% constraint families
    attrs:   (n, m) float32 — optional numeric attributes for range UDFs
    tombstones: (ceil(n/32),) uint32 — optional dead-slot bitmap for the
             streaming mutable index (repro.streaming). A set bit marks a
             slot that must never be RETURNED — deleted-but-unconsolidated
             vertices (still traversable as routing nodes) and free pool
             slots alike. None (the static-index default) means every row
             is live; every constraint family masks against this bitmap
             exactly like a failed constraint (core/constraints.py).
    """

    vectors: Array
    labels: Array
    attrs: Optional[Array] = None
    tombstones: Optional[Array] = None

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]


@pytree_dataclass
class GraphIndex:
    """Proximity-graph index.

    neighbors: (n, deg) int32 adjacency, rows sorted ascending by distance
               to the owning vertex (required by the alter_ratio estimator,
               Eq. 1), padded with -1.
    sample_ids: (s,) int32 — pre-drawn corpus sample for AIRSHIP-Start.
    entry_point: () int32 — medoid-ish global entry vertex.
    """

    neighbors: Array
    sample_ids: Array
    entry_point: Array

    @property
    def degree(self) -> int:
        return self.neighbors.shape[1]


@pytree_dataclass
class SearchParams:
    """Static search configuration (hashable — part of the jit cache key)."""

    mode: str = static_field(default="prefer")  # vanilla|start|alter|prefer
    k: int = static_field(default=10)
    # Result-list capacity used for the termination test. Alg. 1/2 use
    # exactly k ("|topk| = K and now_dist > topk.peek_max()"); production
    # graph searches sweep an HNSW-style ef >= k for the QPS/recall
    # trade-off. 0 -> max(k, 64).
    ef_result: int = static_field(default=0)
    ef_sat: int = static_field(default=128)
    ef_other: int = static_field(default=128)
    n_start: int = static_field(default=32)
    max_iters: int = static_field(default=512)
    # Beam width: vertices popped per query per lock-step iteration
    # (engine/expand.py). 1 reproduces the paper's one-pop-per-hop loop
    # bit-for-bit; wider beams amortize the fused gather+distance launch
    # over beam*deg candidates at the cost of expanding against a
    # threshold that is one iteration stale (DESIGN.md §5).
    beam_width: int = static_field(default=1)
    # None -> estimate per-query via the Eq.-1 kNN statistic.
    alter_ratio: Optional[float] = static_field(default=None)
    alter_ratio_k: int = static_field(default=16)
    # Selects L2KernelBackend (Pallas gather_distance) over ExactBackend
    # for the unfused distance path; identical mathematics, one HBM visit
    # per candidate. Backend selection flows through the TraversalContext
    # (engine/context.py) — no engine layer reads this directly.
    use_kernel: bool = static_field(default=False)
    # Fused candidate pipeline (kernels/fused_expand/): gather + distance +
    # constraint + visited masking in one pass, frontier updates via sorted
    # merges instead of top_k re-selection (engine/loop.py). "auto" targets
    # TPU only — and only for constraint families with in-kernel evaluation
    # (LabelSet / Range) — gated on FUSE_AUTO_ON_TPU
    # (engine/context.py::resolve_auto_fuse); on other
    # backends native top_k wins in-loop so auto stays unfused
    # (EXPERIMENTS.md §Perf PR2). Every distance backend has a fused
    # kernel (exact rows or PQ code rows + in-kernel ADC sums, §Perf PR3);
    # only UDF constraints force the unfused path. Off-TPU the fused path
    # dispatches to the jnp oracle and returns bit-identical results, so
    # "on"/"off" are safe to force; the TPU kernels reduce in a different
    # FP order (ties may break differently) and stay behind
    # FUSE_AUTO_ON_TPU until a chip benchmark picks the default.
    fuse_expand: str = static_field(default="auto")  # auto | on | off
    # Beyond-paper: traverse with PQ/ADC approximate distances (PQBackend,
    # 32x fewer HBM bytes per candidate at d=128/m_sub=16), then exact
    # re-rank of the ef_result survivors. Requires passing pq_index to
    # constrained_search.
    approx: str = static_field(default="exact")  # exact | pq

    def __post_init__(self):
        if self.mode not in ("vanilla", "start", "alter", "prefer"):
            raise ValueError(f"unknown search mode: {self.mode}")
        if self.approx not in ("exact", "pq"):
            raise ValueError(f"unknown approx mode: {self.approx}")
        if self.beam_width < 1:
            raise ValueError(f"beam_width must be >= 1, got {self.beam_width}")
        if self.fuse_expand not in ("auto", "on", "off"):
            raise ValueError(f"unknown fuse_expand mode: {self.fuse_expand}")

    @property
    def result_capacity(self) -> int:
        return self.ef_result if self.ef_result > 0 else max(self.k, 64)


@pytree_dataclass
class SearchStats:
    """Per-query instrumentation (hardware-independent cost measures)."""

    dist_evals: Array  # (B,) int32 — distance computations performed
    hops: Array  # (B,) int32 — vertices expanded
    visited: Array  # (B,) int32 — vertices touched
    iters: Array  # ()  int32 — lock-step iterations of the batch
    # (B, beam_width) int32 — per-beam-slot expansion counts: how many
    # iterations each slot actually expanded a vertex. Column 0 equals the
    # single-pop ``hops`` at beam_width=1; trailing columns quantify how
    # well wide beams stay fed (engine/expand.py). Locally
    # sum(beam_expansions, -1) == hops; in the distributed merge the two
    # intentionally diverge — beam_expansions psums across shards (a work
    # measure, like dist_evals) while hops pmaxes (critical-path measure).
    beam_expansions: Optional[Array] = None


@pytree_dataclass
class SearchResult:
    dists: Array  # (B, K) f32 ascending, +inf padded when fewer than K found
    ids: Array  # (B, K) int32, -1 padded
    stats: SearchStats

    @property
    def filled(self) -> Array:
        """(B,) int32 — result slots actually filled (id >= 0).

        The under-fill signal the paper's Fig. 1 is about: ``filled < k``
        means the walk exhausted its budget before finding k satisfying
        vertices. Callers (serve driver, serving controller, benchmarks)
        read this instead of re-deriving ``sum(ids >= 0)``.
        """
        return jnp.sum(self.ids >= 0, axis=-1, dtype=jnp.int32)


SatisfiedFn = Callable[[Array], Array]  # (B, M) ids -> (B, M) bool
