"""Fixed-capacity, batched priority queues as sorted arrays.

The paper's C++ implementation uses dynamic binary heaps; on TPU we keep each
frontier as a distance-ascending sorted array of static capacity ``C``:

  * empty slots hold ``(+inf, -1)``
  * ``pop``  == take the head, shift everything left by one
  * ``push`` == concatenate, keep the ``C`` smallest, ascending

All operations carry a leading batch axis ``B`` (one queue per query) so the
whole query batch advances in lock-step (DESIGN.md §2).

``queue_push`` has one lowering per platform, chosen from
``jax.default_backend()`` at trace time; both give the same queue, bit for
bit. XLA:CPU selects with ``top_k`` over the ``C + M`` keys and gathers the
ids by the returned positions. On TPU that id gather runs one element at a
time: at C = 1024 it took ~330 us against ~23 us for the sort itself (87%
of a search iteration on a v5e), so there one stable sort carries the ids
beside the distances and the queue is a static slice of its output. On
XLA:CPU that sort measured 1.9-4x slower per push than ``top_k`` + gather,
for (B, C, M) from (8, 64, 16) to (32, 1024, 32).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytree import pytree_dataclass

Array = jax.Array

INF = jnp.inf
PAD_ID = -1


@pytree_dataclass
class BatchedQueue:
    """A batch of fixed-capacity min-queues (sorted ascending by distance)."""

    dists: Array  # (B, C) f32, +inf padded, ascending
    ids: Array  # (B, C) i32, -1 padded

    @property
    def capacity(self) -> int:
        return self.dists.shape[-1]

    @property
    def batch(self) -> int:
        return self.dists.shape[0]


def queue_init(batch: int, capacity: int) -> BatchedQueue:
    return BatchedQueue(
        dists=jnp.full((batch, capacity), INF, dtype=jnp.float32),
        ids=jnp.full((batch, capacity), PAD_ID, dtype=jnp.int32),
    )


def queue_head(q: BatchedQueue) -> tuple[Array, Array]:
    """Best (distance, id) per row; (+inf, -1) when empty."""
    return q.dists[:, 0], q.ids[:, 0]


def queue_nonempty(q: BatchedQueue) -> Array:
    """(B,) bool — does each row hold at least one live element."""
    return jnp.isfinite(q.dists[:, 0])


def queue_size(q: BatchedQueue) -> Array:
    """(B,) number of live elements."""
    return jnp.sum(jnp.isfinite(q.dists), axis=-1).astype(jnp.int32)


def queue_pop(q: BatchedQueue, do_pop: Array) -> tuple[BatchedQueue, Array, Array]:
    """Pop the head of each row where ``do_pop`` (B,) bool is set.

    Rows with ``do_pop == False`` are returned unchanged (their reported
    head is still returned — callers mask on ``do_pop``).
    """
    new, head_d, head_i = queue_pop_n(q, 1, do_pop)
    return new, head_d[:, 0], head_i[:, 0]


def queue_pop_n(
    q: BatchedQueue, n: int, do_pop: Array
) -> tuple[BatchedQueue, Array, Array]:
    """Pop the best ``n`` elements of each row where ``do_pop`` (B,) is set.

    Returns (new_queue, (B, n) dists, (B, n) ids), both ascending per row.
    Empty slots report (+inf, -1); when a row holds fewer than ``n`` live
    elements the trailing slots are padding. Rows with ``do_pop == False``
    are returned unchanged (their best ``n`` are still reported — callers
    mask on ``do_pop``). The beam engine (DESIGN.md §5) uses this to pop a
    whole beam in one shifted copy instead of ``n`` sequential pops.
    """
    c = q.capacity
    if n >= c:
        head_d = jnp.pad(q.dists, ((0, 0), (0, n - c)), constant_values=INF)
        head_i = jnp.pad(q.ids, ((0, 0), (0, n - c)), constant_values=PAD_ID)
        shifted_d = jnp.full_like(q.dists, INF)
        shifted_i = jnp.full_like(q.ids, PAD_ID)
    else:
        head_d, head_i = q.dists[:, :n], q.ids[:, :n]
        shifted_d = jnp.concatenate(
            [q.dists[:, n:], jnp.full((q.batch, n), INF, q.dists.dtype)], axis=-1
        )
        shifted_i = jnp.concatenate(
            [q.ids[:, n:], jnp.full((q.batch, n), PAD_ID, q.ids.dtype)], axis=-1
        )
    new = BatchedQueue(
        dists=jnp.where(do_pop[:, None], shifted_d, q.dists),
        ids=jnp.where(do_pop[:, None], shifted_i, q.ids),
    )
    return new, head_d, head_i


def queue_push(
    q: BatchedQueue, new_d: Array, new_i: Array, valid: Array
) -> BatchedQueue:
    """Insert up to M new elements per row; keep the best ``C``.

    new_d: (B, M) f32, new_i: (B, M) i32, valid: (B, M) bool.
    Invalid entries are masked to (+inf, -1) before the merge. Ties keep
    queue elements first, then the new ones in slot order. On TPU the
    ids ride in a stable sort (``_push_by_sort``); elsewhere ``top_k``
    selects positions and the ids are gathered by them
    (``_push_by_top_k``); the queues are identical (module docstring).
    """
    nd = jnp.where(valid, new_d, INF).astype(q.dists.dtype)
    ni = jnp.where(valid, new_i, PAD_ID).astype(q.ids.dtype)
    all_d = jnp.concatenate([q.dists, nd], axis=-1)  # (B, C+M)
    all_i = jnp.concatenate([q.ids, ni], axis=-1)
    if jax.default_backend() == "tpu":
        return _push_by_sort(all_d, all_i, q.capacity)
    return _push_by_top_k(all_d, all_i, q.capacity)


def _push_by_top_k(all_d: Array, all_i: Array, c: int) -> BatchedQueue:
    # top_k of the negated keys = the C smallest, already ascending — a
    # partial selection network instead of a full (C+M) sort. Measured
    # 3.3x faster end-to-end search on CPU (EXPERIMENTS.md §Perf D5).
    neg, pos = jax.lax.top_k(-all_d, c)
    return BatchedQueue(dists=-neg, ids=jnp.take_along_axis(all_i, pos, axis=-1))


def _push_by_sort(all_d: Array, all_i: Array, c: int) -> BatchedQueue:
    # A stable ascending sort keeps the lower index first on ties, as
    # top_k does, and +inf padding last; the ids travel as a second
    # operand, so no per-element gather follows.
    d, i = jax.lax.sort((all_d, all_i), dimension=-1, is_stable=True, num_keys=1)
    return BatchedQueue(dists=d[:, :c], ids=i[:, :c])


# ---------------------------------------------------------------------------
# Sorted-run machinery for the fused candidate pipeline (EXPERIMENTS.md
# §Perf PR2). Everything below is built from TWO gather-free primitives —
# lexicographic compare-exchange on (key, pos) pairs, and static shifts —
# because on both TPU and XLA:CPU the expensive ops in a queue update are
# comparator sorts and scatters, not elementwise arithmetic. Distances are
# non-negative f32 (squared L2, +inf padding), so their raw bit patterns
# are order-preserving as uint32 ("dist bits"); a per-element position
# makes every (key, pos) pair distinct, which turns the stable-tie-break
# rules of ``top_k`` into an ordinary total order.
# ---------------------------------------------------------------------------

_INF_BITS = jnp.uint32(0x7F800000)  # +inf as its f32 bit pattern


def _dist_bits(d: Array) -> Array:
    """Non-negative f32 (incl. +inf) -> order-preserving uint32 key.

    ``+ 0.0`` canonicalizes a hypothetical -0.0 (bit pattern 0x80000000,
    which would order above +inf) to +0.0 before the bitcast.
    """
    return jax.lax.bitcast_convert_type(
        d.astype(jnp.float32) + 0.0, jnp.uint32
    )


def _bits_dist(u: Array) -> Array:
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _lexmax(ka, pa, kb, pb):
    b_gt = (kb > ka) | ((kb == ka) & (pb > pa))
    return jnp.where(b_gt, kb, ka), jnp.where(b_gt, pb, pa)


def _lexmin(ka, pa, kb, pb):
    b_lt = (kb < ka) | ((kb == ka) & (pb < pa))
    return jnp.where(b_lt, kb, ka), jnp.where(b_lt, pb, pa)


def bitonic_sort_pairs(key: Array, pos: Array) -> tuple[Array, Array]:
    """Ascending row-sort of (B, n) by (key, pos); n must be a power of two.

    The classic bitonic network: log2(n)*(log2(n)+1)/2 compare-exchange
    stages, each a reshape + elementwise lexicographic min/max — no
    comparator sort, no gathers, TPU-vectorizable as-is. ``pos`` uniqueness
    makes the order total, so the result is deterministic under ties.
    """
    b, n = key.shape
    log_n = int(math.log2(n))
    assert 1 << log_n == n, f"bitonic sort needs a power-of-two width, got {n}"
    for blk_log in range(1, log_n + 1):
        for s_log in range(blk_log - 1, -1, -1):
            s = 1 << s_log
            nb = n // (2 * s)
            # ascending for even blocks of size 2**blk_log, else descending
            asc = ((jnp.arange(nb) * 2 * s) >> blk_log) % 2 == 0
            k4 = key.reshape(b, nb, 2, s)
            p4 = pos.reshape(b, nb, 2, s)
            a = asc[None, :, None]
            lo_k, lo_p = _lexmin(k4[:, :, 0], p4[:, :, 0], k4[:, :, 1], p4[:, :, 1])
            hi_k, hi_p = _lexmax(k4[:, :, 0], p4[:, :, 0], k4[:, :, 1], p4[:, :, 1])
            key = jnp.stack(
                [jnp.where(a, lo_k, hi_k), jnp.where(a, hi_k, lo_k)], axis=2
            ).reshape(b, n)
            pos = jnp.stack(
                [jnp.where(a, lo_p, hi_p), jnp.where(a, hi_p, lo_p)], axis=2
            ).reshape(b, n)
    return key, pos


def sort_run(d: Array, i: Array, valid: Array) -> tuple[Array, Array]:
    """Mask + sort a small batch into an ascending (+inf, -1)-padded run.

    d/i/valid: (B, M). Stable under distance ties (original index order),
    i.e. exactly the candidate order ``queue_push`` would honour — the
    output is a valid ``queue_merge_sorted`` run. Width is padded to the
    next power of two internally.
    """
    b, m = d.shape
    mp = 1 << max(1, math.ceil(math.log2(m))) if m > 1 else 2
    key = jnp.where(valid, _dist_bits(d), jnp.uint32(0xFFFFFFFF))
    pos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None, :], (b, m))
    if mp != m:
        key = jnp.pad(key, ((0, 0), (0, mp - m)), constant_values=np.uint32(0xFFFFFFFF))
        pos = jnp.pad(pos, ((0, 0), (0, mp - m)), constant_values=2**30)
    key, pos = bitonic_sort_pairs(key, pos)
    key, pos = key[:, :m], pos[:, :m]
    n_valid = jnp.sum(valid, axis=-1, keepdims=True, dtype=jnp.int32)
    live = jnp.arange(m, dtype=jnp.int32)[None, :] < n_valid
    safe = jnp.minimum(pos, m - 1)
    out_d = jnp.where(live, jnp.take_along_axis(d, safe, axis=-1), INF)
    out_i = jnp.where(live, jnp.take_along_axis(i, safe, axis=-1), PAD_ID)
    return out_d, out_i


def partition_sorted_runs(
    d: Array, i: Array, first: Array, second: Array, cap_first: int, cap_second: int
) -> tuple[tuple[Array, Array], tuple[Array, Array]]:
    """Split a candidate batch into two ascending runs with ONE sort.

    d/i: (B, M); ``first``/``second``: disjoint membership masks (elements
    in neither are dropped). Folds the partition into the top key bit —
    squared distances never use it — so a single bitonic pass yields
    [first-run | second-run | dropped], each segment ascending and
    tie-stable in original index order. Runs are truncated to their
    target queue's capacity (elements beyond rank C can never survive a
    merge) and padded with (+inf, -1).
    """
    b, m = d.shape
    mp = 1 << max(1, math.ceil(math.log2(m))) if m > 1 else 2
    bits = _dist_bits(d)
    key = jnp.where(
        first, bits,
        jnp.where(second, bits + jnp.uint32(0x80000000), jnp.uint32(0xFFFFFFFF)),
    )
    pos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None, :], (b, m))
    if mp != m:
        key = jnp.pad(key, ((0, 0), (0, mp - m)), constant_values=np.uint32(0xFFFFFFFF))
        pos = jnp.pad(pos, ((0, 0), (0, mp - m)), constant_values=2**30)
    key, pos = bitonic_sort_pairs(key, pos)
    n_first = jnp.sum(first, axis=-1, keepdims=True, dtype=jnp.int32)
    n_second = jnp.sum(second, axis=-1, keepdims=True, dtype=jnp.int32)

    def extract(offset, count, width):
        ar = jnp.arange(width, dtype=jnp.int32)[None, :]
        seg = jnp.minimum(ar + offset, mp - 1)
        p = jnp.minimum(jnp.take_along_axis(pos, seg, axis=-1), m - 1)
        live = ar < count
        run_d = jnp.where(live, jnp.take_along_axis(d, p, axis=-1), INF)
        run_i = jnp.where(live, jnp.take_along_axis(i, p, axis=-1), PAD_ID)
        return run_d, run_i

    zero = jnp.zeros_like(n_first)
    run1 = extract(zero, n_first, min(m, cap_first))
    run2 = extract(n_first, n_second, min(m, cap_second))
    return run1, run2


def queue_merge_sorted(
    q: BatchedQueue, run_d: Array, run_i: Array
) -> BatchedQueue:
    """Merge an ascending (+inf, -1)-padded run into the queue; keep best C.

    Bit-for-bit equal to ``queue_push(q, run_d, run_i, isfinite(run_d))``
    — including every distance-tie (queue element first, then run order),
    property-tested in tests/test_queue.py — but built as a *windowed
    min-max merge* instead of a ``top_k`` re-selection over C+M keys:
    since both sides are sorted, the (j+1)-th smallest of the union is

        merged[j] = min_{t=0..R} max(queue[j-t], run[t-1])

    (out-of-range terms are ∓inf sentinels). Each ``t`` is a static shift
    plus an elementwise lexicographic min/max on (dist-bits, position)
    pairs — no gathers, no sort — so the cost is O(C·R) vector ops with a
    tiny constant. For the fused engine's run lengths (R = beam·deg ≤ 64)
    this measures 1.3–4.6× faster than the ``top_k(C+M)`` push on CPU and
    maps onto pure VPU work on TPU (EXPERIMENTS.md §Perf PR2); the
    re-selection stays the right tool for unsorted pushes.
    """
    b, c = q.dists.shape
    r = run_d.shape[-1]
    qk = _dist_bits(q.dists)
    qp = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[None, :], (b, c))
    rk = _dist_bits(run_d)
    rp = jnp.broadcast_to(jnp.arange(c, c + r, dtype=jnp.int32)[None, :], (b, r))

    # One left-extension of the queue (−inf sentinels: key 0, pos −1) turns
    # every shifted term queue[j − t] into a static slice.
    ext_k = jnp.concatenate([jnp.zeros((b, r), jnp.uint32), qk], axis=-1)
    ext_p = jnp.concatenate([jnp.full((b, r), -1, jnp.int32), qp], axis=-1)
    cur_k, cur_p = qk, qp  # t = 0: max(queue[j], run[-1] = -inf) = queue[j]
    for t in range(1, r + 1):
        a_k = ext_k[:, r - t : r - t + c]
        a_p = ext_p[:, r - t : r - t + c]
        cand_k, cand_p = _lexmax(a_k, a_p, rk[:, t - 1 : t], rp[:, t - 1 : t])
        cur_k, cur_p = _lexmin(cur_k, cur_p, cand_k, cand_p)

    out_d = _bits_dist(jnp.minimum(cur_k, _INF_BITS))
    all_i = jnp.concatenate([q.ids, run_i], axis=-1)
    gathered = jnp.take_along_axis(all_i, cur_p, axis=-1)
    out_i = jnp.where(jnp.isfinite(out_d), gathered, PAD_ID)
    return BatchedQueue(dists=out_d, ids=out_i)


def queue_worst_finite(q: BatchedQueue) -> Array:
    """(B,) distance of the worst live element; -inf when empty.

    Used for the ``topk`` result list: termination compares the candidate
    against the K-th best so far (+inf while the list is not yet full — the
    caller handles the not-full case via ``queue_size``).
    """
    masked = jnp.where(jnp.isfinite(q.dists), q.dists, -INF)
    return jnp.max(masked, axis=-1)


def topk_threshold(q: BatchedQueue, k: int) -> Array:
    """(B,) value of the k-th slot (== +inf until the list holds k items).

    The result list has capacity exactly ``k`` and stays sorted, so slot
    ``k-1`` is the current worst of the top-k — the paper's
    ``topk.peek_max()`` with the ``|topk| = K`` condition folded in (slot is
    +inf while not full, which disables early termination, as in Alg. 1/2).
    """
    del k  # capacity of the queue *is* k
    return q.dists[:, -1]
