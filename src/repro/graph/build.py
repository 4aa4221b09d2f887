"""Proximity-graph construction.

Two builders, one contract:

  * ``build_knn_graph`` — blocked *exact* kNN graph (quadratic; the default
    for n up to a few hundred thousand on this host, and the oracle for the
    approximate builder),
  * ``nn_descent`` — iterative neighbor-of-neighbor refinement for large n
    (near-linear per round; Dong et al., WWW'11), used above the exact
    builder's practical range.

Both emit the invariants the searcher and the Eq.-1 estimator rely on:
adjacency rows are distance-ascending, self-free, duplicate-free, and padded
with -1. ``add_reverse_edges`` optionally symmetrizes (HNSW-style) under the
same degree bound, which materially improves reachability for clustered data.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.distances import squared_l2

Array = jax.Array

PAD = -1


def _dedup_sorted_by_dist(ids: Array, dists: Array, degree: int) -> tuple[Array, Array]:
    """Per-row: drop duplicate ids / invalid, keep the ``degree`` closest.

    ids: (n, C) int32 (PAD for invalid), dists: (n, C) f32.
    """
    invalid = ids < 0
    d = jnp.where(invalid, jnp.inf, dists)
    # Sort by id to find duplicates, keep the first (smallest distance wins
    # later anyway because duplicates share the same distance).
    id_order = jnp.argsort(jnp.where(invalid, jnp.iinfo(jnp.int32).max, ids), axis=-1)
    ids_s = jnp.take_along_axis(ids, id_order, axis=-1)
    d_s = jnp.take_along_axis(d, id_order, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(ids_s[:, :1], bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=-1
    )
    d_s = jnp.where(dup, jnp.inf, d_s)
    # Now sort by distance and trim.
    order = jnp.argsort(d_s, axis=-1)
    ids_f = jnp.take_along_axis(ids_s, order, axis=-1)[:, :degree]
    d_f = jnp.take_along_axis(d_s, order, axis=-1)[:, :degree]
    ids_f = jnp.where(jnp.isfinite(d_f), ids_f, PAD)
    return ids_f, d_f


# Bytes of one block's (block, n) f32 score matrix: an eighth of a 16 GB
# chip (512 rows at n = 1M), so a 1M-row corpus builds on one device.
SCORE_BLOCK_BYTES = 2 << 30
# Widest row a single top_k sees. On a v5e one top_k over a 1M-wide score
# row takes about 2.7x as long as cutting the row down by group minima
# first (smallest_k; PERF.md).
TOPK_CHUNK = 4096
# Columns per group whose minimum stands for the group (one lane tile).
TOPK_GROUP = 128


def _score_block(n: int) -> int:
    """Rows per kNN block: a power of two in [8, 4096] whose (block, n) f32
    score matrix fits ``SCORE_BLOCK_BYTES``."""
    block = 4096
    while block > 8 and block * n * 4 > SCORE_BLOCK_BYTES:
        block //= 2
    return block


def smallest_k(
    d: Array, k: int, chunk: int | None = None, group: int | None = None
) -> tuple[Array, Array]:
    """Exact ``k`` smallest entries per row of ``d`` -> (dists, columns).

    Equal to ``lax.top_k(-d, k)``, ties included (the lower column first),
    but no top_k sees a row wider than ``max(chunk, k * group)`` (defaults
    ``TOPK_CHUNK``, ``TOPK_GROUP``). A wider row is cut into groups of
    ``group`` adjacent columns; the k groups with the smallest minima are
    found the same way, recursively, and one top_k over their columns
    finishes. Exact: a group holding one of the row's k smallest has its
    minimum at or below the k-th smallest value, and at most k groups do
    (each holds a distinct such entry); among groups tied at that value the
    lower ones hold the lower tied columns, which top_k prefers too.
    """
    chunk = TOPK_CHUNK if chunk is None else chunk
    group = TOPK_GROUP if group is None else group
    rows, width = d.shape
    if width <= max(chunk, k * group):
        neg, pos = jax.lax.top_k(-d, k)
        return -neg, pos
    c = -(-width // group)
    dg = jnp.pad(d, ((0, 0), (0, c * group - width)), constant_values=jnp.inf)
    dg = dg.reshape(rows, c, group)
    _, groups = smallest_k(jnp.min(dg, axis=-1), k, chunk, group)
    groups = jnp.sort(groups, axis=-1)  # candidates in column order
    cand = jnp.take_along_axis(dg, groups[:, :, None], axis=1)
    neg, pos = jax.lax.top_k(-cand.reshape(rows, k * group), k)
    cols = jnp.take_along_axis(groups, pos // group, axis=-1) * group + pos % group
    return -neg, cols


def build_knn_graph(vectors: Array, degree: int, block: int | None = None) -> Array:
    """Exact kNN adjacency (n, degree), distance-ascending, self excluded.

    Scores ``block`` rows against the corpus at a time (default: the
    largest power of two up to 4096 whose score matrix fits
    ``SCORE_BLOCK_BYTES``)."""
    n = vectors.shape[0]
    return _build_knn_graph(
        vectors, degree, block if block is not None else _score_block(n)
    )


@partial(jax.jit, static_argnames=("degree", "block"))
def _build_knn_graph(vectors: Array, degree: int, block: int) -> Array:
    n, _ = vectors.shape
    n_blocks = (n + block - 1) // block
    pad = n_blocks * block - n
    padded = jnp.pad(vectors, ((0, pad), (0, 0)))

    def row_block(blk):
        rows = jax.lax.dynamic_slice_in_dim(padded, blk * block, block, axis=0)
        d = squared_l2(rows, vectors)  # (block, n)
        rid = blk * block + jnp.arange(block)
        cid = jnp.arange(n)
        d = jnp.where(cid[None, :] == rid[:, None], jnp.inf, d)  # no self
        d = jnp.where(rid[:, None] < n, d, jnp.inf)  # padding rows
        dist, idx = smallest_k(d, degree)
        idx = jnp.where(jnp.isfinite(dist), idx, PAD)
        return idx.astype(jnp.int32), dist

    idx, dist = jax.lax.map(row_block, jnp.arange(n_blocks))
    del dist
    return idx.reshape(-1, degree)[:n]


# Rows per nn_descent candidate-distance block: bounds the gathered
# (rows, C, d) tensor to a small share of device memory at any n.
NND_ROW_BLOCK = 8192


@partial(jax.jit, static_argnames=("degree", "iters", "n_extra"))
def nn_descent(
    rng: Array, vectors: Array, degree: int, iters: int = 8, n_extra: int = 2
) -> Array:
    """NN-descent approximate kNN graph.

    Each round considers, per vertex: current neighbors, a sample of
    neighbors-of-neighbors (``n_extra`` per neighbor), and fresh random
    vertices; keeps the ``degree`` closest.
    """
    n, _ = vectors.shape
    row_blk = min(n, NND_ROW_BLOCK)

    n_blk = -(-n // row_blk)
    own_p = jnp.pad(vectors, ((0, n_blk * row_blk - n), (0, 0)))

    def dist_rows(ids: Array) -> Array:  # (n, C) -> (n, C)
        ids_p = jnp.pad(ids, ((0, n_blk * row_blk - n), (0, 0)), constant_values=-1)

        def one(blk):
            b_ids = jax.lax.dynamic_slice_in_dim(ids_p, blk * row_blk, row_blk)
            own = jax.lax.dynamic_slice_in_dim(own_p, blk * row_blk, row_blk)
            rows = vectors[jnp.maximum(b_ids, 0)]
            diff = rows - own[:, None, :]
            return jnp.sum(diff * diff, axis=-1)

        d = jax.lax.map(one, jnp.arange(n_blk)).reshape(-1, ids.shape[1])[:n]
        self_or_pad = (ids == jnp.arange(n)[:, None]) | (ids < 0)
        return jnp.where(self_or_pad, jnp.inf, d)

    k0 = jax.random.randint(rng, (n, degree), 0, n, dtype=jnp.int32)
    nbrs, _ = _dedup_sorted_by_dist(k0, dist_rows(k0), degree)

    def round_fn(carry, r):
        nbrs = carry
        rng_r = jax.random.fold_in(rng, r)
        safe = jnp.maximum(nbrs, 0)
        # neighbor-of-neighbor sample: for each neighbor take n_extra of its edges
        cols = jax.random.randint(rng_r, (n, degree, n_extra), 0, degree)
        nn2 = jnp.take_along_axis(
            nbrs[safe], cols, axis=-1
        ).reshape(n, degree * n_extra)
        rand = jax.random.randint(
            jax.random.fold_in(rng_r, 1), (n, degree), 0, n, dtype=jnp.int32
        )
        cand = jnp.concatenate([nbrs, nn2, rand], axis=-1)
        new, _ = _dedup_sorted_by_dist(cand, dist_rows(cand), degree)
        return new, None

    nbrs, _ = jax.lax.scan(round_fn, nbrs, jnp.arange(iters))
    return nbrs


# Bytes of the gathered (rows, deg, d) f32 rows add_reverse_edges scores
# at once, and of the (rows, deg + width) candidate keys it ranks at once.
REVERSE_CHUNK_BYTES = 1 << 30
_NO_KEY = np.uint64(np.iinfo(np.uint64).max)


@jax.jit
def _edge_dists(vectors, nbrs, row_ids):
    rows = vectors[jnp.maximum(nbrs, 0)]
    return jnp.sum((rows - vectors[row_ids][:, None, :]) ** 2, axis=-1)


def _closest_distinct(ids: np.ndarray, d: np.ndarray, rows: np.ndarray, degree: int):
    """Per row of (R, C) candidates: the ``degree`` closest distinct ids,
    ties to the lower id, PAD-filled; PAD and self candidates never count.

    Ranks 64-bit keys on the host: an f32 distance >= 0 orders like its
    bit pattern, so (id, distance) and (distance, id) pairs each pack into
    one sortable word.
    """
    ok = (ids >= 0) & (ids != rows[:, None])
    bits = d.astype(np.float32).view(np.uint32).astype(np.uint64)
    idw = ids.astype(np.uint32).astype(np.uint64)
    by_id = np.sort(np.where(ok, (idw << 32) | bits, _NO_KEY), axis=-1)
    dup = np.zeros(by_id.shape, bool)
    dup[:, 1:] = (by_id[:, 1:] >> 32) == (by_id[:, :-1] >> 32)
    by_id[dup] = _NO_KEY
    live = by_id != _NO_KEY
    by_d = np.where(live, ((by_id & 0xFFFFFFFF) << 32) | (by_id >> 32), _NO_KEY)
    top = np.sort(by_d, axis=-1)[:, :degree]
    out = np.where(top != _NO_KEY, (top & 0xFFFFFFFF).astype(np.int64), PAD)
    if out.shape[1] < degree:
        out = np.pad(out, ((0, 0), (0, degree - out.shape[1])), constant_values=PAD)
    return out.astype(np.int32)


def add_reverse_edges(neighbors: Array, vectors: Array, degree: int) -> Array:
    """Symmetrize under the degree bound (build-time only).

    Each vertex's candidates are its own out-edges and the vertices that
    list it; the ``degree`` closest distinct ones are kept, ties to the
    lower id. Each directed edge's distance is computed once on the device,
    in row blocks of ``REVERSE_CHUNK_BYTES``; a reverse candidate reuses it
    (the squared differences are the same numbers either way). Ranking runs
    on the host. In-degrees are heavy-tailed (a few hubs are listed by
    thousands), so rows are ranked in classes of reverse-list width (powers
    of 4), each padded only to its class width.
    """
    nbrs = np.asarray(neighbors).astype(np.int32)
    n, deg = nbrs.shape
    vecs = jnp.asarray(vectors)
    blk = int(min(n, max(1, REVERSE_CHUNK_BYTES // (deg * vecs.shape[1] * 4))))
    n_pad = -(-n // blk) * blk
    nbrs_p = jnp.asarray(np.pad(nbrs, ((0, n_pad - n), (0, 0)), constant_values=PAD))
    row_p = jnp.minimum(jnp.arange(n_pad, dtype=jnp.int32), n - 1)
    edge_d = np.concatenate([
        np.asarray(_edge_dists(vecs, nbrs_p[r0:r0 + blk], row_p[r0:r0 + blk]))
        for r0 in range(0, n_pad, blk)
    ])[:n]
    # Reverse lists: the edges sorted on their target.
    src = np.repeat(np.arange(n, dtype=np.int32), deg)
    dst = nbrs.reshape(-1)
    keep = dst >= 0
    order = np.argsort(dst[keep], kind="stable")
    src, src_d = src[keep][order], edge_d.reshape(-1)[keep][order]
    counts = np.bincount(dst[keep], minlength=n)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    width = np.ones(n, np.int64)
    while np.any(width < counts):
        width = np.where(width < counts, width * 4, width)

    src = np.append(src, np.int32(PAD))  # what an empty slot reads
    src_d = np.append(src_d, np.float32(0))
    out = np.empty((n, degree), np.int32)
    for w in np.unique(width):
        rows = np.flatnonzero(width == w)
        chunk = int(max(1, REVERSE_CHUNK_BYTES // ((deg + w) * 8)))
        for r0 in range(0, len(rows), chunk):
            rr = rows[r0:r0 + chunk]
            slot = np.arange(w)[None, :]
            at = np.where(slot < counts[rr][:, None],
                          starts[rr][:, None] + slot, src.size - 1)
            out[rr] = _closest_distinct(
                np.concatenate([nbrs[rr], src[at]], axis=-1),
                np.concatenate([edge_d[rr], src_d[at]], axis=-1),
                rr, degree,
            )
    return jnp.asarray(out)


def medoid(vectors: Array) -> Array:
    """Approximate medoid: the vector closest to the corpus mean."""
    mean = jnp.mean(vectors.astype(jnp.float32), axis=0, keepdims=True)
    d = squared_l2(mean, vectors)[0]
    return jnp.argmin(d).astype(jnp.int32)
