"""Fused constrained-expansion kernels — the whole candidate pipeline in one pass.

For a batch of queries Q (B, d) and a flattened (B, M = beam*deg) candidate
id batch, ONE ``pallas_call`` performs what the unfused engine spreads over
three independent HBM round trips per iteration (EXPERIMENTS.md §Perf PR2):

  * corpus-row gather + squared-L2 distance   (was: gather_distance / jnp)
  * constraint evaluation against the corpus label / attribute tables
    (was: a second per-candidate metadata gather in ``satisfied()``)
  * visited-bitset probe + padding masking    (was: ``visited_test``)

emitting ``(dists, satisfied, fresh)`` without ever materializing the
(B, M, d) gathered tensor or re-gathering per-candidate metadata.

TPU mapping: the id matrix is *scalar-prefetched* (SMEM) and drives manual
pipelined row DMAs over a ``(B / QB, M / M_blk)`` grid. Each grid step owns
QB = ``min(8, B)`` queries (one f32 sublane tile) and an ``(QB, M_blk)``
output tile; it streams its ``QB * M_blk`` candidates' corpus rows through
a ``dma_depth``-slot VMEM ring and their 4-byte metadata words through an
SMEM ring, overlapping up to ``dma_depth - 1`` upcoming copies with the
current row's VPU distance reduction. The per-query operands (query rows,
constraint words / bounds, visited-bitset words) ride along as (QB, ·)
VMEM blocks revisited across the inner grid axis. Three layout rules of
the TPU compiler shape the body:

  * every block's last two dims are multiples of (8, 128) or the whole
    array (``M_blk`` is one tile of ``round_up(M, 8)`` or whole 128-lane
    tiles — ``repro.tune.config.lane_tile``);
  * VMEM cannot take scalar stores, so the three output tiles are carried
    through the candidate loop as values and stored once;
  * VMEM cannot load one word at a dynamic lane, so a bitmap probe loads
    the aligned 128-word window holding the word and picks it by lane
    compare (``_bit``); bitmaps wider than one window are padded to whole
    windows (``core.visited`` allocates them that way).

Block shapes are no longer fixed: ``m_blk`` (an output-tile-width CAP),
``dma_depth`` (2..4) and the ADC kernel's ``lut_tile`` come from
``repro.tune.KernelConfig`` via the ops.py wrappers — the autotuner
(DESIGN.md §11) sweeps that lattice and every point is bit-identical by
construction: tiling/pipelining only reorders DMAs, never the
per-candidate arithmetic.

Two distance variants share the layout (PR3):

  * ``fused_expand_kernel``     — exact squared L2 over (1, d) corpus rows.
  * ``fused_expand_adc_kernel`` — PQ/ADC: the DMA streams (1, m_sub) *code*
    rows (m_sub words instead of d floats — 32x fewer HBM bytes at d=128,
    m_sub=16) and the distance is a per-subspace LUT gather + sum against
    the query's ADC table, held transposed as (n_cent, m_sub) so the code
    row broadcasts down the sublanes. The gather is a one-hot
    compare-select-reduce (``broadcasted_iota`` against the code row) —
    plain VPU work, no dynamic VMEM indexing — evaluated in
    ``lut_tile``-row slices when tiled. Each code row selects exactly one
    entry per subspace, picked by a max over -inf padding, which is exact:
    every ``lut_tile`` produces identical bits.

Constraint families (static ``family`` switch, one compiled kernel each):

  * ``"label"`` — LabelSet bitmask: meta table is the (n,) int32 label
    column, per-query operand is the (B, Lw) uint32 allowed-label words.
  * ``"range"`` — numeric window: meta table is the (n,) f32 attribute
    column, per-query operand is the (B, 2) f32 [lo, hi] bounds.
  * ``"udf"``   — precompiled predicate table: meta is the (n,) int32
    verdict column (the UDF evaluated over every vertex at table-build
    time — core/constraints.py), non-zero means satisfied. There is no
    per-query operand; the cons block is a (1, 1) dummy pinned to block
    (0, 0). This removed the last ``fusable=False`` constraint family.

Padding ids (< 0) are redirected to row 0 and reported as (+inf, 0, 0);
``satisfied``/``fresh`` are int32 masks (cast to bool by ops.py) since TPU
output tiles are happier as 32-bit lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.tune.config import lane_tile

Array = jax.Array

WORD_BITS = 32
LANES = 128
QUERY_BLOCK = 8  # f32 sublane tile: queries per grid step

FAMILIES = ("label", "range", "udf")


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def query_block(b: int) -> int:
    """Queries per grid step: one sublane tile, or the whole (smaller) batch."""
    return min(b, QUERY_BLOCK)


def pad_rows(x: Array, rows: int, value=0) -> Array:
    """Pad axis 0 of ``x`` up to ``rows`` (a no-op when already that tall)."""
    if x.shape[0] == rows:
        return x
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=value)


def _bitmap(words: Array) -> Array:
    """(R, W) uint32 bitmap -> int32 (a free bitcast), the word axis padded
    to whole 128-word windows when it is wider than one window."""
    words = jax.lax.bitcast_convert_type(words, jnp.int32)
    w = words.shape[-1]
    if w > LANES and w % LANES:
        words = jnp.pad(words, ((0, 0), (0, LANES - w % LANES)))
    return words


def _bit(ref, idx):
    """Bit ``idx`` of every row of an (R, W) int32 bitmap ref -> (R, 1) bool.

    Loads the whole block when its rows fit one 128-lane window, else the
    aligned window holding word ``idx // 32``, and selects the word by lane
    compare; the caller keeps the row it needs.
    """
    w = idx // WORD_BITS
    if ref.shape[1] <= LANES:
        start = 0
        win = ref[...]
    else:
        start = pl.multiple_of(w - w % LANES, LANES)
        win = ref[:, pl.ds(start, LANES)]
    lane = jax.lax.broadcasted_iota(jnp.int32, win.shape, 1)
    bits = jnp.where(lane == w - start, (win >> (idx % WORD_BITS)) & 1, 0)
    return jnp.max(bits, axis=1, keepdims=True) == 1


def _constraint_ok(family, meta_val, cons_ref):
    """Evaluate the candidate's metadata word against every query's operand
    in the block -> (QB, 1) bool (the caller keeps its own query's row)."""
    if family == "label":
        return _bit(cons_ref, meta_val)
    if family == "udf":
        # Precompiled predicate table: the metadata word IS the verdict.
        return meta_val != 0
    # "range": lane 0 holds lo, lane 1 hi
    bounds = cons_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, bounds.shape, 1)
    lo_ok = jnp.where(bounds <= meta_val, 1, 0)
    hi_ok = jnp.where(meta_val <= bounds, 1, 0)
    inside = jnp.where(lane == 0, lo_ok, hi_ok)
    return jnp.min(inside, axis=1, keepdims=True) == 1


def _cons_spec(family: str, qb: int, cons: Array):
    """Per-query operand block — except "udf", whose (1, 1) dummy is pinned
    to block (0, 0) (the predicate travels in the metadata column)."""
    if family == "udf":
        return pl.BlockSpec(cons.shape, lambda i, j, *_: (0, 0))
    return pl.BlockSpec((qb, cons.shape[1]), lambda i, j, *_: (i, 0))


def _prepare(ids, visited, meta, cons, tomb, family, m_blk, b_pad):
    """Shared operand layout of both fused kernels: padded ids, their
    gathered metadata words, int32 bitmaps, the per-query operand."""
    b, m = ids.shape
    m_blk = lane_tile(m_blk if m_blk is not None else 128, m)
    m_pad = _round_up(m, m_blk)
    ids = ids.astype(jnp.int32)
    if m_pad != m:
        ids = jnp.pad(ids, ((0, 0), (0, m_pad - m)), constant_values=-1)
    ids = pad_rows(ids, b_pad, -1)
    # One 4-byte word per candidate, gathered by XLA and scalar-prefetched
    # beside the ids: Mosaic cannot DMA a single word out of the 1-D HBM
    # column (its tile is 1024 words).
    meta = meta.reshape(-1)[jnp.maximum(ids, 0)]
    if family == "range":
        meta = meta.astype(jnp.float32)
    elif family == "label":
        cons = _bitmap(cons)
    if family != "udf":
        cons = pad_rows(cons, b_pad)
    visited = pad_rows(_bitmap(visited), b_pad)
    tomb = None if tomb is None else _bitmap(tomb.reshape(1, -1))
    return ids, meta, visited, cons, tomb, m_blk, m_pad


def _fused_loop(family, qb, m_blk, refs, outs, distance, ring=None):
    """The candidate loop both fused kernels share: visited / constraint /
    tombstone probes and the three output tiles, carried as values and
    stored once at the end. ``refs`` = (ids, meta, cons, visited, tomb|None).
    ``ring`` = (hbm, buf, sem, depth) streams each candidate's (1, ·) row
    through a ``depth``-slot VMEM ring, overlapping up to ``depth - 1``
    upcoming copies with the current candidate's work;
    ``distance(u, slot)`` scores flat candidate ``u`` -> (QB | 1, 1)."""
    ids_ref, meta_ref, cons_ref, vis_ref, tomb_ref = refs
    i = pl.program_id(0)
    jb = pl.program_id(1)
    n_steps = qb * m_blk

    def at(ref, u):  # flat step -> this candidate's scalar (query-major)
        return ref[i * qb + u // m_blk, jb * m_blk + u % m_blk]

    if ring is not None:
        hbm, buf, sem, depth = ring

        def row_dma(u, slot):
            cid = jnp.maximum(at(ids_ref, u), 0)
            return pltpu.make_async_copy(
                hbm.at[pl.ds(cid, 1), :], buf.at[slot], sem.at[slot]
            )

        # Warm up the pipeline: the first depth-1 candidates' rows in
        # flight (the classic double buffer at depth 2).
        for u0 in range(min(depth - 1, n_steps)):
            row_dma(u0, u0 % depth).start()
    sub = jax.lax.broadcasted_iota(jnp.int32, (qb, m_blk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (qb, m_blk), 1)

    def body(u, carry):
        dist, sat, fresh = carry
        slot = None
        if ring is not None:
            slot = u % depth

            # Keep depth-1 copies in flight: start candidate u + depth - 1's
            # DMA before waiting on candidate u.
            @pl.when(u + depth - 1 < n_steps)
            def _():
                nxt = u + depth - 1
                row_dma(nxt, nxt % depth).start()

            row_dma(u, slot).wait()

        cid = at(ids_ref, u)
        valid = cid >= 0
        sid = jnp.maximum(cid, 0)
        # The probes below test the candidate against all QB queries of the
        # block — as cheap as one row, since a (1, ·) value fills a whole
        # vreg, and free of loads at a dynamic sublane — and `here` keeps
        # the row of the query the candidate belongs to.
        d2 = distance(u, slot)
        unvisited = jnp.logical_not(_bit(vis_ref, sid))
        ok = _constraint_ok(family, at(meta_ref, u), cons_ref)
        if tomb_ref is not None:
            # Tombstone-as-constraint (streaming mutable index): a deleted
            # slot fails `sat` but stays `fresh`-traversable.
            ok = ok & jnp.logical_not(_bit(tomb_ref, sid))

        here = (sub == u // m_blk) & (lane == u % m_blk)
        dist = jnp.where(here, jnp.where(valid, d2, jnp.inf), dist)
        sat = jnp.where(here, (valid & ok).astype(jnp.int32), sat)
        fresh = jnp.where(here, (valid & unvisited).astype(jnp.int32), fresh)
        return dist, sat, fresh

    init = (
        jnp.zeros((qb, m_blk), jnp.float32),
        jnp.zeros((qb, m_blk), jnp.int32),
        jnp.zeros((qb, m_blk), jnp.int32),
    )
    for ref, val in zip(outs, jax.lax.fori_loop(0, n_steps, body, init)):
        ref[...] = val


def _split_refs(refs, with_tomb):
    """(ids, meta, head, cons, visited, [tomb,] *rest) -> (head, shared
    probe refs, rest)."""
    ids_ref, meta_ref, head_ref, cons_ref, vis_ref, *rest = refs
    tomb_ref, rest = (rest[0], rest[1:]) if with_tomb else (None, rest)
    return head_ref, (ids_ref, meta_ref, cons_ref, vis_ref, tomb_ref), rest


def _make_kernel(family, qb, m_blk, with_tomb, dma_depth):
    def kernel(*refs):
        # rest: corpus (n, d) in HBM, 3 (QB, M_blk) outs, row ring, DMA sems.
        q_ref, probe_refs, rest = _split_refs(refs, with_tomb)
        corpus_hbm, *outs, row_buf, row_sem = rest

        def distance(u, slot):
            # VPU reduction of the landed (1, d) row against every query.
            del u
            diff = q_ref[...].astype(jnp.float32) - row_buf[slot].astype(
                jnp.float32
            )
            return jnp.sum(diff * diff, axis=1, keepdims=True)

        _fused_loop(
            family, qb, m_blk, probe_refs, outs, distance,
            ring=(corpus_hbm, row_buf, row_sem, dma_depth),
        )

    return kernel


def _call(kernel, grid_spec, b_pad, m_pad, b, m, interpret, args):
    dists, sat, fresh = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, m_pad), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, m_pad), jnp.int32),
            jax.ShapeDtypeStruct((b_pad, m_pad), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    return dists[:b, :m], sat[:b, :m], fresh[:b, :m]


def _grid_spec(family, qb, m_blk, b_pad, m_pad, head_spec, cons, visited,
               tomb, tail_specs, scratch):
    out_spec = pl.BlockSpec((qb, m_blk), lambda i, j, *_: (i, j))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # candidate ids + their metadata words
        grid=(b_pad // qb, m_pad // m_blk),
        in_specs=[
            head_spec,
            _cons_spec(family, qb, cons),
            pl.BlockSpec((qb, visited.shape[1]), lambda i, j, *_: (i, 0)),
            # The tombstone bitmap is corpus-wide: ONE block revisited by
            # every grid step, unlike the per-query operands.
            *([pl.BlockSpec(tomb.shape, lambda i, j, *_: (0, 0))]
              if tomb is not None else []),
            *tail_specs,
        ],
        out_specs=[out_spec, out_spec, out_spec],
        scratch_shapes=scratch,
    )


@functools.partial(
    jax.jit, static_argnames=("family", "m_blk", "dma_depth", "interpret")
)
def fused_expand_kernel(
    queries: Array,
    corpus: Array,
    ids: Array,
    visited: Array,
    meta: Array,
    cons: Array,
    tomb: Array | None = None,
    *,
    family: str,
    m_blk: int | None = None,
    dma_depth: int = 2,
    interpret: bool = False,
) -> tuple[Array, Array, Array]:
    """(B, d), (n, d), (B, M) i32, (B, W) u32, (n,|n,1) meta, (B, ·) cons
    [, (Wt,) u32 tombstones]
    -> ((B, M) f32 dists, (B, M) i32 satisfied, (B, M) i32 fresh)."""
    if family not in FAMILIES:
        raise ValueError(f"unsupported in-kernel constraint family: {family}")
    b, d = queries.shape
    m = ids.shape[1]
    qb = query_block(b)
    b_pad = _round_up(b, qb)
    ids, meta, visited, cons, tomb, m_blk, m_pad = _prepare(
        ids, visited, meta, cons, tomb, family, m_blk, b_pad
    )
    grid_spec = _grid_spec(
        family, qb, m_blk, b_pad, m_pad,
        pl.BlockSpec((qb, d), lambda i, j, *_: (i, 0)),
        cons, visited, tomb,
        [pl.BlockSpec(memory_space=pl.ANY)],  # corpus stays in HBM
        [
            pltpu.VMEM((dma_depth, 1, d), corpus.dtype),
            pltpu.SemaphoreType.DMA((dma_depth,)),
        ],
    )
    tomb_args = () if tomb is None else (tomb,)
    return _call(
        _make_kernel(family, qb, m_blk, tomb is not None, dma_depth),
        grid_spec, b_pad, m_pad, b, m, interpret,
        (ids, meta, pad_rows(queries, b_pad), cons, visited, *tomb_args,
         corpus),
    )


def _make_adc_kernel(family, qb, m_blk, n_cent, with_tomb, lut_tile):
    # lut_tile == 0 (or >= n_cent) means one whole-table slice; either way
    # the selection below is exact, so every tile width is bit-identical.
    chunk = lut_tile if 0 < lut_tile < n_cent else n_cent

    def kernel(*refs):
        # rest: the (QB, M_blk, m_sub) code-row block, 3 (QB, M_blk) outs.
        lut_ref, probe_refs, (code_ref, *outs) = _split_refs(refs, with_tomb)

        def distance(u, slot):
            del slot
            qi, t = u // m_blk, u % m_blk
            # Candidate t's (1, m_sub) centroid ids, picked by sublane
            # compare from its query's code rows.
            rows = code_ref[qi]
            sub = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
            crow = jnp.max(jnp.where(sub == t, rows, 0), axis=0, keepdims=True)
            # Sliced over `chunk` centroid rows of the transposed
            # (n_cent, m_sub) table; each subspace column holds exactly one
            # selected entry, so a max over -inf padding makes vals[s]
            # EXACTLY lut[s, crow[s]] for every tile width (max is exact
            # and associative, so no compiler reassociation can change it).
            vals = jnp.full(crow.shape, -jnp.inf, jnp.float32)
            for c0 in range(0, n_cent, chunk):
                c1 = min(c0 + chunk, n_cent)
                lut = lut_ref[qi, pl.ds(c0, c1 - c0), :]
                cent = c0 + jax.lax.broadcasted_iota(jnp.int32, lut.shape, 0)
                vals = jnp.maximum(vals, jnp.max(
                    jnp.where(cent == crow, lut, -jnp.inf), axis=0,
                    keepdims=True,
                ))
            return jnp.sum(vals, axis=1, keepdims=True)

        _fused_loop(family, qb, m_blk, probe_refs, outs, distance)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("family", "m_blk", "dma_depth", "lut_tile", "interpret"),
)
def fused_expand_adc_kernel(
    lut: Array,
    codes: Array,
    ids: Array,
    visited: Array,
    meta: Array,
    cons: Array,
    tomb: Array | None = None,
    *,
    family: str,
    m_blk: int | None = None,
    dma_depth: int = 2,
    lut_tile: int = 0,
    interpret: bool = False,
) -> tuple[Array, Array, Array]:
    """(B, m_sub, n_cent) f32 LUT, (n, m_sub) i32 codes, (B, M) i32 ids,
    (B, W) u32 visited, (n,|n,1) meta, (B, ·) cons [, (Wt,) u32 tombstones]
    -> ((B, M) f32 ADC dists, (B, M) i32 satisfied, (B, M) i32 fresh).

    ``dma_depth`` is accepted for the shared tuning lattice and unused: the
    candidates' code rows are gathered by XLA (one (n, m_sub) row is
    narrower than the 128-lane tile a DMA may slice) and arrive as a
    (QB, M_blk, m_sub) block."""
    del dma_depth
    if family not in FAMILIES:
        raise ValueError(f"unsupported in-kernel constraint family: {family}")
    b, m_sub, n_cent = lut.shape
    m = ids.shape[1]
    qb = query_block(b)
    b_pad = _round_up(b, qb)
    ids, meta, visited, cons, tomb, m_blk, m_pad = _prepare(
        ids, visited, meta, cons, tomb, family, m_blk, b_pad
    )
    crows = codes.astype(jnp.int32)[jnp.maximum(ids, 0)]  # (B, M, m_sub)
    # (B, n_cent, m_sub): the code row then broadcasts down the sublanes.
    lut_t = pad_rows(jnp.swapaxes(lut.astype(jnp.float32), 1, 2), b_pad)
    grid_spec = _grid_spec(
        family, qb, m_blk, b_pad, m_pad,
        pl.BlockSpec((qb, n_cent, m_sub), lambda i, j, *_: (i, 0, 0)),
        cons, visited, tomb,
        [pl.BlockSpec((qb, m_blk, m_sub), lambda i, j, *_: (i, j, 0))],
        [],
    )
    tomb_args = () if tomb is None else (tomb,)
    return _call(
        _make_adc_kernel(family, qb, m_blk, n_cent, tomb is not None,
                         lut_tile),
        grid_spec, b_pad, m_pad, b, m, interpret,
        (ids, meta, lut_t, cons, visited, *tomb_args, crows),
    )
