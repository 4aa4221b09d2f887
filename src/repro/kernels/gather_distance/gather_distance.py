"""Fused gather + distance kernel — the inner step of the graph search.

For a batch of queries Q (B, d) and per-query neighbor id lists IDS (B, M),
computes D[b, m] = ||Q[b] - corpus[IDS[b, m]]||^2 without materializing the
(B, M, d) gathered tensor in HBM.

TPU mapping: the id matrix is *scalar-prefetched* (SMEM) and drives manual
pipelined row DMAs over a ``(B / QB, M / m_blk)`` grid with ``(QB, m_blk)``
output tiles, QB = ``min(8, B)`` queries per step — the same layout as the
fused-expansion kernels (kernels/fused_expand), minus their metadata word
and constraint / visited probes. Each grid step streams its ``QB * m_blk``
corpus rows through a ``dma_depth``-slot VMEM ring buffer, overlapping
upcoming row copies with the current row's VPU distance reduction, and
carries its output tile as a value stored once (VMEM takes no scalar
stores). This kernel is HBM-bandwidth-bound by construction — see
EXPERIMENTS.md §Roofline.

Padding ids (< 0) are redirected to row 0 and reported as +inf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_expand.fused_expand import pad_rows, query_block
from repro.tune.config import lane_tile

Array = jax.Array


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _make_kernel(qb: int, m_blk: int, dma_depth: int):
    n_steps = qb * m_blk

    def kernel(
        ids_ref,  # (B, M) int32, scalar-prefetched (SMEM)
        q_ref,  # (QB, d) query rows (VMEM)
        corpus_hbm,  # (n, d) full corpus (ANY/HBM)
        out_ref,  # (QB, m_blk) f32 out
        row_buf,  # (dma_depth, 1, d) VMEM scratch — corpus-row ring
        row_sem,  # (dma_depth,) DMA semaphores
    ):
        i = pl.program_id(0)
        jb = pl.program_id(1)

        def cand(u):  # flat step -> candidate id (query-major)
            return ids_ref[i * qb + u // m_blk, jb * m_blk + u % m_blk]

        def row_dma(u, slot):
            cid = jnp.maximum(cand(u), 0)
            return pltpu.make_async_copy(
                corpus_hbm.at[pl.ds(cid, 1), :], row_buf.at[slot], row_sem.at[slot]
            )

        for u0 in range(min(dma_depth - 1, n_steps)):
            row_dma(u0, u0 % dma_depth).start()
        q = q_ref[...].astype(jnp.float32)  # (QB, d)
        sub = jax.lax.broadcasted_iota(jnp.int32, (qb, m_blk), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (qb, m_blk), 1)

        def body(u, out):
            slot = u % dma_depth

            @pl.when(u + dma_depth - 1 < n_steps)
            def _():
                nxt = u + dma_depth - 1
                row_dma(nxt, nxt % dma_depth).start()

            row_dma(u, slot).wait()
            # Scored against every query of the block (one vreg either
            # way); `here` keeps the row of the query the candidate
            # belongs to.
            diff = q - row_buf[slot].astype(jnp.float32)
            d2 = jnp.sum(diff * diff, axis=1, keepdims=True)  # (QB, 1)
            here = (sub == u // m_blk) & (lane == u % m_blk)
            return jnp.where(here, jnp.where(cand(u) < 0, jnp.inf, d2), out)

        out_ref[...] = jax.lax.fori_loop(
            0, n_steps, body, jnp.zeros((qb, m_blk), jnp.float32)
        )

    return kernel


@functools.partial(
    jax.jit, static_argnames=("m_blk", "dma_depth", "interpret")
)
def gather_distance_kernel(
    queries: Array,
    corpus: Array,
    ids: Array,
    *,
    m_blk: int | None = None,
    dma_depth: int = 2,
    interpret: bool = False,
) -> Array:
    """(B, d), (n, d), (B, M) int32 -> (B, M) f32 squared distances."""
    b, d = queries.shape
    _, m = ids.shape
    # m_blk is a cap on the output-tile width: small neighbor lists
    # collapse to one tile (see repro.tune.config.lane_tile).
    m_blk = lane_tile(m_blk if m_blk is not None else 128, m)
    m_pad = _round_up(m, m_blk)
    qb = query_block(b)
    b_pad = _round_up(b, qb)
    ids = ids.astype(jnp.int32)
    if m_pad != m:
        ids = jnp.pad(ids, ((0, 0), (0, m_pad - m)), constant_values=-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b_pad // qb, m_pad // m_blk),
        in_specs=[
            pl.BlockSpec((qb, d), lambda i, j, ids_pref: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # corpus stays in HBM
        ],
        out_specs=pl.BlockSpec((qb, m_blk), lambda i, j, ids_pref: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((dma_depth, 1, d), corpus.dtype),
            pltpu.SemaphoreType.DMA((dma_depth,)),
        ],
    )
    out = pl.pallas_call(
        _make_kernel(qb, m_blk, dma_depth),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_pad, m_pad), jnp.float32),
        interpret=interpret,
    )(pad_rows(ids, b_pad, -1), pad_rows(queries, b_pad), corpus)
    return out[:b, :m]
