"""PQ asymmetric-distance (ADC) table-scan kernel.

Given per-query LUTs (B, m_sub, n_cent) of subspace distances and the code
matrix (N, m_sub), computes ADC[b, v] = sum_s LUT[b, s, codes[v, s]].

TPU mapping: VMEM-gather is awkward on the VPU, so the lookup is recast as a
one-hot × LUT matmul that rides the MXU. A grid step owns QB = min(8, B)
queries and a (bn,)-row code slice; per subspace the slice becomes a
(bn, n_cent) one-hot block contracted with the queries' (QB, n_cent) LUT
rows, accumulated into the (QB, bn) output tile. The one-hot block lives
only in VMEM (bn=256, n_cent=256 → 256 KB f32, well inside the scoped
limit) and the scan streams code blocks from HBM — memory-bound at ~m_sub
words per corpus vector, the same arithmetic the paper's CPU baseline does
per scan. ``bn`` is a cap resolved like the fused kernels' tile width
(``repro.tune.config.lane_tile``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_expand.fused_expand import pad_rows, query_block
from repro.tune.config import lane_tile

Array = jax.Array


def _kernel(lut_ref, codes_ref, out_ref, *, m_sub: int, n_cent: int):
    codes = codes_ref[...]  # (bn, m_sub) int32
    cent = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], n_cent), 1)
    acc = jnp.zeros(out_ref.shape, jnp.float32)  # (QB, bn)
    for s in range(m_sub):
        # One subspace at a time: a (bn, n_cent) one-hot block contracted
        # with the block's (QB, n_cent) LUT rows on the MXU. HIGHEST
        # precision keeps the f32 LUT entries exact through the multiply.
        onehot = (cent == codes[:, s:s + 1]).astype(jnp.float32)
        acc = acc + jax.lax.dot_general(
            lut_ref[s].astype(jnp.float32),
            onehot,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def pq_adc_kernel(
    lut: Array, codes: Array, *, bn: int = 256, interpret: bool = False
) -> Array:
    """(B, m_sub, n_cent) x (N, m_sub) -> (B, N) f32 ADC distances."""
    b, m_sub, n_cent = lut.shape
    n, m2 = codes.shape
    if m_sub != m2:
        raise ValueError(f"LUT has {m_sub} subspaces, codes have {m2}")
    bn = lane_tile(bn, n)
    n_pad = -(-n // bn) * bn
    qb = query_block(b)
    b_pad = -(-b // qb) * qb
    cp = jnp.pad(codes.astype(jnp.int32), ((0, n_pad - n), (0, 0)))
    # (m_sub, B, n_cent): the kernel takes one subspace's (QB, n_cent) rows
    # per step along the leading axis.
    lut_s = jnp.swapaxes(pad_rows(lut, b_pad), 0, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, m_sub=m_sub, n_cent=n_cent),
        grid=(b_pad // qb, n_pad // bn),
        in_specs=[
            pl.BlockSpec((m_sub, qb, n_cent), lambda i, j: (0, i, 0)),
            pl.BlockSpec((bn, m_sub), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((qb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(lut_s, cp)
    return out[:b, :n]
