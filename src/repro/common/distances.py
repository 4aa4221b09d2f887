"""Distance primitives shared across the system.

All distances are *squared* Euclidean unless noted — monotone in L2, cheaper,
and what proximity-graph searches actually rank by. Inner-product and cosine
variants are provided for the MIPS-style retrieval paths (two-tower).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def squared_l2(a: Array, b: Array) -> Array:
    """Pairwise squared L2 between rows of ``a`` (A, d) and ``b`` (B, d).

    Uses the matmul expansion ``|a|^2 - 2 a.b + |b|^2`` so the MXU does the
    heavy lifting; accumulates in f32. The product runs at HIGHEST
    precision: at the default a TPU multiplies f32 inputs in bf16, which
    would make the exact oracle, the graph build and the start-point
    seeding inexact there (on the CPU it is the same f32 product).
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    a2 = jnp.sum(a * a, axis=-1, keepdims=True)  # (A, 1)
    b2 = jnp.sum(b * b, axis=-1, keepdims=True).T  # (1, B)
    ab = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)  # (A, B)
    d = a2 - 2.0 * ab + b2
    return jnp.maximum(d, 0.0)


def squared_l2_one_to_many(q: Array, x: Array) -> Array:
    """Squared L2 between a single query (d,) and rows of ``x`` (N, d)."""
    diff = x.astype(jnp.float32) - q.astype(jnp.float32)[None, :]
    return jnp.sum(diff * diff, axis=-1)


def batched_rowwise_sqdist(q: Array, rows: Array) -> Array:
    """(B, d) queries vs (B, M, d) gathered rows -> (B, M) squared distances."""
    diff = rows.astype(jnp.float32) - q.astype(jnp.float32)[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def neg_inner_product(a: Array, b: Array) -> Array:
    """Negative inner product (so that smaller == more similar), (A,d)x(B,d)."""
    return -(a.astype(jnp.float32) @ b.astype(jnp.float32).T)


def cosine_distance(a: Array, b: Array) -> Array:
    an = a / (jnp.linalg.norm(a, axis=-1, keepdims=True) + 1e-12)
    bn = b / (jnp.linalg.norm(b, axis=-1, keepdims=True) + 1e-12)
    return 1.0 - an @ bn.T
