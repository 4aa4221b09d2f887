"""The plain reference: exact constrained top-k by brute force.

Written from the semantics alone and importing nothing of the system under
test. For each request it scores every corpus row, masks the rows that fail
the request's constraint (allowed label set, or attribute in [lo, hi]) and
keeps the k nearest by squared L2.

At ``precision="highest"`` (what the configuration states: float32 rows,
exact distances) the rows are scored by the matmul expansion at HIGHEST,
a shortlist of ``k + SHORTLIST_MARGIN`` is kept and its distances are
recomputed directly as sum((q - x)^2), which involves no product at a
lower precision. The control is the same reference one precision lower,
``"high"``: the product in three bf16 passes (each operand split into a
bf16 head and a bf16 tail, the tail x tail pass dropped), written out so
that it reads the same on a TPU and on the CPU; it keeps its expansion
distances.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 128  # queries per compiled block
SHORTLIST_MARGIN = 16


def _dot(a, b, precision: str):
    """(A, d) x (B, d)^T in f32 at HIGHEST, or in three bf16 passes."""
    if precision == "highest":
        return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(x):
        # reduce_precision, not a bf16 round trip: a compiler may fold
        # f32 -> bf16 -> f32 away, which would leave the tail at zero
        head = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return head.astype(jnp.bfloat16), (x - head).astype(jnp.bfloat16)

    def bf16_pass(x, y):
        return jnp.matmul(x, y.T, preferred_element_type=jnp.float32)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    return bf16_pass(a_hi, b_hi) + bf16_pass(a_hi, b_lo) + bf16_pass(a_lo, b_hi)


@partial(jax.jit, static_argnames=("k", "precision"))
def _topk_block(vectors, labels, attr, queries, allow, lo, hi, is_range, *, k, precision):
    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)
    x2 = jnp.sum(vectors * vectors, axis=-1)[None, :]
    d = jnp.maximum(q2 - 2.0 * _dot(queries, vectors, precision) + x2, 0.0)
    label_ok = ((allow[:, None] >> labels[None, :].astype(jnp.uint32)) & 1) == 1
    range_ok = (attr[None, :] >= lo[:, None]) & (attr[None, :] <= hi[:, None])
    ok = jnp.where(is_range[:, None], range_ok, label_ok)
    n_sat = jnp.sum(ok, axis=-1, dtype=jnp.int32)
    width = min(k + SHORTLIST_MARGIN if precision == "highest" else k, vectors.shape[0])
    neg, ids = jax.lax.top_k(jnp.where(ok, -d, -jnp.inf), width)
    ids = jnp.where(jnp.isfinite(neg), ids, -1)
    if precision == "highest":
        dd, _ = point_dists_impl(vectors, queries, ids)
        order = jnp.argsort(dd, axis=-1)[:, :k]
        return jnp.take_along_axis(ids, order, -1), jnp.take_along_axis(dd, order, -1), n_sat
    return ids, jnp.where(ids >= 0, -neg, jnp.inf), n_sat


def point_dists_impl(vectors, queries, ids):
    """(Q, d) queries, (Q, m) ids -> (Q, m) direct squared L2 (inf at -1),
    and (Q, m) |q|^2 + |x|^2, the scale at which an expansion rounds."""
    rows = vectors[jnp.maximum(ids, 0)]
    diff = rows - queries[:, None, :]
    scale = jnp.sum(rows * rows, axis=-1) + jnp.sum(queries * queries, axis=-1)[:, None]
    return jnp.where(ids >= 0, jnp.sum(diff * diff, axis=-1), jnp.inf), scale


_point_dists = jax.jit(point_dists_impl)


def _encode(items, n_labels: int):
    """Per-request constraint arrays: allowed-label mask, range, family."""
    if n_labels > 32:
        raise ValueError("the reference packs allowed labels into one uint32 word")
    allow = np.zeros(len(items), np.uint32)
    lo = np.zeros(len(items), np.float32)
    hi = np.zeros(len(items), np.float32)
    is_range = np.zeros(len(items), bool)
    cols = {it.range[2] for it in items if it.kind == "range"}
    if len(cols) > 1:
        raise ValueError("one attribute column per run")
    for i, it in enumerate(items):
        if it.kind == "range":
            lo[i], hi[i], _ = it.range
            is_range[i] = True
        else:
            for lab in it.labels:
                allow[i] |= np.uint32(1) << np.uint32(lab)
    return allow, lo, hi, is_range, (cols.pop() if cols else 0)


def _blocks(n: int):
    for s in range(0, n, BLOCK):
        yield s, min(s + BLOCK, n)


def _pad(a: np.ndarray) -> np.ndarray:
    short = BLOCK - a.shape[0]
    return np.concatenate([a, np.repeat(a[-1:], short, axis=0)]) if short else a


def constrained_topk(data, items, k: int, precision: str = "highest") -> tuple:
    """(ids (Q, k) -1 padded, dists (Q, k) inf padded, satisfying rows (Q,))."""
    allow, lo, hi, is_range, col = _encode(items, data.n_labels)
    queries = np.stack([it.query for it in items]).astype(np.float32)
    attr = data.attrs_dev[:, col]
    out = [], [], []
    for s, e in _blocks(len(items)):
        res = _topk_block(
            data.vectors, data.labels_dev, attr, jnp.asarray(_pad(queries[s:e])),
            jnp.asarray(_pad(allow[s:e])), jnp.asarray(_pad(lo[s:e])),
            jnp.asarray(_pad(hi[s:e])), jnp.asarray(_pad(is_range[s:e])),
            k=k, precision=precision,
        )
        for acc, part in zip(out, res):
            acc.append(np.asarray(part)[: e - s])
    return tuple(np.concatenate(acc) for acc in out)


def point_dists(data, items, ids: np.ndarray) -> tuple:
    """Direct squared L2 of each request's query to the given ids, and the
    scale |q|^2 + |x|^2 of each pair."""
    queries = np.stack([it.query for it in items]).astype(np.float32)
    out = [], []
    for s, e in _blocks(len(items)):
        res = _point_dists(data.vectors, jnp.asarray(_pad(queries[s:e])),
                           jnp.asarray(_pad(ids[s:e])))
        for acc, part in zip(out, res):
            acc.append(np.asarray(part)[: e - s])
    return tuple(np.concatenate(acc) for acc in out)
