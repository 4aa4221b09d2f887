"""RecSys model family: DLRM (MLPerf), DeepFM, SASRec, two-tower retrieval.

The embedding lookup is the hot path; JAX has no EmbeddingBag or sparse
gather-reduce, so the bag/lookup substrate here is `jnp.take` +
`jax.ops.segment_sum` (with the fused Pallas kernel in
repro/kernels/embedding_bag as the TPU path). Large tables are row-sharded
over the ``model`` axis (vocab padded to a multiple of the axis size);
lookups over sharded tables lower to GSPMD's masked-gather + psum.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.meshinfo import MeshInfo
from repro.models.common.modules import (
    chunked_attention,
    dense_init,
    layernorm_apply,
    layernorm_init,
    mlp_apply,
    mlp_init,
)

Array = jax.Array
Params = dict


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    model: str  # dlrm | deepfm | sasrec | two_tower
    embed_dim: int
    # categorical fields
    vocab_sizes: Tuple[int, ...] = ()
    n_dense: int = 0
    # mlps
    bot_mlp: Tuple[int, ...] = ()
    top_mlp: Tuple[int, ...] = ()
    mlp: Tuple[int, ...] = ()
    # sasrec
    seq_len: int = 0
    n_blocks: int = 0
    n_heads: int = 1
    item_vocab: int = 0
    # two-tower
    tower_mlp: Tuple[int, ...] = ()
    user_vocab: int = 0
    hist_len: int = 0
    table_shard_axis: str = "model"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    def padded_vocab(self, v: int, tp: int) -> int:
        return ((v + tp - 1) // tp) * tp


# ---------------------------------------------------------------------------
# embedding tables
# ---------------------------------------------------------------------------
def _tables_init(rng, cfg, vocabs: Sequence[int], dim: int, tp_pad: int = 256):
    tables = {}
    for i, v in enumerate(vocabs):
        vp = cfg.padded_vocab(v, tp_pad)
        r = jax.random.fold_in(rng, i)
        tables[f"t{i}"] = (
            jax.random.normal(r, (vp, dim), cfg.param_dtype)
            / math.sqrt(dim)
        )
    return tables


def _tables_specs(cfg, vocabs, mi: MeshInfo):
    # Rows over model (the big dim), embedding cols FSDP'd over data when
    # divisible — fully-sharded tables keep optimizer state in-budget.
    tp, fs = mi.tp_axis, mi.fsdp_axis
    col = mi.axes_if_divisible(cfg.embed_dim, fs)
    return {f"t{i}": P(tp, col) for i in range(len(vocabs))}


def _lookup(tables: Params, ids: Array) -> Array:
    """ids (B, F) -> (B, F, D): one gather per field table."""
    outs = [tables[f"t{i}"][ids[:, i]] for i in range(ids.shape[1])]
    return jnp.stack(outs, axis=1)


def embedding_bag_sum(table: Array, ids: Array) -> Array:
    """(V, D) x (B, L) -1-padded -> (B, D). The take+mask+sum substrate."""
    rows = table[jnp.maximum(ids, 0)]
    mask = (ids >= 0).astype(rows.dtype)[..., None]
    return jnp.sum(rows * mask, axis=1)


# ===========================================================================
# DLRM (MLPerf config)
# ===========================================================================
def dlrm_init(rng, cfg: RecsysConfig) -> Params:
    ks = jax.random.split(rng, 3)
    d = cfg.embed_dim
    n_f = len(cfg.vocab_sizes) + 1  # + dense projection
    n_inter = n_f * (n_f - 1) // 2
    return {
        "tables": _tables_init(ks[0], cfg, cfg.vocab_sizes, d),
        "bot": mlp_init(ks[1], (cfg.n_dense,) + cfg.bot_mlp, cfg.param_dtype),
        "top": mlp_init(
            ks[2], (n_inter + cfg.bot_mlp[-1],) + cfg.top_mlp, cfg.param_dtype
        ),
    }


def dlrm_specs(cfg, mi: MeshInfo) -> Params:
    return {
        "tables": _tables_specs(cfg, cfg.vocab_sizes, mi),
        "bot": mlp_specs_like(cfg.bot_mlp, P(None, None)),
        "top": mlp_specs_like(cfg.top_mlp, P(None, None)),
    }


def mlp_specs_like(dims, spec):
    return {"layers": [{"w": spec, "b": P(None)} for _ in range(len(dims))]}


def dlrm_forward(p: Params, cfg, mi: MeshInfo, batch: dict) -> Array:
    dense = batch["dense"].astype(cfg.compute_dtype)  # (B, 13)
    sparse = batch["sparse"]  # (B, 26)
    x0 = mlp_apply(p["bot"], dense, final_act=True)  # (B, D)
    emb = _lookup(p["tables"], sparse).astype(cfg.compute_dtype)  # (B, 26, D)
    z = jnp.concatenate([x0[:, None], emb], axis=1)  # (B, 27, D)
    z = mi.constrain(z, mi.axes_if_divisible(z.shape[0], mi.dp_axes), None, None)
    inter = jnp.einsum("bfd,bgd->bfg", z, z)  # (B, 27, 27) dot interaction
    n_f = z.shape[1]
    iu, ju = jnp.tril_indices(n_f, k=-1)
    flat = inter[:, iu, ju]  # (B, 351)
    top_in = jnp.concatenate([x0, flat], axis=-1)
    return mlp_apply(p["top"], top_in)[..., 0]  # (B,) logit


def dlrm_loss(p, cfg, mi, batch):
    logit = dlrm_forward(p, cfg, mi, batch)
    label = batch["label"].astype(jnp.float32)
    loss = jnp.mean(_bce(logit.astype(jnp.float32), label))
    return loss, {"loss": loss}


def _bce(logit, label):
    return jnp.maximum(logit, 0) - logit * label + jnp.log1p(jnp.exp(-jnp.abs(logit)))


# ===========================================================================
# DeepFM
# ===========================================================================
def deepfm_init(rng, cfg: RecsysConfig) -> Params:
    ks = jax.random.split(rng, 4)
    d = cfg.embed_dim
    n_f = len(cfg.vocab_sizes)
    return {
        "tables": _tables_init(ks[0], cfg, cfg.vocab_sizes, d),
        "linear": _tables_init(ks[1], cfg, cfg.vocab_sizes, 1),
        "deep": mlp_init(ks[2], (n_f * d,) + cfg.mlp + (1,), cfg.param_dtype),
        "bias": jnp.zeros((), cfg.param_dtype),
    }


def deepfm_specs(cfg, mi: MeshInfo) -> Params:
    return {
        "tables": _tables_specs(cfg, cfg.vocab_sizes, mi),
        "linear": _tables_specs(cfg, cfg.vocab_sizes, mi),
        "deep": mlp_specs_like(cfg.mlp + (1,), P(None, None)),
        "bias": P(),
    }


def deepfm_forward(p, cfg, mi: MeshInfo, batch):
    sparse = batch["sparse"]  # (B, 39)
    emb = _lookup(p["tables"], sparse).astype(cfg.compute_dtype)  # (B, 39, D)
    lin = _lookup(p["linear"], sparse)[..., 0].astype(cfg.compute_dtype)  # (B, 39)
    # FM second-order: 0.5 * ((sum v)^2 - sum v^2)
    s = jnp.sum(emb, axis=1)
    s2 = jnp.sum(emb * emb, axis=1)
    fm = 0.5 * jnp.sum(s * s - s2, axis=-1)  # (B,)
    deep = mlp_apply(p["deep"], emb.reshape(emb.shape[0], -1))[..., 0]
    return fm + jnp.sum(lin, axis=-1) + deep + p["bias"].astype(jnp.float32)


def deepfm_loss(p, cfg, mi, batch):
    logit = deepfm_forward(p, cfg, mi, batch)
    loss = jnp.mean(_bce(logit.astype(jnp.float32), batch["label"].astype(jnp.float32)))
    return loss, {"loss": loss}


# ===========================================================================
# SASRec
# ===========================================================================
def sasrec_init(rng, cfg: RecsysConfig) -> Params:
    ks = jax.random.split(rng, 3 + cfg.n_blocks)
    d = cfg.embed_dim
    vp = cfg.padded_vocab(cfg.item_vocab, 256)
    blocks = []
    for i in range(cfg.n_blocks):
        r = jax.random.split(ks[3 + i], 6)
        blocks.append(
            {
                "ln1": layernorm_init(d, cfg.param_dtype),
                "wq": dense_init(r[0], d, d, cfg.param_dtype),
                "wk": dense_init(r[1], d, d, cfg.param_dtype),
                "wv": dense_init(r[2], d, d, cfg.param_dtype),
                "wo": dense_init(r[3], d, d, cfg.param_dtype),
                "ln2": layernorm_init(d, cfg.param_dtype),
                "ff1": dense_init(r[4], d, d, cfg.param_dtype),
                "ff2": dense_init(r[5], d, d, cfg.param_dtype),
            }
        )
    return {
        "items": jax.random.normal(ks[0], (vp, d), cfg.param_dtype) * 0.02,
        "pos": jax.random.normal(ks[1], (cfg.seq_len, d), cfg.param_dtype) * 0.02,
        "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
        "final_ln": layernorm_init(d, cfg.param_dtype),
    }


def sasrec_specs(cfg, mi: MeshInfo) -> Params:
    tp = mi.tp_axis
    col = mi.axes_if_divisible(cfg.embed_dim, mi.fsdp_axis)
    blk = {
        "ln1": {"scale": P(None, None), "bias": P(None, None)},
        "ln2": {"scale": P(None, None), "bias": P(None, None)},
        **{k: {"w": P(None, None, None)} for k in ("wq", "wk", "wv", "wo", "ff1", "ff2")},
    }
    return {
        "items": P(tp, col),
        "pos": P(None, None),
        "blocks": blk,
        "final_ln": {"scale": P(None), "bias": P(None)},
    }


def sasrec_hidden(p, cfg, mi: MeshInfo, seq: Array) -> Array:
    """seq (B, S) item ids (0 = padding) -> (B, S, D)."""
    b, s = seq.shape
    h = p["items"][seq].astype(cfg.compute_dtype) + p["pos"][None, :s].astype(
        cfg.compute_dtype
    )
    nheads = cfg.n_heads
    d = cfg.embed_dim

    def block(h, bp):
        x = layernorm_apply(bp["ln1"], h)
        q = (x @ bp["wq"]["w"].astype(x.dtype)).reshape(b, s, nheads, d // nheads)
        k = (x @ bp["wk"]["w"].astype(x.dtype)).reshape(b, s, nheads, d // nheads)
        v = (x @ bp["wv"]["w"].astype(x.dtype)).reshape(b, s, nheads, d // nheads)
        a = chunked_attention(q, k, v, causal=True, chunk=min(64, s))
        h = h + a.reshape(b, s, d) @ bp["wo"]["w"].astype(x.dtype)
        x = layernorm_apply(bp["ln2"], h)
        ff = jax.nn.relu(x @ bp["ff1"]["w"].astype(x.dtype)) @ bp["ff2"]["w"].astype(
            x.dtype
        )
        return h + ff, None

    h, _ = jax.lax.scan(block, h, p["blocks"])
    return layernorm_apply(p["final_ln"], h)


def sasrec_loss(p, cfg, mi, batch):
    """BCE over (positive next item, sampled negative) pairs — SASRec §3."""
    h = sasrec_hidden(p, cfg, mi, batch["seq"])  # (B, S, D)
    pos_e = p["items"][batch["pos"]].astype(h.dtype)  # (B, S, D)
    neg_e = p["items"][batch["neg"]].astype(h.dtype)
    pos_s = jnp.sum(h * pos_e, axis=-1).astype(jnp.float32)
    neg_s = jnp.sum(h * neg_e, axis=-1).astype(jnp.float32)
    mask = (batch["pos"] > 0).astype(jnp.float32)
    loss = jnp.sum(
        (_bce(pos_s, jnp.ones_like(pos_s)) + _bce(neg_s, jnp.zeros_like(neg_s))) * mask
    ) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, {"loss": loss}


def sasrec_serve(p, cfg, mi, batch):
    """Score last position against candidate items (B, C) -> (B, C)."""
    h = sasrec_hidden(p, cfg, mi, batch["seq"])[:, -1]  # (B, D)
    cand = p["items"][batch["candidates"]].astype(h.dtype)  # (B, C, D)
    return jnp.einsum("bd,bcd->bc", h, cand)


# ===========================================================================
# Two-tower retrieval
# ===========================================================================
def two_tower_init(rng, cfg: RecsysConfig) -> Params:
    ks = jax.random.split(rng, 5)
    d = cfg.embed_dim
    up = cfg.padded_vocab(cfg.user_vocab, 256)
    ip = cfg.padded_vocab(cfg.item_vocab, 256)
    return {
        "user_emb": jax.random.normal(ks[0], (up, d), cfg.param_dtype) * 0.02,
        "item_emb": jax.random.normal(ks[1], (ip, d), cfg.param_dtype) * 0.02,
        # user tower input: user emb + history bag
        "user_tower": mlp_init(ks[2], (2 * d,) + cfg.tower_mlp, cfg.param_dtype),
        "item_tower": mlp_init(ks[3], (d,) + cfg.tower_mlp, cfg.param_dtype),
        "log_tau": jnp.zeros((), jnp.float32),
    }


def two_tower_specs(cfg, mi: MeshInfo) -> Params:
    tp = mi.tp_axis
    col = mi.axes_if_divisible(cfg.embed_dim, mi.fsdp_axis)
    return {
        "user_emb": P(tp, col),
        "item_emb": P(tp, col),
        "user_tower": mlp_specs_like(cfg.tower_mlp, P(None, None)),
        "item_tower": mlp_specs_like(cfg.tower_mlp, P(None, None)),
        "log_tau": P(),
    }


def two_tower_user(p, cfg, mi, batch) -> Array:
    ue = p["user_emb"][batch["user_id"]].astype(cfg.compute_dtype)  # (B, D)
    hist = embedding_bag_sum(p["item_emb"], batch["hist"]).astype(
        cfg.compute_dtype
    )  # (B, D)
    x = jnp.concatenate([ue, hist], axis=-1)
    u = mlp_apply(p["user_tower"], x, act=jax.nn.relu)
    return u / (jnp.linalg.norm(u, axis=-1, keepdims=True) + 1e-8)


def two_tower_item(p, cfg, mi, item_ids: Array) -> Array:
    ie = p["item_emb"][item_ids].astype(cfg.compute_dtype)
    v = mlp_apply(p["item_tower"], ie, act=jax.nn.relu)
    return v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + 1e-8)


def two_tower_loss(p, cfg, mi, batch, *, neg_chunk: int = 4096):
    """In-batch sampled softmax (RecSys'19 two-tower retrieval objective).

    The (B, B) logit matrix at the assigned train batch (65536) is 17 GB in
    f32 (34 GB with its gradient) — §Perf iteration C. The logsumexp is
    streamed over negative chunks instead (online-softmax recurrence, body
    rematerialized), so peak logit memory is (B, neg_chunk) and the
    backward recomputes each chunk.
    """
    u = two_tower_user(p, cfg, mi, batch)  # (B, D)
    v = two_tower_item(p, cfg, mi, batch["item_id"])  # (B, D)
    tau = jnp.maximum(jnp.exp(p["log_tau"]), 1e-3)
    b = u.shape[0]
    diag = jnp.sum(u * v, axis=-1).astype(jnp.float32) / tau
    if b <= neg_chunk:
        logits = (u @ v.T).astype(jnp.float32) / tau
        lse = jax.nn.logsumexp(logits, axis=-1)
    else:
        assert b % neg_chunk == 0
        n_chunks = b // neg_chunk
        u32 = u.astype(jnp.float32)
        vc_all = v.astype(jnp.float32).reshape(n_chunks, neg_chunk, -1)

        @jax.checkpoint
        def step(carry, vc):
            m, lsum = carry
            logits = (u32 @ vc.T) / tau  # (B, chunk)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            lsum = lsum * jnp.exp(m - m_new) + jnp.sum(
                jnp.exp(logits - m_new[:, None]), axis=-1
            )
            return (m_new, lsum), None

        init = (jnp.full((b,), -jnp.inf, jnp.float32), jnp.zeros((b,), jnp.float32))
        (m, lsum), _ = jax.lax.scan(step, init, vc_all)
        lse = m + jnp.log(jnp.maximum(lsum, 1e-30))
    loss = jnp.mean(lse - diag)
    return loss, {"loss": loss}


def two_tower_score_candidates(
    p, cfg, mi: MeshInfo, batch, *, two_phase_topk: bool = True
) -> Array:
    """retrieval_cand: score users against a candidate matrix (C, D).

    Candidates (precomputed item-tower outputs) shard over the model axis;
    the score is one sharded matmul + top-k merge — the brute-force baseline
    AIRSHIP's constrained graph search replaces (see core/ + examples).

    ``two_phase_topk`` (beyond-paper §Perf iteration): each shard takes its
    local top-k and only (P x k) score/id pairs cross the wire, instead of
    letting GSPMD all-gather the full (B, C) score matrix for the global
    top-k — measured ~250x collective-byte reduction at C=1M, k=100.
    """
    u = two_tower_user(p, cfg, mi, batch)  # (B, D)
    cand = batch["candidates"].astype(u.dtype)  # (C, D)
    c = cand.shape[0]
    k = min(100, c)
    if two_phase_topk and mi.tp_size > 1 and c % mi.tp_size == 0:
        tp = mi.tp_axis
        bspec = mi.axes_if_divisible(u.shape[0], mi.dp_axes)

        def local(u_l, cand_l):
            shard = jax.lax.axis_index(tp)
            scores = u_l @ cand_l.T  # (B_l, C_local)
            top, idx = jax.lax.top_k(scores, k)
            idx = idx + shard * cand_l.shape[0]
            all_top = jax.lax.all_gather(top, tp, axis=1)  # (B_l, P, k)
            all_idx = jax.lax.all_gather(idx, tp, axis=1)
            t2, pos = jax.lax.top_k(all_top.reshape(top.shape[0], -1), k)
            i2 = jnp.take_along_axis(
                all_idx.reshape(idx.shape[0], -1), pos, axis=-1
            )
            return t2, i2

        return jax.shard_map(
            local,
            mesh=mi.mesh,
            check_vma=False,
            in_specs=(P(bspec, None), P(tp, None)),
            out_specs=(P(bspec, None), P(bspec, None)),
        )(u, cand)
    cand = mi.constrain(cand, mi.tp_axis, None)
    scores = u @ cand.T  # (B, C)
    scores = mi.constrain(
        scores, mi.axes_if_divisible(u.shape[0], mi.dp_axes), mi.tp_axis
    )
    top, idx = jax.lax.top_k(scores, k)
    return top, idx
