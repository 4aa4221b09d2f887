"""Decoder-only transformer LM: dense (GQA) and MoE (MLA, DeepSeek-style),
with scan-over-layers, sequence parallelism, chunked-softmax CE loss, a
sequence-sharded KV-cache decode path, and optional multi-token prediction
(DeepSeek-V3 MTP).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.meshinfo import MeshInfo
from repro.models.common.modules import (
    dense_init,
    rmsnorm_apply,
    rmsnorm_init,
)
from repro.models.transformer import attention as attn
from repro.models.transformer import moe as moe_mod

Array = jax.Array
Params = dict


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    attn_type: str = "gqa"  # gqa | mla
    rope_theta: float = 10_000.0
    # MLA
    q_lora_rank: int = 0
    kv_lora_rank: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    n_dense_layers: int = -1  # -1 -> all dense (no MoE)
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.0
    # MTP (DeepSeek-V3)
    mtp: bool = False
    mtp_coef: float = 0.3
    # numerics / memory
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    attn_chunk: int = 512
    ce_chunk: int = 1024
    remat: str = "full"  # full | dots | none
    sequence_parallel: bool = True

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up to a TP-friendly multiple (Megatron-style
        padding; logical ids stay < vocab_size)."""
        return ((self.vocab_size + 511) // 512) * 512

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_dense(self) -> int:
        if not self.is_moe:
            return self.n_layers
        return max(self.n_dense_layers, 0)

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    def param_count(self) -> int:
        """Total parameter count (for 6ND model-flops accounting)."""
        import math

        shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), self))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    def active_param_count(self) -> int:
        """Active params per token (MoE: shared + top_k routed only)."""
        total = self.param_count()
        if not self.is_moe:
            return total
        e, fe, d = self.n_experts, self.d_ff_expert, self.d_model
        routed = self.n_moe * 3 * d * fe * e
        active_routed = self.n_moe * 3 * d * fe * self.top_k
        return total - routed + active_routed


# ---------------------------------------------------------------------------
# init + specs
# ---------------------------------------------------------------------------
def _attn_init(rng, cfg):
    return attn.mla_init(rng, cfg) if cfg.attn_type == "mla" else attn.gqa_init(rng, cfg)


def _attn_specs(cfg, mi):
    return attn.mla_specs(cfg, mi) if cfg.attn_type == "mla" else attn.gqa_specs(cfg, mi)


def _dense_ffn_init(rng, cfg):
    ks = jax.random.split(rng, 3)
    return {
        "w1": dense_init(ks[0], cfg.d_model, cfg.d_ff, cfg.param_dtype),
        "w3": dense_init(ks[1], cfg.d_model, cfg.d_ff, cfg.param_dtype),
        "w2": dense_init(ks[2], cfg.d_ff, cfg.d_model, cfg.param_dtype),
    }


def _dense_ffn_specs(cfg, mi):
    fs, tp = mi.fsdp_axis, mi.tp_axis
    return {"w1": {"w": P(fs, tp)}, "w3": {"w": P(fs, tp)}, "w2": {"w": P(tp, fs)}}


def _layer_init(rng, cfg, kind: str):
    ks = jax.random.split(rng, 2)
    p = {
        "ln1": rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "ln2": rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "attn": _attn_init(ks[0], cfg),
    }
    p["ffn"] = (
        moe_mod.moe_init(ks[1], cfg) if kind == "moe" else _dense_ffn_init(ks[1], cfg)
    )
    return p


def _layer_specs(cfg, mi, kind: str):
    return {
        "ln1": {"scale": P(None)},
        "ln2": {"scale": P(None)},
        "attn": _attn_specs(cfg, mi),
        "ffn": moe_mod.moe_specs(cfg, mi) if kind == "moe" else _dense_ffn_specs(cfg, mi),
    }


def _stack(layers):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def init_params(rng: Array, cfg: TransformerConfig) -> Params:
    ks = jax.random.split(rng, 6)
    p: Params = {
        "embed": {
            "table": jax.random.normal(
                ks[0], (cfg.vocab_padded, cfg.d_model), cfg.param_dtype
            )
            * 0.02
        },
        "final_norm": rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "lm_head": dense_init(ks[1], cfg.d_model, cfg.vocab_padded, cfg.param_dtype),
    }
    if cfg.n_dense:
        p["dense_layers"] = _stack(
            [
                _layer_init(jax.random.fold_in(ks[2], i), cfg, "dense")
                for i in range(cfg.n_dense)
            ]
        )
    if cfg.n_moe:
        p["moe_layers"] = _stack(
            [
                _layer_init(jax.random.fold_in(ks[3], i), cfg, "moe")
                for i in range(cfg.n_moe)
            ]
        )
    if cfg.mtp:
        p["mtp"] = {
            "proj": dense_init(ks[4], 2 * cfg.d_model, cfg.d_model, cfg.param_dtype),
            "layer": _layer_init(ks[5], cfg, "dense"),
            "norm": rmsnorm_init(cfg.d_model, cfg.param_dtype),
        }
    return p


def _prefix_none(tree):
    return jax.tree.map(
        lambda s: P(*((None,) + tuple(s))),
        tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def param_specs(cfg: TransformerConfig, mi: MeshInfo) -> Params:
    fs, tp = mi.fsdp_axis, mi.tp_axis
    p: Params = {
        "embed": {"table": P(tp, fs)},
        "final_norm": {"scale": P(None)},
        "lm_head": {"w": P(fs, tp)},
    }
    if cfg.n_dense:
        p["dense_layers"] = _prefix_none(_layer_specs(cfg, mi, "dense"))
    if cfg.n_moe:
        p["moe_layers"] = _prefix_none(_layer_specs(cfg, mi, "moe"))
    if cfg.mtp:
        p["mtp"] = {
            "proj": {"w": P(fs, tp)},
            "layer": _layer_specs(cfg, mi, "dense"),
            "norm": {"scale": P(None)},
        }
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _residual_constraint(cfg, mi: MeshInfo, x: Array) -> Array:
    # Megatron-style sequence parallelism: the residual stream is sharded
    # over (dp, seq=model); blocks internally reshard to head/ff layouts.
    seq = mi.tp_axis if cfg.sequence_parallel else None
    return mi.constrain(x, mi.dp_axes, seq, None)


def _layer_apply(cfg, mi: MeshInfo, kind: str, lp: Params, x: Array, positions):
    h = rmsnorm_apply(lp["ln1"], x, cfg.norm_eps)
    if cfg.attn_type == "mla":
        a = attn.mla_train(lp["attn"], cfg, mi, h, positions)
    else:
        a = attn.gqa_train(lp["attn"], cfg, mi, h, positions)
    x = _residual_constraint(cfg, mi, x + a)
    h = rmsnorm_apply(lp["ln2"], x, cfg.norm_eps)
    if kind == "moe":
        f = moe_mod.moe_ffn(lp["ffn"], cfg, mi, h)
    else:
        ff = lp["ffn"]
        hh = jax.nn.silu(h @ ff["w1"]["w"].astype(h.dtype)) * (
            h @ ff["w3"]["w"].astype(h.dtype)
        )
        hh = mi.constrain(hh, mi.dp_axes, None, mi.tp_axis)
        f = hh @ ff["w2"]["w"].astype(h.dtype)
    return _residual_constraint(cfg, mi, x + f)


def _remat_wrap(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def forward_hidden(
    params: Params, cfg: TransformerConfig, mi: MeshInfo, tokens: Array
) -> Array:
    """tokens (B, S) -> hidden states (B, S, D)."""
    _, s = tokens.shape
    x = params["embed"]["table"][tokens].astype(cfg.compute_dtype)
    x = _residual_constraint(cfg, mi, x)
    positions = jnp.arange(s)

    def scan_stack(x, stacked, kind):
        body = _remat_wrap(
            cfg, lambda x, lp: (_layer_apply(cfg, mi, kind, lp, x, positions), None)
        )
        x, _ = jax.lax.scan(body, x, stacked)
        return x

    if cfg.n_dense:
        x = scan_stack(x, params["dense_layers"], "dense")
    if cfg.n_moe:
        x = scan_stack(x, params["moe_layers"], "moe")
    return rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)


def _chunked_ce(
    cfg, mi: MeshInfo, h: Array, lm_head: Array, labels: Array, weights: Array
) -> Array:
    """Cross-entropy without materializing full (B, S, V) logits."""
    b, s, d = h.shape
    chunk = min(cfg.ce_chunk, s)
    n_chunks = (s + chunk - 1) // chunk
    assert s % chunk == 0, (s, chunk)

    def step(carry, idx):
        tot, wsum = carry
        hc = jax.lax.dynamic_slice_in_dim(h, idx * chunk, chunk, axis=1)
        lc = jax.lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, axis=1)
        wc = jax.lax.dynamic_slice_in_dim(weights, idx * chunk, chunk, axis=1)
        logits = (hc @ lm_head.astype(hc.dtype)).astype(jnp.float32)
        logits = mi.constrain(logits, mi.dp_axes, None, mi.tp_axis)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # Label-logit extraction via masked-max: stays vocab-sharded under
        # GSPMD (take_along_axis would all-gather the (B, c, V) logits).
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        ll = jnp.max(
            jnp.where(vocab_iota == lc[..., None], logits, -jnp.inf), axis=-1
        )
        tot = tot + jnp.sum((lse - ll) * wc)
        return (tot, wsum + jnp.sum(wc)), None

    body = _remat_wrap(cfg, step)
    (tot, wsum), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), jnp.arange(n_chunks)
    )
    return tot / jnp.maximum(wsum, 1.0)


def lm_loss(
    params: Params, cfg: TransformerConfig, mi: MeshInfo, batch: dict
) -> tuple[Array, dict]:
    """batch: tokens (B, S) int32. Next-token CE (+ optional MTP, aux)."""
    tokens = batch["tokens"]
    h = forward_hidden(params, cfg, mi, tokens)
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    weights = jnp.concatenate(
        [
            jnp.ones_like(tokens[:, 1:], jnp.float32),
            jnp.zeros_like(tokens[:, :1], jnp.float32),
        ],
        axis=1,
    )
    loss = _chunked_ce(cfg, mi, h, params["lm_head"]["w"], labels, weights)
    metrics = {"ce": loss}
    if cfg.mtp:
        # Predict token t+2 from [h_t ; emb(token_{t+1})] through one extra
        # block (simplified DeepSeek-V3 MTP with a single depth-1 module).
        emb_next = params["embed"]["table"][labels].astype(cfg.compute_dtype)
        mixed = jnp.concatenate([h.astype(cfg.compute_dtype), emb_next], axis=-1)
        h2 = mixed @ params["mtp"]["proj"]["w"].astype(mixed.dtype)
        h2 = _layer_apply(
            cfg, mi, "dense", params["mtp"]["layer"], h2, jnp.arange(tokens.shape[1])
        )
        h2 = rmsnorm_apply(params["mtp"]["norm"], h2, cfg.norm_eps)
        labels2 = jnp.concatenate([tokens[:, 2:], tokens[:, :2]], axis=1)
        w2 = jnp.concatenate(
            [
                jnp.ones_like(tokens[:, 2:], jnp.float32),
                jnp.zeros_like(tokens[:, :2], jnp.float32),
            ],
            axis=1,
        )
        mtp_loss = _chunked_ce(cfg, mi, h2, params["lm_head"]["w"], labels2, w2)
        metrics["mtp_ce"] = mtp_loss
        loss = loss + cfg.mtp_coef * mtp_loss
    if cfg.is_moe and cfg.router_aux_coef > 0:
        # Aux loss on the last MoE layer's router (cheap proxy).
        aux = moe_mod.router_aux_loss(
            jax.tree.map(lambda x: x[-1], params["moe_layers"])["ffn"], cfg, h
        )
        metrics["router_aux"] = aux
        loss = loss + cfg.router_aux_coef * aux
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# decode (serve) path
# ---------------------------------------------------------------------------
def cache_shape(cfg: TransformerConfig, batch: int, s_max: int):
    """Abstract KV-cache shapes (per layer stacked over L)."""
    if cfg.attn_type == "mla":
        entry = (batch, s_max, cfg.kv_lora_rank + cfg.d_rope)
        return {
            "c": jax.ShapeDtypeStruct((cfg.n_layers,) + entry, cfg.compute_dtype),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
    kv = (batch, s_max, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct((cfg.n_layers,) + kv, cfg.compute_dtype),
        "v": jax.ShapeDtypeStruct((cfg.n_layers,) + kv, cfg.compute_dtype),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def cache_specs(cfg: TransformerConfig, mi: MeshInfo, batch: int, s_max: int):
    """Cache sharding: batch over dp (when divisible), sequence over model."""
    bspec = mi.axes_if_divisible(batch, mi.dp_axes)
    sspec = mi.axes_if_divisible(s_max, (mi.tp_axis,))
    if cfg.attn_type == "mla":
        return {"c": P(None, bspec, sspec, None), "pos": P()}
    kv = P(None, bspec, sspec, None, None)
    return {"k": kv, "v": kv, "pos": P()}


def init_cache(cfg: TransformerConfig, batch: int, s_max: int):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_shape(cfg, batch, s_max)
    )


def _stacked_layer_params(params, cfg):
    """Concatenate dense + moe stacks into one per-layer scan structure.

    Dense and MoE layers differ structurally, so we scan them separately but
    must interleave caches correctly; layer order = dense first, then moe.
    """
    return params.get("dense_layers"), params.get("moe_layers")


def decode_step(
    params: Params,
    cfg: TransformerConfig,
    mi: MeshInfo,
    cache: dict,
    tokens: Array,  # (B,) int32 — current step's token ids
) -> tuple[Array, dict]:
    """One greedy decode step against a sequence-sharded KV cache.

    Returns (logits (B, V), updated cache).
    """
    b = tokens.shape[0]
    pos = cache["pos"]
    x = params["embed"]["table"][tokens].astype(cfg.compute_dtype)  # (B, D)
    x = mi.constrain(x, mi.axes_if_divisible(b, mi.dp_axes), None)
    seq_axis = mi.tp_axis if mi.tp_size > 1 else None

    is_mla = cfg.attn_type == "mla"
    cache_arrays = (cache["c"],) if is_mla else (cache["k"], cache["v"])

    def one_layer(x, lp, layer_cache, kind):
        h = rmsnorm_apply(lp["ln1"], x[:, None, :], cfg.norm_eps)[:, 0]
        if is_mla:
            (c_l,) = layer_cache
            a, c_l = _mla_decode_sharded(lp["attn"], cfg, mi, h, c_l, pos, seq_axis)
            new_cache = (c_l,)
        else:
            k_l, v_l = layer_cache
            a, k_l, v_l = _gqa_decode_sharded(
                lp["attn"], cfg, mi, h, k_l, v_l, pos, seq_axis
            )
            new_cache = (k_l, v_l)
        x = x + a
        h = rmsnorm_apply(lp["ln2"], x[:, None, :], cfg.norm_eps)
        if kind == "moe":
            f = moe_mod.moe_ffn(lp["ffn"], cfg, mi, h)[:, 0]
        else:
            ff = lp["ffn"]
            hh = jax.nn.silu(h[:, 0] @ ff["w1"]["w"].astype(x.dtype)) * (
                h[:, 0] @ ff["w3"]["w"].astype(x.dtype)
            )
            f = hh @ ff["w2"]["w"].astype(x.dtype)
        return x + f, new_cache

    dense_p, moe_p = _stacked_layer_params(params, cfg)
    nd = cfg.n_dense
    new_caches = []
    for kind, stacked, lo, hi in (
        ("dense", dense_p, 0, nd),
        ("moe", moe_p, nd, cfg.n_layers),
    ):
        if stacked is None or hi <= lo:
            continue
        span = hi - lo
        layer_cache = tuple(
            jax.lax.dynamic_slice_in_dim(c, lo, span, axis=0) for c in cache_arrays
        )

        def body(x, inputs, kind=kind):
            lp, lc = inputs
            x, new_lc = one_layer(x, lp, lc, kind)
            return x, new_lc

        x, updated = jax.lax.scan(body, x, (stacked, layer_cache))
        new_caches.append((lo, updated))

    # Reassemble full cache arrays.
    out_arrays = list(cache_arrays)
    for lo, updated in new_caches:
        for i in range(len(out_arrays)):
            out_arrays[i] = jax.lax.dynamic_update_slice_in_dim(
                out_arrays[i], updated[i], lo, axis=0
            )

    h = rmsnorm_apply(params["final_norm"], x[:, None, :], cfg.norm_eps)[:, 0]
    logits = (h @ params["lm_head"]["w"].astype(h.dtype)).astype(jnp.float32)
    logits = mi.constrain(logits, mi.axes_if_divisible(b, mi.dp_axes), mi.tp_axis)
    new_cache = dict(
        zip(("c",) if is_mla else ("k", "v"), out_arrays), pos=pos + 1
    )
    return logits, new_cache


def _gqa_decode_sharded(ap, cfg, mi, h, k_cache, v_cache, pos, seq_axis):
    b = h.shape[0]
    hds, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ ap["wq"]["w"].astype(h.dtype)).reshape(b, hds, dh)
    k_new = (h @ ap["wk"]["w"].astype(h.dtype)).reshape(b, hkv, dh)
    v_new = (h @ ap["wv"]["w"].astype(h.dtype)).reshape(b, hkv, dh)
    posv = jnp.asarray(pos)
    q = _rope_one(q, posv, cfg.rope_theta)
    k_new = _rope_one(k_new, posv, cfg.rope_theta)

    if seq_axis is None:
        out, k_c, v_c = attn.gqa_decode_attend(
            q, k_cache, v_cache, k_new, v_new, pos,
            seq_axis=None, shard_idx=jnp.int32(0),
        )
    else:
        bspec = mi.axes_if_divisible(b, mi.dp_axes)

        def inner(q, kc, vc, kn, vn):
            return attn.gqa_decode_attend(
                q, kc, vc, kn, vn, pos,
                seq_axis=seq_axis, shard_idx=jax.lax.axis_index(seq_axis),
            )

        out, k_c, v_c = jax.shard_map(
            inner,
            mesh=mi.mesh,
            check_vma=False,
            in_specs=(
                P(bspec, None, None),
                P(bspec, seq_axis, None, None),
                P(bspec, seq_axis, None, None),
                P(bspec, None, None),
                P(bspec, None, None),
            ),
            out_specs=(
                P(bspec, None, None),
                P(bspec, seq_axis, None, None),
                P(bspec, seq_axis, None, None),
            ),
        )(q, k_cache, v_cache, k_new, v_new)
    proj = out.reshape(b, hds * dh).astype(h.dtype) @ ap["wo"]["w"].astype(h.dtype)
    return proj, k_c, v_c


def _mla_decode_sharded(ap, cfg, mi, h, c_cache, pos, seq_axis):
    b = h.shape[0]
    if seq_axis is None:
        out, c_c = attn.mla_decode_attend(
            ap, cfg, h, c_cache, pos, seq_axis=None, shard_idx=jnp.int32(0)
        )
        return out, c_c
    bspec = mi.axes_if_divisible(b, mi.dp_axes)

    def inner(h_, cc):
        return attn.mla_decode_attend(
            ap, cfg, h_, cc, pos,
            seq_axis=seq_axis, shard_idx=jax.lax.axis_index(seq_axis),
        )

    out, c_c = jax.shard_map(
        inner,
        mesh=mi.mesh,
        check_vma=False,
        in_specs=(P(bspec, None), P(bspec, seq_axis, None)),
        out_specs=(P(bspec, None), P(bspec, seq_axis, None)),
    )(h, c_cache)
    return out, c_c


def _rope_one(x: Array, pos: Array, theta: float) -> Array:
    """RoPE for a single position: x (B, H, d) at scalar position."""
    from repro.models.common.modules import rope_frequencies

    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)
    angles = pos.astype(jnp.float32) * freqs  # (d/2,)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def prefill_logits(
    params: Params, cfg: TransformerConfig, mi: MeshInfo, tokens: Array
) -> Array:
    """Full-sequence prefill returning last-position logits (B, V)."""
    h = forward_hidden(params, cfg, mi, tokens)
    last = h[:, -1]
    logits = (last @ params["lm_head"]["w"].astype(last.dtype)).astype(jnp.float32)
    return mi.constrain(logits, mi.dp_axes, mi.tp_axis)
