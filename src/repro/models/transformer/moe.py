"""DeepSeek-style MoE FFN with expert parallelism over the ``model`` axis.

Dispatch scheme (dropless-ish, fixed shapes — see DESIGN.md):
  * the router runs globally (tiny GEMM);
  * tokens are replicated within each data-parallel group (they already are,
    between TP blocks), experts are sharded over ``model``;
  * each shard ranks the tokens routed to *its* experts by router weight and
    keeps the best C per expert (capacity = cf * T * top_k / E), gathers
    them, runs the local expert GEMMs as one batched einsum, scatters back
    weighted by the (renormalized) gate, and a single psum over ``model``
    sums expert contributions — the same collective volume as a dense TP
    FFN's all-reduce, with no all-to-all.

Shared experts (DeepSeek: always-on) are a dense SwiGLU with TP sharding.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.meshinfo import MeshInfo
from repro.models.common.modules import dense_init

Array = jax.Array
Params = dict


def moe_init(rng, cfg) -> Params:
    d, e, fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    ks = jax.random.split(rng, 7)
    scale = 1.0 / jnp.sqrt(d)
    p: Params = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "experts": {
            "w1": jax.random.uniform(ks[1], (e, d, fe), cfg.param_dtype, -scale, scale),
            "w3": jax.random.uniform(ks[2], (e, d, fe), cfg.param_dtype, -scale, scale),
            "w2": jax.random.uniform(ks[3], (e, fe, d), cfg.param_dtype, -scale, scale),
        },
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * fe
        p["shared"] = {
            "w1": dense_init(ks[4], d, fs, cfg.param_dtype),
            "w3": dense_init(ks[5], d, fs, cfg.param_dtype),
            "w2": dense_init(ks[6], fs, d, cfg.param_dtype),
        }
    return p


def moe_specs(cfg, mi: MeshInfo) -> Params:
    fs, tp = mi.fsdp_axis, mi.tp_axis
    p = {
        "router": {"w": P(None, None)},
        "experts": {
            "w1": P(tp, fs, None),
            "w3": P(tp, fs, None),
            "w2": P(tp, None, fs),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = {
            "w1": {"w": P(fs, tp)},
            "w3": {"w": P(fs, tp)},
            "w2": {"w": P(tp, fs)},
        }
    return p


def _swiglu(x: Array, w1: Array, w3: Array, w2: Array) -> Array:
    h = jax.nn.silu(x @ w1.astype(x.dtype)) * (x @ w3.astype(x.dtype))
    return h @ w2.astype(x.dtype)


def _moe_local(
    x: Array,  # (Bl, S, D) tokens of this DP group (replicated over model)
    probs: Array,  # (Bl, S, E) router probabilities (full expert axis)
    w1: Array,  # (E_local, D, Fe)
    w3: Array,
    w2: Array,
    *,
    cfg,
    tp_axis: Optional[str],
):
    bl, s, d = x.shape
    e = probs.shape[-1]
    e_local = w1.shape[0]
    t = bl * s
    top_k = cfg.top_k
    n_shards = e // e_local
    # capacity per *local* expert; total kept tokens = cf * T * top_k.
    cap = max(1, int(cfg.capacity_factor * t * top_k / e))

    xf = x.reshape(t, d)
    pf = probs.reshape(t, e)
    # Token-choice top-k threshold (k-th largest prob per token).
    thresh = jax.lax.top_k(pf, top_k)[0][:, -1]  # (T,)
    shard = jax.lax.axis_index(tp_axis) if tp_axis else 0
    local_p = jax.lax.dynamic_slice_in_dim(pf, shard * e_local, e_local, axis=1)
    gate = jnp.where(local_p >= thresh[:, None], local_p, 0.0)  # (T, E_local)
    # Renormalize selected gates to sum 1 over the chosen experts (DeepSeek).
    local_sum = jnp.sum(gate, axis=-1)
    denom = (
        jax.lax.psum(local_sum, tp_axis) if tp_axis else local_sum
    )
    gate = gate / jnp.maximum(denom[:, None], 1e-9)

    # Per-expert top-C tokens by gate weight (capacity-drop dispatch).
    scores = gate.T  # (E_local, T)
    top_w, top_idx = jax.lax.top_k(scores, min(cap, t))  # (E_local, C)
    valid = top_w > 0.0
    xg = xf[top_idx]  # (E_local, C, D)
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xg, w1.astype(xg.dtype))
    ) * jnp.einsum("ecd,edf->ecf", xg, w3.astype(xg.dtype))
    y = jnp.einsum("ecf,efd->ecd", h, w2.astype(xg.dtype))
    y = y * (top_w * valid)[..., None].astype(y.dtype)
    out = jnp.zeros((t, d), y.dtype).at[top_idx.reshape(-1)].add(
        y.reshape(-1, d), mode="drop"
    )
    if tp_axis:
        out = jax.lax.psum(out, tp_axis)
    return out.reshape(bl, s, d)


def moe_ffn(p: Params, cfg, mi: MeshInfo, x: Array) -> Array:
    """(B, S, D) -> (B, S, D). Router global; experts via shard_map EP."""
    probs = jax.nn.softmax(
        (x.astype(jnp.float32) @ p["router"]["w"]), axis=-1
    )  # (B, S, E)

    # B=1 decode cannot shard the token batch over the data axes.
    dp = mi.axes_if_divisible(x.shape[0], mi.dp_axes)
    tp = mi.tp_axis
    e = cfg.n_experts
    if mi.tp_size > 1 and e % mi.tp_size == 0:
        local = jax.shard_map(
            lambda xs, ps, w1, w3, w2: _moe_local(
                xs, ps, w1, w3, w2, cfg=cfg, tp_axis=tp
            ),
            mesh=mi.mesh,
            check_vma=False,
            in_specs=(
                P(dp, None, None),
                P(dp, None, None),
                P(tp, None, None),
                P(tp, None, None),
                P(tp, None, None),
            ),
            out_specs=P(dp, None, None),
        )
        out = local(
            x,
            probs.astype(x.dtype),
            p["experts"]["w1"],
            p["experts"]["w3"],
            p["experts"]["w2"],
        )
    else:
        out = _moe_local(
            x,
            probs.astype(x.dtype),
            p["experts"]["w1"],
            p["experts"]["w3"],
            p["experts"]["w2"],
            cfg=cfg,
            tp_axis=None,
        )
    if cfg.n_shared_experts:
        sh = p["shared"]
        shared = _swiglu(x, sh["w1"]["w"], sh["w3"]["w"], sh["w2"]["w"])
        out = out + shared
    return out


def router_aux_loss(p: Params, cfg, x: Array) -> Array:
    """Switch-style load-balancing loss (optional; DeepSeek-V3 is
    aux-loss-free via bias updates — we expose the standard aux loss as a
    config knob instead and note the deviation)."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"]["w"], axis=-1)
    e = probs.shape[-1]
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    return e * jnp.sum(frac_tokens * frac_probs)
