"""Roofline-model validation.

1. Demonstrates the XLA artifact the analytic model exists to correct:
   cost_analysis counts a while/scan body once, independent of trip count.
2. Cross-checks the analytic LM FLOPs against cost_analysis on a
   single-layer (loop-light) config, where the two must agree.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.distributed.meshinfo import single_device_meshinfo
from repro.models.transformer.model import TransformerConfig, forward_hidden, init_params
from repro.roofline.model import (
    RooflineTerms,
    _lm_matmul_params,
    lm_prefill_terms,
)

MI = single_device_meshinfo()


def test_xla_cost_analysis_undercounts_scans():
    def f_scan(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=10)[0]

    def f_once(x, w):
        return jnp.tanh(x @ w)

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    f10 = jax.jit(f_scan).lower(x, w).compile().cost_analysis()["flops"]
    f1 = jax.jit(f_once).lower(x, w).compile().cost_analysis()["flops"]
    # the artifact: 10 iterations counted ~once (tiny loop-counter ops only)
    assert f10 < 1.5 * f1


def test_analytic_lm_flops_matches_measured_single_layer():
    cfg = TransformerConfig(
        name="probe", n_layers=1, d_model=128, n_heads=4, n_kv_heads=4,
        head_dim=32, d_ff=256, vocab_size=512, attn_type="gqa",
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
        attn_chunk=64, ce_chunk=64, remat="none", sequence_parallel=False,
    )
    b, s = 2, 64
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)

    def fwd(p, t):
        h = forward_hidden(p, cfg, MI, t)
        return (h[:, -1] @ p["lm_head"]["w"]).astype(jnp.float32)

    measured = jax.jit(fwd).lower(params, toks).compile().cost_analysis()["flops"]
    f, _, _, mf = lm_prefill_terms(cfg, b, s, chips=1)
    # last-position logits only in the probe; analytic assumes full-seq CE.
    # Compare the dominant matmul component instead.
    _, active = _lm_matmul_params(cfg)
    analytic_core = 2.0 * (active - 2 * cfg.d_model * cfg.vocab_padded) * b * s
    assert measured > 0
    ratio = analytic_core / measured
    assert 0.5 < ratio < 1.6, (analytic_core, measured)


def test_roofline_terms_math():
    t = RooflineTerms(
        cell="x", mesh="m", chips=256,
        flops=256 * 197e12,  # exactly 1 second of compute
        hbm_bytes=256 * 819e9 * 0.5,
        coll_bytes=50e9 * 0.25,
        model_flops=256 * 197e12 * 0.8,
    )
    assert abs(t.t_compute - 1.0) < 1e-9
    assert abs(t.t_memory - 0.5) < 1e-9
    assert abs(t.t_collective - 0.25) < 1e-9
    assert t.bottleneck == "compute"
    assert abs(t.roofline_fraction - 0.8) < 1e-9


def test_param_count_consistency_with_analytic():
    """Analytic matmul-param count tracks eval_shape param count."""
    from repro.archs.base import get_arch

    cfg = get_arch("granite-3-2b").cfg
    total, active = _lm_matmul_params(cfg)
    n = cfg.param_count()
    assert total == active  # dense model
    assert abs(total - n) / n < 0.02  # norms are the only non-matmul params


def test_kernel_bound_refuses_an_unknown_tpu():
    from repro.roofline.model import TPU_PEAKS, chip_peaks

    assert chip_peaks("tpu", "TPU v5 lite") == TPU_PEAKS["TPU v5 lite"]
    with pytest.raises(ValueError, match="device kind"):
        chip_peaks("tpu", "TPU v4")
    assert chip_peaks("cpu") == chip_peaks("cpu", "anything")
