"""Analytic roofline model per (arch x shape x mesh).

Why analytic: XLA's ``compiled.cost_analysis()`` counts a ``while`` body
ONCE regardless of trip count (verified in tests/test_roofline_model.py), so
any scan-over-layers model under-reports FLOPs/bytes by ~L x. The dry-run
still supplies memory analysis and the *structure* of the collective
schedule; the three roofline terms are computed here from first principles
and cross-checked against cost_analysis on single-layer (loop-free) configs,
where the two must agree.

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI (per the assignment).
"""
from __future__ import annotations

import dataclasses

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # B/s
LINK_BW = 50e9  # B/s per ICI link


@dataclasses.dataclass
class RooflineTerms:
    cell: str
    mesh: str
    chips: int
    flops: float  # total FLOPs per step, summed over chips
    hbm_bytes: float  # total HBM bytes touched per step, summed over chips
    coll_bytes: float  # per-chip wire bytes per step
    model_flops: float  # 6*N*D (train) / 2*N_active*D (serve) "useful" flops

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        ts = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(ts, key=ts.get)

    @property
    def step_time(self) -> float:
        """No-overlap upper bound (sum) — conservative."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_fraction(self) -> float:
        return self.model_flops / max(self.flops, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU bound: useful flops / (step_time * peak)."""
        return self.model_flops / (self.step_time * self.chips * PEAK_FLOPS)


# ---------------------------------------------------------------------------
# LM transformers
# ---------------------------------------------------------------------------
def _lm_matmul_params(cfg) -> tuple[float, float]:
    """(total matmul params, active matmul params per token)."""
    d = cfg.d_model
    attn = {}
    if cfg.attn_type == "mla":
        per = cfg.kv_lora_rank * cfg.n_heads * (cfg.d_nope + cfg.d_v)  # wkv_b
        per += d * (cfg.kv_lora_rank + cfg.d_rope)  # wkv_a
        per += cfg.n_heads * cfg.d_v * d  # wo
        if cfg.q_lora_rank:
            per += d * cfg.q_lora_rank + cfg.q_lora_rank * cfg.n_heads * (
                cfg.d_nope + cfg.d_rope
            )
        else:
            per += d * cfg.n_heads * (cfg.d_nope + cfg.d_rope)
    else:
        per = d * cfg.n_heads * cfg.head_dim * 2 + d * cfg.n_kv_heads * cfg.head_dim * 2
    dense_ffn = 3 * d * cfg.d_ff
    moe_total = 3 * d * cfg.d_ff_expert * cfg.n_experts if cfg.is_moe else 0
    moe_active = 3 * d * cfg.d_ff_expert * cfg.top_k if cfg.is_moe else 0
    shared = 3 * d * cfg.d_ff_expert * cfg.n_shared_experts if cfg.is_moe else 0
    head = 2 * d * cfg.vocab_padded  # embed + lm_head
    total = (
        cfg.n_dense * (per + dense_ffn)
        + cfg.n_moe * (per + moe_total + shared)
        + head
    )
    active = (
        cfg.n_dense * (per + dense_ffn)
        + cfg.n_moe * (per + moe_active + shared)
        + head
    )
    if cfg.mtp:
        total += per + dense_ffn + 2 * d * d
        active += per + dense_ffn + 2 * d * d
    return float(total), float(active)


def _lm_attn_flops_fwd(cfg, batch: int, s_q: int, s_kv: int) -> float:
    """Score+PV matmuls; our flash kernel computes the full rectangle (the
    causal mask is applied, not skipped), so no /2."""
    dh_qk = cfg.d_nope + cfg.d_rope if cfg.attn_type == "mla" else cfg.head_dim
    dh_v = cfg.d_v if cfg.attn_type == "mla" else cfg.head_dim
    return 2.0 * batch * cfg.n_heads * s_q * s_kv * (dh_qk + dh_v) * cfg.n_layers


def lm_train_terms(cfg, batch: int, seq: int, chips: int, grad_accum: int = 1):
    tokens = batch * seq
    total_p, active_p = _lm_matmul_params(cfg)
    # fwd 2, bwd 4, full-remat recompute +2.
    remat_mult = 8.0 if cfg.remat == "full" else 6.0
    mm_flops = remat_mult / 2.0 * 2.0 * active_p * tokens
    # attention: fwd + remat recompute + FA2 bwd (5 matmuls vs 2 fwd).
    attn_fwd = _lm_attn_flops_fwd(cfg, batch, seq, seq)
    attn_flops = attn_fwd * (1.0 + 1.0 + 2.5)
    flops = mm_flops + attn_flops
    model_flops = 6.0 * active_p * tokens

    p_bytes = total_p * 2.0  # bf16
    # params: fwd read + bwd read + grad write + opt read/write (factored
    # stats are negligible; momentum bf16 r/w).
    param_traffic = p_bytes * 5.0
    # activations: residual + block internals, ~12 r/w of (T, D) per layer,
    # x2 for remat recompute; bf16.
    act_traffic = 12.0 * 2.0 * cfg.n_layers * tokens * cfg.d_model * 2.0
    hbm = param_traffic + act_traffic

    # Collectives per chip: TP reduce-scatter+all-gather pairs per layer
    # (SP residual x4), MoE psum, FSDP param all-gather (fwd+bwd) + grad RS.
    tp = 16
    t_local = tokens / max(chips / tp, 1)
    layer_ar = 4.0 * t_local * cfg.d_model * 2.0 * cfg.n_layers * grad_accum
    fsdp = 3.0 * p_bytes / tp  # AG fwd + AG bwd + RS grads, per chip
    coll = layer_ar + fsdp
    return flops, hbm, coll, model_flops


def lm_prefill_terms(cfg, batch: int, seq: int, chips: int):
    tokens = batch * seq
    _, active_p = _lm_matmul_params(cfg)
    flops = 2.0 * active_p * tokens + _lm_attn_flops_fwd(cfg, batch, seq, seq)
    model_flops = 2.0 * active_p * tokens
    total_p, _ = _lm_matmul_params(cfg)
    hbm = total_p * 2.0 + 8.0 * cfg.n_layers * tokens * cfg.d_model * 2.0
    tp = 16
    t_local = tokens / max(chips / tp, 1)
    coll = 4.0 * t_local * cfg.d_model * 2.0 * cfg.n_layers
    return flops, hbm, coll, model_flops


def lm_decode_terms(cfg, batch: int, s_cache: int, chips: int):
    total_p, active_p = _lm_matmul_params(cfg)
    flops = 2.0 * active_p * batch
    if cfg.attn_type == "mla":
        kv_row = cfg.kv_lora_rank + cfg.d_rope  # latent cache, no head dim
        attn = 2.0 * batch * cfg.n_heads * s_cache * (kv_row + cfg.kv_lora_rank)
        cache_bytes = batch * s_cache * kv_row * 2.0 * cfg.n_layers
    else:
        attn = (
            2.0 * batch * cfg.n_heads * s_cache * 2 * cfg.head_dim
        )
        cache_bytes = (
            2.0 * batch * s_cache * cfg.n_kv_heads * cfg.head_dim * 2.0 * cfg.n_layers
        )
    attn *= cfg.n_layers
    flops += attn
    model_flops = 2.0 * active_p * batch + attn
    hbm = total_p * 2.0 + cache_bytes  # weights + whole cache read each step
    # LSE-combine psums (tiny) + TP psum of (B, D) per layer + head gather.
    coll = 4.0 * batch * cfg.d_model * 2.0 * cfg.n_layers / max(chips / 16, 1)
    return flops, hbm, coll, model_flops


# ---------------------------------------------------------------------------
# MACE GNN
# ---------------------------------------------------------------------------
def mace_terms(cfg, n_nodes: int, n_edges: int, chips: int, mode: str):
    k = cfg.d_hidden
    # per edge: radial MLP + messages for 13 lm components; per node: 8K->K
    # update + invariant contractions (~30 K flops) ; x3 for fwd+bwd(energy)
    # and x2 again for the force grad (second backward).
    edge_flops = n_edges * (
        2 * (cfg.n_rbf * cfg.d_radial_mlp + cfg.d_radial_mlp * 3 * k) + 2 * 13 * k
    )
    node_flops = n_nodes * (2 * 8 * k * k + 40 * k)
    fwd = (edge_flops + node_flops) * cfg.n_layers
    flops = fwd * 6.0  # fwd + bwd + force-grad double-backward
    model_flops = fwd * 6.0
    feat = cfg.d_feat if cfg.d_feat else cfg.n_species
    hbm = (
        n_edges * (13 + 3) * k * 4.0 * cfg.n_layers * 3.0
        + n_nodes * (13 * k + feat) * 4.0 * 3.0
    )
    if mode == "dst_partitioned":
        coll = cfg.n_layers * 3.0 * n_nodes * k * 2.0  # all-gather h per layer
    elif mode == "simple":
        coll = 0.0
    else:
        coll = n_nodes * k * 4.0  # psum of A for edge-sharded modes
    return flops, hbm, coll, model_flops


# ---------------------------------------------------------------------------
# RecSys
# ---------------------------------------------------------------------------
def _mlp_params(dims) -> float:
    return float(sum(a * b for a, b in zip(dims[:-1], dims[1:])))


def recsys_terms(cfg, batch: int, chips: int, kind: str, n_candidates: int = 0):
    d = cfg.embed_dim
    if cfg.model == "dlrm":
        n_f = len(cfg.vocab_sizes) + 1
        mlp_p = _mlp_params((cfg.n_dense,) + cfg.bot_mlp) + _mlp_params(
            (n_f * (n_f - 1) // 2 + cfg.bot_mlp[-1],) + cfg.top_mlp
        )
        inter_flops = 2.0 * batch * n_f * n_f * d
        lookup_rows = batch * len(cfg.vocab_sizes)
    elif cfg.model == "deepfm":
        n_f = len(cfg.vocab_sizes)
        mlp_p = _mlp_params((n_f * d,) + cfg.mlp + (1,))
        inter_flops = 2.0 * batch * n_f * d
        lookup_rows = batch * n_f * 2
    elif cfg.model == "sasrec":
        mlp_p = 8.0 * d * d * cfg.n_blocks
        inter_flops = (
            4.0 * batch * cfg.seq_len**2 * d * cfg.n_blocks
            + 2.0 * batch * cfg.seq_len * d  # scoring
        )
        lookup_rows = batch * cfg.seq_len * 3
    else:  # two_tower
        mlp_p = _mlp_params((2 * d,) + cfg.tower_mlp) + _mlp_params(
            (d,) + cfg.tower_mlp
        )
        inter_flops = 2.0 * batch * batch * cfg.tower_mlp[-1]  # in-batch logits
        lookup_rows = batch * (2 + cfg.hist_len)

    mm = 2.0 * mlp_p * batch
    mult = 6.0 if kind == "train" else 2.0
    flops = mm / 2.0 * mult + inter_flops * (3.0 if kind == "train" else 1.0)
    if n_candidates:
        flops += 2.0 * batch * n_candidates * cfg.tower_mlp[-1] if cfg.model == "two_tower" \
            else 2.0 * batch * n_candidates * d
    model_flops = flops
    emb_traffic = lookup_rows * d * 4.0 * (2.0 if kind == "train" else 1.0)
    hbm = emb_traffic + mlp_p * 4.0 * (3.0 if kind == "train" else 1.0)
    if n_candidates:
        hbm += n_candidates * cfg.tower_mlp[-1] * 4.0 if cfg.model == "two_tower" \
            else n_candidates * d * 4.0
    # sharded-table lookups: psum of gathered rows across the model axis
    coll = lookup_rows / max(chips / 16, 1) * d * 4.0
    return flops, hbm, coll, model_flops


# ---------------------------------------------------------------------------
# Per-config kernel roofline (PR8 autotuner)
# ---------------------------------------------------------------------------
# The block-shape autotuner (repro.tune) prunes lattice configs the model
# predicts are memory-dominated-worse before spending wall-clock on them,
# and the regression gate anchors measured kernel time against the same
# bound. On a TPU the bound uses that chip's published peaks, looked up by
# the device kind JAX reports — a kind missing from TPU_PEAKS is an error,
# never a silent v5e. Any other platform is treated as a host (CPU
# jnp/interpret) with the sustained-DRAM numbers below — deliberately round
# figures, because the gate compares *fractions of the bound across runs on
# the same platform*, where the constant cancels, not absolute MFU claims.
HOST_BW = 20e9  # B/s sustained single-socket DRAM stream
HOST_FLOPS = 100e9  # f32 FLOP/s, one core + modest SIMD (pytest/CI class)
VMEM_BYTES = 64 * 1024 * 1024  # per-core VMEM budget we allow a config
# (FLOP/s, HBM bytes/s) per chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s).
TPU_PEAKS = {"TPU v5 lite": (PEAK_FLOPS, HBM_BW)}


def chip_peaks(platform: str, device_kind: str | None = None) -> tuple:
    """(FLOP/s, bytes/s) that bound a kernel on ``platform``.

    On "tpu" these are the peaks of ``device_kind`` (default: the first
    device's); a kind not in ``TPU_PEAKS`` raises ValueError.
    """
    if platform != "tpu":
        return HOST_FLOPS, HOST_BW
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in TPU_PEAKS:
        raise ValueError(
            f"no roofline peaks for TPU device kind {device_kind!r} "
            f"(known: {sorted(TPU_PEAKS)})"
        )
    return TPU_PEAKS[device_kind]


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@dataclasses.dataclass(frozen=True)
class KernelRoofline:
    """Predicted cost of ONE tuned-kernel invocation at a fixed config.

    flops/hbm_bytes follow from shape + padding (m_blk caps the tile, so
    the padded candidate count m_pad = round_up(m, effective tile) is the
    config-sensitive term); vmem_bytes is the peak resident working set
    (DMA ring + per-query operands + output tile). dma_depth never moves
    the bound — it is pure scheduling — so depth variants of one m_blk
    tie here and are separated only by measurement.
    """

    flops: float
    hbm_bytes: float
    vmem_bytes: float

    def t_compute(self, platform: str = "tpu") -> float:
        return self.flops / chip_peaks(platform)[0]

    def t_memory(self, platform: str = "tpu") -> float:
        return self.hbm_bytes / chip_peaks(platform)[1]

    def time_bound(self, platform: str = "tpu") -> float:
        return max(self.t_compute(platform), self.t_memory(platform))

    def memory_bound(self, platform: str = "tpu") -> bool:
        return self.t_memory(platform) >= self.t_compute(platform)


def kernel_roofline(
    kernel: str,
    config,
    *,
    b: int,
    m: int,
    d: int,
    n_cent: int = 16,
) -> KernelRoofline:
    """Roofline terms for one tuned kernel at (batch b, candidates m).

    ``d`` is the payload width: the vector dim for fused_exact /
    gather_distance, the subquantizer count m_sub for fused_adc / pq_adc
    (for pq_adc, ``m`` is the corpus row count the scan covers). Mirrors
    the kernels' own padding arithmetic: effective tile =
    ``lane_tile(m_blk, m)``, m_pad = round_up(m, tile) — the term that
    makes one m_blk beat another at fixed work.
    """
    from repro.tune.config import lane_tile

    eff = lane_tile(config.m_blk, max(m, 1))
    m_pad = _round_up(max(m, 1), eff)
    row = 4.0 * d  # f32 vector row / int32 code row
    if kernel in ("fused_exact", "fused_adc"):
        meta = 4.0  # constraint metadata word riding the row DMA
        out = 12.0  # dist f32 + satisfied/fresh words
    elif kernel == "gather_distance":
        meta, out = 0.0, 4.0
    elif kernel == "pq_adc":
        meta, out = 0.0, 4.0
    else:
        raise ValueError(f"unknown kernel {kernel!r}")

    hbm = b * m_pad * (row + meta) + b * m_pad * out
    if kernel in ("fused_exact", "gather_distance"):
        # query row in + 3 flops/element (sub, square, accumulate)
        hbm += b * row
        flops = 3.0 * b * m_pad * d
    else:
        # ADC: per candidate row, each of d code words scans its n_cent
        # LUT chunk (compare + select + add); LUT streamed in once per
        # query. lut_tile re-shapes the scan, never its flop count.
        hbm += b * d * n_cent * 4.0
        flops = 3.0 * b * m_pad * d * n_cent

    lut_res = d * n_cent * 4.0 if kernel in ("fused_adc", "pq_adc") else 0.0
    chunk = getattr(config, "lut_tile", 0) or n_cent
    vmem = (
        config.dma_depth * (row + 4.0)  # row ring + meta ring
        + row  # query / per-query operand block
        + eff * out  # output tile
        + lut_res
        + min(chunk, n_cent) * d * 4.0  # active LUT slice of the scan
    )
    return KernelRoofline(flops=float(flops), hbm_bytes=float(hbm), vmem_bytes=float(vmem))


def prune_configs(
    kernel: str,
    configs,
    *,
    b: int,
    m: int,
    d: int,
    n_cent: int = 16,
    platform: str = "tpu",
):
    """Split a config lattice into (survivors, pruned) before timing.

    A config is pruned when (a) its working set exceeds VMEM_BYTES, or
    (b) the model says the kernel is memory-bound at this shape AND the
    config reads strictly more HBM bytes than the best config — timing
    it cannot change the winner, only burn sweep budget. Compute-bound
    shapes keep every feasible config: byte count no longer predicts
    rank there.
    """
    terms = {
        cfg: kernel_roofline(kernel, cfg, b=b, m=m, d=d, n_cent=n_cent)
        for cfg in configs
    }
    feasible = {c: t for c, t in terms.items() if t.vmem_bytes <= VMEM_BYTES}
    survivors, pruned = [], []
    best_bytes = min((t.hbm_bytes for t in feasible.values()), default=0.0)
    for cfg in configs:
        t = terms[cfg]
        if cfg not in feasible:
            pruned.append(cfg)
        elif t.memory_bound(platform) and t.hbm_bytes > best_bytes:
            pruned.append(cfg)
        else:
            survivors.append(cfg)
    return survivors, pruned


# ---------------------------------------------------------------------------
# AIRSHIP constrained search (serve)
# ---------------------------------------------------------------------------
def airship_terms(cfg, batch: int, chips: int, est_iters: float = 200.0):
    tp = 16
    d = cfg.dim
    # Per query per iteration: gather degree rows + distances; queue merge
    # sort ~ (ef+deg) log; across tp shards each runs the full search on its
    # shard (scatter-search-merge executes tp searches per query).
    per_iter_flops = 3.0 * cfg.degree * d  # sub+sq+add
    flops = batch * tp * est_iters * per_iter_flops + batch * tp * (
        cfg.sample_per_shard * 3.0 * d
    )
    model_flops = flops
    hbm = batch * tp * est_iters * cfg.degree * d * 4.0  # the gathers
    k = cfg.params.k
    coll = batch / max(chips / tp, 1) * tp * k * 8.0  # final all-gather merge
    return flops, hbm, coll, model_flops
