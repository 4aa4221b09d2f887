"""Destination-partitioned distributed MACE for full-graph training at
ogb-products scale (2.4M nodes, 62M edges).

Memory problem: the ACE A-basis is (N, K, 13) floats — ~16 GB at N=2.4M,
K=128 — far over a v5e's HBM if replicated. Layout that fixes it:

  * edges are partitioned by *destination* shard (data pipeline contract:
    every edge lives on the shard that owns its receiver; receiver ids are
    shard-local),
  * node state h is sharded by the same node blocks; each layer all-gathers
    only h (N x K, ~1 GB bf16) to read sender features, and accumulates the
    13x larger A-basis strictly locally — no psum of A ever happens,
  * readout reduces locally + one scalar psum.

Per-layer collective volume = one all-gather of (N, K) over the flattened
mesh; everything edge- and A-sized stays shard-local.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.meshinfo import MeshInfo
from repro.models.common.modules import mlp_apply
from repro.models.gnn.mace import MACEConfig, bessel_rbf

Array = jax.Array
Params = dict


def _flat_shard_index(mi: MeshInfo):
    idx = jnp.int32(0)
    for a in mi.dp_axes + (mi.tp_axis,):
        idx = idx * mi.mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _all_axes(mi: MeshInfo):
    return mi.dp_axes + (mi.tp_axis,)


def dst_partitioned_energy(
    params: Params, cfg: MACEConfig, mi: MeshInfo, batch: dict
) -> Array:
    """Total energy with the dst-partitioned layout. Returns scalar."""

    axes = _all_axes(mi)

    def local_fn(positions, feat, senders, receivers_local):
        # positions/feat replicated (N, .); edges local.
        n = positions.shape[0]
        n_shards = 1
        for a in axes:
            n_shards *= mi.mesh.shape[a]
        n_local = n // n_shards
        shard = _flat_shard_index(mi)
        lo = shard * n_local

        if cfg.d_feat:
            feat_local = jax.lax.dynamic_slice_in_dim(feat, lo, n_local, axis=0)
            h_local = feat_local.astype(cfg.compute_dtype) @ params["embed"][
                "w"
            ].astype(cfg.compute_dtype)
        else:
            sp_local = jax.lax.dynamic_slice_in_dim(feat, lo, n_local, axis=0)
            h_local = jax.nn.one_hot(
                sp_local, cfg.n_species, dtype=cfg.compute_dtype
            ) @ params["embed"]["w"].astype(cfg.compute_dtype)

        valid = (senders >= 0) & (receivers_local >= 0)
        s = jnp.maximum(senders, 0)
        r = jnp.maximum(receivers_local, 0)
        pos_local = jax.lax.dynamic_slice_in_dim(positions, lo, n_local, axis=0)
        rvec = pos_local[r] - positions[s]  # (E_l, 3)
        dist = jnp.linalg.norm(rvec + 1e-12, axis=-1)
        rhat = rvec / jnp.maximum(dist, 1e-9)[..., None]
        rbf = bessel_rbf(dist, cfg.n_rbf, cfg.r_cut).astype(cfg.compute_dtype)
        eye = jnp.eye(3, dtype=cfg.compute_dtype) / 3.0
        y2 = rhat[:, :, None] * rhat[:, None, :] - eye

        def layer(h_local, lp):
            # The only inter-shard traffic: gather global sender features.
            h_global = jax.lax.all_gather(h_local, axes, tiled=True)  # (N, K)
            rad = mlp_apply(lp["radial"], rbf, act=jax.nn.silu)
            rad = rad * valid[:, None].astype(rad.dtype)
            r0, r1, r2 = rad[:, : cfg.d_hidden], rad[:, cfg.d_hidden : 2 * cfg.d_hidden], rad[:, 2 * cfg.d_hidden :]
            hs = h_global[s] @ lp["mix_a"]["w"].astype(h_local.dtype)
            m0 = r0 * hs
            m1 = (r1 * hs)[:, :, None] * rhat.astype(hs.dtype)[:, None, :]
            m2 = (r2 * hs)[:, :, None, None] * y2.astype(hs.dtype)[:, None]
            seg = lambda m: jax.ops.segment_sum(m, r, num_segments=n_local)
            a0, a1, a2 = seg(m0), seg(m1), seg(m2)
            i_a0 = a0
            i_11 = jnp.einsum("nki,nki->nk", a1, a1)
            i_22 = jnp.einsum("nkij,nkij->nk", a2, a2)
            i_00 = a0 * a0
            i_121 = jnp.einsum("nki,nkij,nkj->nk", a1, a2, a1)
            i_222 = jnp.einsum("nkij,nkjl,nkli->nk", a2, a2, a2)
            i_000 = a0 * a0 * a0
            i_011 = a0 * i_11
            feats = jnp.concatenate(
                [i_a0, i_11, i_22, i_00, i_121, i_222, i_000, i_011], axis=-1
            )
            return h_local + feats @ lp["update"]["w"].astype(h_local.dtype), None

        h_local, _ = jax.lax.scan(layer, h_local, params["layers"])
        node_e = mlp_apply(params["readout"], h_local, act=jax.nn.silu)[..., 0]
        return jax.lax.psum(jnp.sum(node_e), axes)

    feat_key = "node_feat" if cfg.d_feat else "species"
    edge_spec = P(axes)
    fn = jax.shard_map(
        local_fn,
        mesh=mi.mesh,
        check_vma=False,
        in_specs=(P(), P(), edge_spec, edge_spec),
        out_specs=P(),
    )
    return fn(
        batch["positions"].astype(cfg.compute_dtype),
        batch[feat_key],
        batch["senders"],
        batch["receivers_local"],
    )


def dst_partitioned_loss(params, cfg, mi, batch):
    """Energy + force objective under the dst-partitioned layout."""

    def e_total(pos):
        return dst_partitioned_energy(params, cfg, mi, dict(batch, positions=pos))

    e, neg_f = jax.value_and_grad(e_total)(batch["positions"])
    f = -neg_f
    e_target = jnp.sum(batch.get("energy", jnp.zeros(())))
    f_target = batch.get("forces", jnp.zeros_like(f))
    n = batch["positions"].shape[0]
    e_loss = (e - e_target) ** 2 / n
    f_loss = jnp.mean(jnp.sum((f - f_target) ** 2, axis=-1))
    total = e_loss + f_loss
    return total, {"loss": total, "e_loss": e_loss, "f_loss": f_loss}
