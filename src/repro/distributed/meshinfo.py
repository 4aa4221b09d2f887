"""Mesh metadata threaded through model builders.

Axis roles:
  * ``pod``   — data parallelism across pods (outermost; optional)
  * ``data``  — data parallel / FSDP parameter+optimizer sharding
  * ``model`` — tensor / expert / sequence(-cache) parallelism

Models never hardcode axis names; they consume a MeshInfo and emit
PartitionSpecs relative to it, so the same model code runs on the 1-device
test mesh, the 16x16 single pod, and the 2x16x16 multi-pod mesh.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

Array = jax.Array


@dataclass(frozen=True)
class MeshInfo:
    mesh: Mesh

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def has_pod(self) -> bool:
        return "pod" in self.axis_names

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return ("pod", "data") if self.has_pod else ("data",)

    @property
    def tp_axis(self) -> str:
        return "model"

    @property
    def dp_size(self) -> int:
        size = 1
        for a in self.dp_axes:
            size *= self.mesh.shape[a]
        return size

    @property
    def tp_size(self) -> int:
        return self.mesh.shape["model"]

    @property
    def fsdp_axis(self):
        """Parameter/optimizer sharding axes (ZeRO): spans every DP axis, so
        multi-pod runs shard state across pods too instead of replicating."""
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def axes_if_divisible(self, dim: int, axes):
        """Return ``axes`` when they evenly divide ``dim``, else None.

        Used to drop shardings that cannot apply (e.g. batch=1 decode cannot
        shard over the data axes; an 8-way KV-head dim cannot shard over a
        16-way model axis).
        """
        if axes is None:
            return None

        def flat(a):
            if isinstance(a, str):
                return (a,)
            out = ()
            for x in a:
                out += flat(x)
            return out

        size = 1
        for a in flat(axes):
            size *= self.mesh.shape[a]
        return axes if dim % size == 0 else None

    def constrain(self, x: Array, *spec) -> Array:
        """with_sharding_constraint that silently skips non-divisible dims."""
        fixed = []
        for dim, s in zip(x.shape, spec):
            if s is None:
                fixed.append(None)
                continue
            axes = (s,) if isinstance(s, str) else tuple(s)
            size = 1
            for a in axes:
                size *= self.mesh.shape[a]
            fixed.append(s if dim % size == 0 else None)
        # Trailing unspecified dims stay unsharded. On a mesh with Explicit
        # axes (jax.make_mesh's default) the sharding is part of the type
        # and with_sharding_constraint only asserts it, so reshard there.
        sharding = self.sharding(*fixed)
        if AxisType.Explicit in self.mesh.axis_types:
            return jax.sharding.reshard(x, sharding)
        return jax.lax.with_sharding_constraint(x, sharding)


def single_device_meshinfo() -> MeshInfo:
    """1-chip mesh with the production axis names (for CPU tests)."""
    dev = jax.devices()[0]
    import numpy as np

    mesh = Mesh(np.asarray([dev]).reshape(1, 1), ("data", "model"))
    return MeshInfo(mesh=mesh)
