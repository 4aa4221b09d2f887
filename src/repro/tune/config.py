"""KernelConfig: the explicit block-shape parameter space of every kernel.

Before the autotuner the Pallas kernels ran at fixed, hand-picked shapes —
``m_blk = min(128, round_up(m, 8))``, a hard-coded 2-deep DMA double
buffer, the whole ADC LUT reduced per probe, ``bn = 256`` for the ADC
table scan. ``KernelConfig`` names those degrees of freedom so the sweep
harness (tune/sweep.py) can search them and the committed tuning table
(tune/table.json) can pin winners per (kernel, shape, platform) key.

Semantics — chosen so every config is numerically invisible:

  * ``m_blk`` is a CAP on the output-tile width, resolved per call by
    ``lane_tile``: small candidate batches always collapse to one tile of
    ``round_up(m, 8)``, exactly like the pre-autotuner default; larger
    ones split into tiles of the cap rounded up to whole 128-lane tiles
    (the only other width the TPU compiler accepts). Distances are
    computed per candidate regardless of tiling — every ``m_blk`` yields
    identical bits (tests/test_tune.py property tests).
  * ``dma_depth`` is the candidate-row DMA pipeline depth (ring-buffer
    slots). 2 is the classic double buffer; 3–4 keep more row copies in
    flight to ride out HBM latency jitter at the cost of VMEM. Scheduling
    only — never touches values.
  * ``lut_tile`` (fused ADC kernel only) chunks the per-probe one-hot
    LUT selection over ``n_cent`` in ``lut_tile``-row slices; 0 means
    the whole table at once. Each code row selects exactly ONE entry per
    subspace, picked by an exact max over -inf padding, so tiling is
    bit-invariant by construction (kernels/fused_expand).

The declared lattice is the ONLY space the sweep searches and the only
space ``table.json`` may contain (CI validates membership — see
``repro.tune.table``).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Tuple

# Kernel names are the tuning-table key's first component.
KERNELS = ("fused_exact", "fused_adc", "gather_distance", "pq_adc")

# The declared search lattice (ISSUE 8): m_blk caps 64..512, DMA pipeline
# depth 2..4, ADC LUT tiles {whole, 8, 16} centroid columns.
LATTICE = {
    "m_blk": (64, 128, 256, 512),
    "dma_depth": (2, 3, 4),
    "lut_tile": (0, 8, 16),
}


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point of the block-shape lattice (hashable: rides jit keys and
    pytree treedefs as static aux data)."""

    m_blk: int = 128
    dma_depth: int = 2
    lut_tile: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KernelConfig":
        return cls(
            m_blk=int(d["m_blk"]),
            dma_depth=int(d["dma_depth"]),
            lut_tile=int(d["lut_tile"]),
        )


# Per-kernel defaults reproduce the pre-autotuner fixed constants exactly:
# the fused/gather kernels' min(128, round_up(m, 8)) tile + double buffer,
# pq_adc's bn=256 scan block. Used whenever the table has no entry at all
# for a (kernel, platform) — and asserted bit-identical to every other
# lattice point anyway.
DEFAULT_CONFIGS = {
    "fused_exact": KernelConfig(m_blk=128, dma_depth=2, lut_tile=0),
    "fused_adc": KernelConfig(m_blk=128, dma_depth=2, lut_tile=0),
    "gather_distance": KernelConfig(m_blk=128, dma_depth=2, lut_tile=0),
    # pq_adc consumes only m_blk (its HBM scan block ``bn``); depth/tile
    # are pinned at the lattice floor so table entries stay canonical.
    "pq_adc": KernelConfig(m_blk=256, dma_depth=2, lut_tile=0),
}


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def lane_tile(cap: int, m: int) -> int:
    """Output-tile width for ``m`` candidates under an ``m_blk`` cap.

    One tile of ``round_up(m, 8)`` when the cap covers ``m`` (a block equal
    to the whole padded axis); otherwise the cap rounded up to whole
    128-lane tiles, the only other block width the TPU compiler accepts.
    """
    tile = min(cap, _round_up(m, 8))
    return tile if tile >= m else _round_up(tile, 128)


def effective_m_blk(config: KernelConfig, m: int) -> int:
    """Resolve the m_blk cap against an actual candidate count."""
    return lane_tile(config.m_blk, m)


def validate_config(kernel: str, config: KernelConfig) -> None:
    """Raise ValueError unless ``config`` is a declared lattice point for
    ``kernel`` (the CI table-consistency check and the loader both call
    this — nothing outside the searched space ever reaches a kernel)."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r} (expected one of {KERNELS})")
    if config.m_blk not in LATTICE["m_blk"]:
        raise ValueError(f"{kernel}: m_blk={config.m_blk} outside {LATTICE['m_blk']}")
    if config.dma_depth not in LATTICE["dma_depth"]:
        raise ValueError(
            f"{kernel}: dma_depth={config.dma_depth} outside {LATTICE['dma_depth']}"
        )
    if config.lut_tile not in LATTICE["lut_tile"]:
        raise ValueError(
            f"{kernel}: lut_tile={config.lut_tile} outside {LATTICE['lut_tile']}"
        )
    if kernel != "fused_adc" and config.lut_tile != 0:
        raise ValueError(f"{kernel}: lut_tile only applies to fused_adc")
    if kernel == "pq_adc" and config.dma_depth != LATTICE["dma_depth"][0]:
        raise ValueError("pq_adc: dma_depth is not a tunable of the ADC scan")


def lattice_configs(kernel: str) -> Tuple[KernelConfig, ...]:
    """Every lattice point that applies to ``kernel`` — the sweep space.

    Dimensions a kernel does not consume are pinned at their canonical
    value (lut_tile=0 outside fused_adc, dma_depth=2 for pq_adc) so the
    sweep never times duplicate configs.
    """
    lut_tiles = LATTICE["lut_tile"] if kernel == "fused_adc" else (0,)
    depths = LATTICE["dma_depth"] if kernel != "pq_adc" else (2,)
    return tuple(
        KernelConfig(m_blk=m, dma_depth=dd, lut_tile=lt)
        for m, dd, lt in itertools.product(LATTICE["m_blk"], depths, lut_tiles)
    )
