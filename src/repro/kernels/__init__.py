"""Pallas TPU kernels for the compute hot-spots of the constrained-search
system. Each subpackage ships <name>.py (pl.pallas_call + BlockSpec),
ops.py (jit'd public wrapper with a pure-jnp fallback) and ref.py (the
oracle the tests assert against). On a TPU the kernels are compiled by
Mosaic (tests/test_tpu_compile.py compiles the search-path kernels for a
described v5e at 1M x 128; chip_smoke.py runs the fused pipeline on the
chip); on the CPU the tests run them in interpret mode.

Every ops.py wrapper routes through ``dispatch_kernel`` below — the one
copy of the "Pallas on TPU, jnp oracle elsewhere, interpret-mode Pallas
for tests/CI smoke" platform policy. On a TPU it never swaps in the
oracle or the interpreter.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax


def dispatch_kernel(
    kernel_fn: Callable,
    ref_fn: Callable,
    *,
    force_kernel: bool = False,
) -> Tuple[Callable, bool]:
    """Select the execution path for one kernel call.

    Returns ``(fn, used_kernel)``: the compiled Pallas kernel on TPU, the
    interpret-mode kernel when ``force_kernel`` (tests and CI smoke runs
    exercise the real kernel body on CPU), the pure-jnp oracle otherwise.
    ``used_kernel`` lets wrappers post-process kernel-only output quirks
    (e.g. the fused kernels' int32 masks -> bool).

    ``kernel_fn`` must accept ``interpret=``; both callables must share
    the remaining signature.
    """
    if jax.default_backend() == "tpu":
        return functools.partial(kernel_fn, interpret=False), True
    if force_kernel:
        return functools.partial(kernel_fn, interpret=True), True
    return ref_fn, False
