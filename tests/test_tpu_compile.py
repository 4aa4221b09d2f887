"""Compile the search path for a described TPU v5e chip (no chip attached).

The TPU compiler ships with jaxlib's libtpu, so these tests lower and
compile the Pallas kernels and one whole jitted search at the paper's
deployment shapes (airship-sift1m: n = 1M, d = 128, degree 32, B = 128,
m_sub = 16) exactly as the chip's compiler would, catching what interpret
mode cannot: block shapes off the (8, 128) tiling, scalar VMEM stores,
dynamic lane loads, programs larger than the chip's memory. Nothing runs,
so nothing here says anything about results or times.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file. Keep these tests in this one file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.constraints import LabelSetConstraint
from repro.core.engine.context import build_context
from repro.core.engine.loop import search_with_context
from repro.core.types import Corpus, GraphIndex, SearchParams
from repro.kernels.fused_expand.fused_expand import (
    FAMILIES,
    fused_expand_adc_kernel,
    fused_expand_kernel,
)
from repro.kernels.gather_distance.gather_distance import gather_distance_kernel
from repro.kernels.pq_adc.pq_adc import pq_adc_kernel

N, D, DEG, B, M_SUB, N_CENT, N_LABELS, SAMPLE = (
    1_000_000, 128, 32, 128, 16, 256, 10, 512,
)
W = (N + 31) // 32  # visited / tombstone words
HBM_BYTES = 16 * 10**9  # one v5e chip

f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described chip. The
    persistent compile cache is off around these compiles: an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)


def _fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, mem
    return total


def _family_operands(spec, family):
    meta = spec((N,), f32 if family == "range" else i32)
    cons = {
        "label": spec((B, (N_LABELS + 31) // 32), u32),
        "range": spec((B, 2), f32),
        "udf": spec((1, 1), i32),
    }[family]
    return meta, cons


@pytest.mark.parametrize("with_tomb", [False, True])
@pytest.mark.parametrize("family", FAMILIES)
def test_fused_expand_compiles(spec, family, with_tomb):
    meta, cons = _family_operands(spec, family)
    tomb = spec((W,), u32) if with_tomb else None
    fn = jax.jit(functools.partial(fused_expand_kernel, family=family))
    compiled = fn.lower(
        spec((B, D), f32), spec((N, D), f32), spec((B, DEG), i32),
        spec((B, W), u32), meta, cons, tomb,
    ).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("with_tomb", [False, True])
@pytest.mark.parametrize("family", FAMILIES)
def test_fused_expand_adc_compiles(spec, family, with_tomb):
    meta, cons = _family_operands(spec, family)
    tomb = spec((W,), u32) if with_tomb else None
    fn = jax.jit(functools.partial(fused_expand_adc_kernel, family=family))
    compiled = fn.lower(
        spec((B, M_SUB, N_CENT), f32), spec((N, M_SUB), i32),
        spec((B, DEG), i32), spec((B, W), u32), meta, cons, tomb,
    ).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [DEG, 200])  # one tile; two 128-lane tiles
def test_gather_distance_compiles(spec, m):
    compiled = jax.jit(gather_distance_kernel).lower(
        spec((B, D), f32), spec((N, D), f32), spec((B, m), i32)
    ).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_pq_adc_compiles(spec):
    compiled = jax.jit(pq_adc_kernel).lower(
        spec((B, M_SUB, N_CENT), f32), spec((N, M_SUB), i32)
    ).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def _compile_search(spec, params, b):
    """Compile one whole jitted search over the 1M x 128 deployment with a
    batch of ``b`` label-constrained queries."""

    def search(corpus, graph, queries, cons):
        ctx = build_context(corpus, cons, queries, params, degree=DEG)
        return search_with_context(ctx, corpus, graph, queries, params)

    corpus = Corpus(
        vectors=spec((N, D), f32), labels=spec((N,), i32),
        attrs=spec((N, 2), f32),
    )
    graph = GraphIndex(
        neighbors=spec((N, DEG), i32), sample_ids=spec((SAMPLE,), i32),
        entry_point=spec((), i32),
    )
    cons = LabelSetConstraint(words=spec((b, (N_LABELS + 31) // 32), u32))
    return jax.jit(search).lower(
        corpus, graph, spec((b, D), f32), cons
    ).compile()


@pytest.mark.parametrize("fuse", ["on", "off"])
def test_search_step_compiles_and_fits(spec, monkeypatch, fuse):
    """One whole jitted search at the airship-sift1m shapes. The program
    asks jax.default_backend() which kernels to dispatch; here that is the
    CPU, so the test answers "tpu" for the duration of the trace."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = SearchParams(
        mode="prefer", k=16, ef_result=64, ef_sat=64, ef_other=64,
        n_start=16, max_iters=128, fuse_expand=fuse,
    )
    compiled = _compile_search(spec, params, B)
    total = _fits(compiled)
    assert total > N * D * 4  # the corpus itself is an argument
    assert ("tpu_custom_call" in compiled.as_text()) == (fuse == "on")


def test_frontier_push_gathers_no_ids(spec, monkeypatch):
    """The unfused search at the bulk serving shape (32 queries, ef 1024)
    updates its frontiers with a sort that carries the ids: the program
    holds no per-row id gather to a queue's width ef, nor to the width
    ef + M that a push sorts (M = degree for the sat/other pushes, 1 for
    the result list). Each such gather ran one element at a time on the
    chip, ~330 us at this shape, against ~23 us for the sort beside it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bulk, ef = 32, 1024
    params = SearchParams(
        mode="prefer", k=16, ef_result=ef, ef_sat=ef, ef_other=ef,
        n_start=16, max_iters=4096, fuse_expand="off",
    )
    text = _compile_search(spec, params, bulk).as_text()
    widths = "|".join(str(w) for w in (ef, ef + 1, ef + DEG))
    id_gathers = re.findall(
        rf"= s32\[{bulk},(?:{widths})\]\{{[^}}]*\}} gather\(", text)
    assert id_gathers == []
    # the pushes are there, as sorts over (ef + M) keys with the ids beside
    pushes = re.findall(
        rf"sort\(\S+, \S+, \S+\), dimensions=\{{1\}}, is_stable=true", text)
    assert len(pushes) >= 3
    assert f"s32[{bulk},{ef + DEG}]" in text
