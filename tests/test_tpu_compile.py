"""Compile every serving-path Pallas kernel for a TPU v5e at the published
stablelm-1.6b widths (d_model 2048, 32 x 64 heads, d_ff 5632), without a
chip.

The TPU compiler is installed with jax; it compiles for a described (not
attached) ``v5e:2x2`` topology and raises what the chip's compiler would:
illegal block shapes, unsupported Mosaic ops, fast memory over the scoped
limit.  Interpret-mode tests cannot see any of these.  Nothing runs, so
these tests say nothing about results or speed.

The topology is described inside a module fixture - never at import - so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.  Keep every such compile in this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kv_cache import (cache_scatter_p, decode_attend_i8kv_fused_p,
                                    decode_attend_i8kv_p, scatter_rows_block)
from repro.kernels.pdq_prologue import pdq_prologue_p
from repro.kernels.w8a8_matmul import (swiglu_block_rows, w8a8_matmul_p,
                                       w8a8_swiglu_matmul_p)

# stablelm-1.6b published widths
D_MODEL, N_HEADS, HEAD_DIM, D_FF = 2048, 32, 64, 5632
M = 256                      # rows of a prefill batch (slots x bucket)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


f32, i32, i8, bf16 = jnp.float32, jnp.int32, jnp.int8, jnp.bfloat16


@pytest.mark.parametrize("N,cols", [(D_MODEL, 1), (3 * N_HEADS * HEAD_DIM, 48)],
                         ids=["wo_fp_clamp", "qkv_per_nblock"])
def test_w8a8_matmul_compiles_for_v5e(one_chip, N, cols):
    def fn(x, w, sx, zx, sw, cs, so, zo, lo, hi):
        return w8a8_matmul_p(x, w, sx, zx, sw, cs, so, zo, lo, hi,
                             requant=False, fp_clamp=True,
                             per_nblock=cols > 1, out_dtype=bf16)

    _compile(fn, one_chip, ((M, D_MODEL), i8), ((D_MODEL, N), i8),
             ((M, 1), f32), ((M, 1), i32), ((1, N), f32), ((1, N), i32),
             ((M, cols), f32), ((M, cols), i32), ((M, cols), f32),
             ((M, cols), f32))


def test_w8a8_swiglu_matmul_compiles_for_v5e(one_chip):
    N = 2 * D_FF                                  # gate | up, 128-padded
    nb = N // 128
    bm = swiglu_block_rows(M, N, 128)

    def fn(x, w, sx, zx, sw, cs, lo, hi):
        return w8a8_swiglu_matmul_p(x, w, sx, zx, sw, cs, lo, hi,
                                    block=(bm, 128, 128))

    _compile(fn, one_chip, ((M, D_MODEL), i8), ((D_MODEL, N), i8),
             ((M, 1), f32), ((M, 1), i32), ((1, N), f32), ((1, N), i32),
             ((M, nb), f32), ((M, nb), f32))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "plain"])
def test_decode_attend_i8kv_compiles_for_v5e(one_chip, fused):
    B, S = 8, 2048
    kern = decode_attend_i8kv_fused_p if fused else decode_attend_i8kv_p

    def fn(q, k, v, ks, vs, ln):
        return jax.vmap(lambda *a: kern(*a, bs=256))(q, k, v, ks, vs, ln)

    _compile(fn, one_chip, ((B, N_HEADS, 1, HEAD_DIM), f32),
             ((B, N_HEADS, S, HEAD_DIM), i8), ((B, N_HEADS, S, HEAD_DIM), i8),
             ((B, N_HEADS, S), f32), ((B, N_HEADS, S), f32), ((B, 1, 1), i32))


@pytest.mark.parametrize("dtype,rows", [(bf16, 1024), (i8, 1024), (f32, 64)],
                         ids=["bf16_kv_slot_row", "int8_kv_slot_row",
                              "f32_page_row"])
def test_cache_scatter_compiles_for_v5e(one_chip, dtype, rows):
    # one slot row of a full-width KV leaf: rows x 32 heads x 64 lanes
    C = rows * N_HEADS * HEAD_DIM // 128
    assert scatter_rows_block(C, jnp.dtype(dtype).itemsize)
    _compile(lambda m, d, s: cache_scatter_p(m, d, s), one_chip,
             ((8,), i32), ((8, C, 128), dtype), ((8, C, 128), dtype))


def test_pdq_prologue_compiles_for_v5e(one_chip):
    _compile(lambda x: pdq_prologue_p(x, block=(128, 512)), one_chip,
             ((M, D_MODEL), bf16))
