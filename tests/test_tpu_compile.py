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

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.kv_cache import (cache_scatter_p, decode_attend_i8kv_fused_p,
                                    decode_attend_i8kv_p, scatter_rows_block)
from repro.kernels.pdq_prologue import pdq_prologue_p
from repro.kernels.w8a8_matmul import (swiglu_block_rows, w8a8_matmul_p,
                                       w8a8_swiglu_matmul_p)
from repro.models import build_model
from repro.models.linops import quantize_param_tree
from repro.serve.engine import decode_scan

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


@pytest.mark.parametrize("fused,layers", [(True, 1), (False, 1), (True, 24),
                                          (False, 24)],
                         ids=["fused", "plain", "fused_stacked", "plain_stacked"])
def test_decode_attend_i8kv_compiles_for_v5e(one_chip, fused, layers):
    # one layer's cache; or, as the decode step runs it, a layer of the
    # whole 24-layer stack (a traced index) with the step's token written
    # into it in place by the same launch
    B, S = 8, 2048
    kern = decode_attend_i8kv_fused_p if fused else decode_attend_i8kv_p
    stacked = layers > 1

    def fn(q, k, v, ks, vs, ln, layer, slots, kn, vn, ksn, vsn):
        new = (slots, kn, vn, ksn, vsn) if stacked else None
        return kern(q, k, v, ks, vs, ln, layer, new, bs=256)

    _compile(
        fn, one_chip, ((B, N_HEADS, 1, HEAD_DIM), f32),
        ((layers, B, N_HEADS, S, HEAD_DIM), i8),
        ((layers, B, N_HEADS, S, HEAD_DIM), i8),
        ((layers, B, N_HEADS, S), f32), ((layers, B, N_HEADS, S), f32),
        ((B,), i32), ((), i32), ((B,), i32), ((B, N_HEADS, HEAD_DIM), i8),
        ((B, N_HEADS, HEAD_DIM), i8), ((B, N_HEADS), f32), ((B, N_HEADS), f32))


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


# an HLO instruction: "%name = type[dims]{layout} opcode(" at any depth
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def _large_moves(hlo: str, elems: int) -> list[str]:
    """Instructions that MATERIALISE a copy, dynamic slice or dynamic
    update of at least ``elems`` elements: such an op itself, or a fusion
    of at least that size around one.  A slice fused into a smaller
    consumer (the attention dot reading its layer) moves nothing."""
    comps: dict[str, list] = {}
    calls = []
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = comps.setdefault(head.group(1), [])
            continue
        m = _HLO_OP.match(line)
        if m is None or comp is None:
            continue
        name, dims, op = m.groups()
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        comp.append((name, op, n))
        if op == "fusion":
            calls.append((name, n, re.search(r"calls=%?([\w.\-]+)",
                                             line).group(1)))
    fused = {c for _, _, c in calls}
    found = [name for c, ops_ in comps.items() if c not in fused
             for name, op, n in ops_ if op in _MOVES and n >= elems]
    found += [name for name, size, c in calls if size >= elems and any(
        op in _MOVES and n >= elems for _, op, n in comps[c])]
    return found


@pytest.mark.parametrize("quant_kv,slots", [("none", 8), ("dynamic", 16)],
                         ids=["bf16_fp_kv", "pdq_int8_kv"])
def test_decode_dispatch_updates_kv_in_place_for_v5e(one_chip, monkeypatch,
                                                     quant_kv, slots):
    """The 4-step decode dispatch (``decode_scan`` over ``decode_step``) at
    stablelm-1.6b widths, two layers, 1024 cache rows: the layer scan
    carries the stacked K/V and writes each token in place, so no program
    copies, slices or writes back a layer of cache or more."""
    monkeypatch.setattr(ops, "_IMPL", "kernel")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    max_len = 1024
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), n_layers=2,
                              quant_kv=quant_kv).validate()
    bundle = build_model(cfg)
    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    if quant_kv != "none":                   # the PDQ-int8 serving path
        params = jax.eval_shape(quantize_param_tree, params)
    caches = jax.eval_shape(lambda: bundle.init_caches(slots, max_len))

    def greedy(rng, logits, uids, steps):
        return jnp.argmax(logits, -1), jnp.isfinite(logits).all(axis=-1)

    run = decode_scan(bundle.decode_step, greedy, 4, True)

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    rows = jax.ShapeDtypeStruct((slots,), i32, sharding=one_chip)
    col = jax.ShapeDtypeStruct((slots, 1), i32, sharding=one_chip)
    key = on(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    # the cache is donated and its layout left to the compiler, as the
    # paged program's cache, gathered inside the program, is
    auto = jax.tree.map(lambda _: Format(Layout.AUTO, one_chip), caches)
    compiled = jax.jit(
        run, donate_argnums=(2,),
        in_shardings=(one_chip, one_chip, auto) + (one_chip,) * 5,
        out_shardings=(one_chip, one_chip, auto, one_chip),
    ).lower(key, on(params), on(caches), col, col, rows, rows, rows).compile()
    hlo = compiled.as_text()
    if quant_kv != "none":
        assert "decode_attend_i8kv" in hlo
    layer_kv = slots * max_len * cfg.n_kv_heads * cfg.hd
    assert _large_moves(hlo, layer_kv) == []
