"""Pallas TPU kernel: elementwise affine quantize / dequantize.

Used to write int8 tensors (e.g. the KV cache) directly from bf16/f32
activations with a PDQ-predicted (per-row) or per-channel scale, without a
second range-finding pass.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, s_ref, z_ref, o_ref):
    q = jnp.round(x_ref[...].astype(jnp.float32) / s_ref[...]) + z_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.clip(q, -128, 127).astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, z_ref, o_ref):
    o_ref[...] = ((q_ref[...].astype(jnp.int32) - z_ref[...]).astype(jnp.float32)
                  * s_ref[...]).astype(o_ref.dtype)


def _scale_spec(scale_shape, bm, bn):
    if scale_shape[0] == 1:        # per-channel (1, N)
        return pl.BlockSpec((1, bn), lambda i, j: (0, j))
    return pl.BlockSpec((bm, 1), lambda i, j: (i, 0))   # per-row (M, 1)


def quantize_p(x, scale, zero_point, *, block=(256, 256), interpret=False):
    """x (M, N) float -> int8; scale/zero_point are (M,1) or (1,N)."""
    M, N = x.shape
    bm, bn = min(block[0], M), min(block[1], N)
    assert M % bm == 0 and N % bn == 0, (
        f"quantize_p requires block-multiple shapes: got x ({M}, {N}) with "
        f"block ({bm}, {bn}) - trailing rows/cols would be silently dropped; "
        f"pad the inputs or call repro.kernels.ops.quantize, which pads")
    grid = (M // bm, N // bn)
    sspec = _scale_spec(scale.shape, bm, bn)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)), sspec, sspec],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8),
        name="quantize",
        interpret=interpret,
    )(x, scale, zero_point)


def dequantize_p(q, scale, zero_point, *, out_dtype=jnp.float32, block=(256, 256),
                 interpret=False):
    M, N = q.shape
    bm, bn = min(block[0], M), min(block[1], N)
    assert M % bm == 0 and N % bn == 0, (
        f"dequantize_p requires block-multiple shapes: got q ({M}, {N}) with "
        f"block ({bm}, {bn}) - trailing rows/cols would be silently dropped; "
        f"pad the inputs or call repro.kernels.ops.dequantize, which pads")
    grid = (M // bm, N // bn)
    sspec = _scale_spec(scale.shape, bm, bn)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)), sspec, sspec],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        name="dequantize",
        interpret=interpret,
    )(q, scale, zero_point)
