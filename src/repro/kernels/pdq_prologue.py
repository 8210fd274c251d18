"""Pallas TPU kernel: fused PDQ prologue for the int8 serving path.

ONE read of the activation tile from HBM produces everything the W8A8
matmul needs *before* it runs:

  * ``x_q``  - per-row symmetric int8 quantization of x,
  * ``s_x``  - the per-row scale (amax / 127),
  * ``s1``   - per-row sum x   (paper Eq. 8 surrogate input),
  * ``s2``   - per-row sum x^2 (paper Eq. 9 surrogate input).

The unfused path reads x three times (amax pass, quantize pass, act_stats
pass); this kernel stages a (bm, K) row block in VMEM and performs a
two-stage amax reduction over k-chunks - stage 1 accumulates per-chunk
partial amax/s1/s2, stage 2 revisits the staged chunks to quantize with
the now-known row scale - so HBM traffic is exactly one read of x plus
one int8 write of x_q and O(M) scalars.

Grid: (M // bm,); the full K extent of a row block lives in VMEM (the
wrapper in ``ops.py`` shrinks bm for very large K to stay within VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, xq_ref, sx_ref, s1_ref, s2_ref, *, n_k: int, bk: int):
    # Stage 1: per-chunk partial reductions over the staged row block.
    xb = x_ref[:, 0:bk].astype(jnp.float32)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    s1 = jnp.sum(xb, axis=-1, keepdims=True)
    s2 = jnp.sum(xb * xb, axis=-1, keepdims=True)
    for k in range(1, n_k):
        xb = x_ref[:, k * bk:(k + 1) * bk].astype(jnp.float32)
        amax = jnp.maximum(amax, jnp.max(jnp.abs(xb), axis=-1, keepdims=True))
        s1 = s1 + jnp.sum(xb, axis=-1, keepdims=True)
        s2 = s2 + jnp.sum(xb * xb, axis=-1, keepdims=True)

    amax = jnp.maximum(amax, 1e-8)
    scale = amax / 127.0
    sx_ref[...] = scale
    s1_ref[...] = s1
    s2_ref[...] = s2

    # Stage 2: quantize the (still-VMEM-resident) chunks with the row scale.
    r = 1.0 / scale
    for k in range(n_k):
        xb = x_ref[:, k * bk:(k + 1) * bk].astype(jnp.float32)
        xq_ref[:, k * bk:(k + 1) * bk] = jnp.clip(
            jnp.round(xb * r), -127.0, 127.0).astype(jnp.int8)


def pdq_prologue_p(
    x: jax.Array,                      # (M, K) float
    *,
    block: tuple[int, int] = (128, 512),
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Raw pallas call; returns (x_q (M,K) i8, s_x, s1, s2 each (M,1) f32).

    M and K must already be multiples of the block (the ``ops.pdq_prologue``
    wrapper pads).
    """
    M, K = x.shape
    bm, bk = block
    assert M % bm == 0 and K % bk == 0, (
        f"pdq_prologue_p requires block-multiple shapes: got x ({M}, {K}) "
        f"with block ({bm}, {bk}); pad the inputs or call "
        f"repro.kernels.ops.pdq_prologue, which pads for you")
    grid = (M // bm,)
    kern = functools.partial(_kernel, n_k=K // bk, bk=bk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, K), jnp.int8),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        name="pdq_prologue",
        interpret=interpret,
    )(x)
