"""Pallas TPU kernel: fused input-moment reduction (paper Eqs. 8-9).

One pass over the input produces per-row s1 = sum_k x and s2 = sum_k x^2 -
the entire cost of the PDQ surrogate for a linear layer.  Fusing both sums
means the input is read from HBM exactly once; the outputs are O(M) scalars
(the paper's "2 b' bits of memory overhead", here 2 VREGs per row-block).

Sampling-stride gamma is applied by the wrapper (row subsampling) so the
kernel itself stays dense and aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s1_ref, s2_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    xb = x_ref[...].astype(jnp.float32)
    s1_ref[...] += jnp.sum(xb, axis=-1, keepdims=True)
    s2_ref[...] += jnp.sum(xb * xb, axis=-1, keepdims=True)


def act_stats_p(
    x: jax.Array,                      # (M, K)
    *,
    block: tuple[int, int] = (256, 512),
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw pallas call; M, K must be multiples of the block."""
    M, K = x.shape
    bm, bk = block
    assert M % bm == 0 and K % bk == 0, (
        f"act_stats_p requires block-multiple shapes: got x ({M}, {K}) with "
        f"block ({bm}, {bk}) - trailing rows/cols would be silently dropped "
        f"from the sums; pad the inputs or call repro.kernels.ops.act_stats, "
        f"which pads for you")
    n_k = K // bk
    grid = (M // bm, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, k: (i, k))],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        name="act_stats",
        interpret=interpret,
    )(x)
    return out[0][:, 0], out[1][:, 0]
