"""Pallas TPU kernel: int8 x int8 -> int32 matmul with PDQ requant epilogue.

The PDQ-critical property: the output requantization scale ``s_out`` is an
*input* to the kernel (predicted by the surrogate before the matmul runs),
so the int32 MXU accumulator is collapsed to int8 inside the epilogue and
the fp32/bf16 output tile never round-trips through HBM.  A dynamic-quant
epilogue cannot do this - it needs the full output materialized to find its
range first (the paper's O(b' * h) overhead, transposed to HBM traffic).

Two epilogues share the kernel (see DESIGN.md Sec. 2): ``requant`` emits
int8 for consumers that stay integer (KV-cache writes, stacked projections);
``fp_clamp`` emits bf16/f32 clamped to the PDQ-predicted per-row interval
[lo, hi], so chained fp consumers (residual adds, norms) skip the
requant -> dequant double rounding and the int8 intermediate entirely.

Grouped execution (DESIGN.md "Grouped execution"): with ``per_nblock=True``
the epilogue operands (s_out, z_out, lo, hi) are shaped (M, N/bn) and
indexed by the N-grid coordinate, so each 128-lane output block carries its
own surrogate interval.  Sibling projections concatenated along N (each
segment padded to the block boundary) then run as ONE wide matmul off ONE
prologue while every segment keeps its own PDQ grid.

Tiling: (bm, bn, bk) = (128, 128, 128) by default - MXU-aligned; the int32
accumulator lives in VMEM scratch across the K grid dimension.

Zero-point convention: activations are affine (z_x), weights symmetric
(z_w = 0, standard practice), so

    y = s_x * s_w * (x_q @ w_q - z_x * colsum(w_q)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_col(ref, j):
    """Column ``j`` of a (bm, E) epilogue block as (bm, 1).

    Per-(row, N-block) operands arrive lane-dense - the whole (bm, N/bn)
    row block, resident across the N and K grid axes - and the N-grid
    coordinate picks its column here with a masked lane max, which returns
    the selected value exactly.  E == 1 is the per-row layout."""
    v = ref[...]
    if v.shape[1] == 1:
        return v
    hit = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) == j
    low = (jnp.finfo(v.dtype).min if jnp.issubdtype(v.dtype, jnp.floating)
           else jnp.iinfo(v.dtype).min)
    return jnp.max(jnp.where(hit, v, low), axis=1, keepdims=True)


def _kernel(x_ref, w_ref, sx_ref, zx_ref, sw_ref, colsum_ref, sout_ref, zout_ref,
            lo_ref, hi_ref, o_ref, acc_ref, *, n_k: int, requant: bool,
            fp_clamp: bool):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 operands straight into the MXU, int32 accumulation
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...] - zx_ref[...] * colsum_ref[...]          # (bm, bn)
        y = acc.astype(jnp.float32) * (sx_ref[...] * sw_ref[...])
        if requant:
            q = (jnp.round(y / _block_col(sout_ref, j))
                 + _block_col(zout_ref, j).astype(jnp.float32))
            o_ref[...] = jnp.clip(q, -128, 127).astype(jnp.int8)
        else:
            if fp_clamp:
                # PDQ fp-out epilogue: the surrogate-predicted interval is
                # applied in-register, so chained fp consumers skip the
                # int8 requant -> dequant double rounding entirely.
                y = jnp.clip(y, _block_col(lo_ref, j), _block_col(hi_ref, j))
            o_ref[...] = y.astype(o_ref.dtype)


def w8a8_matmul_p(
    x_q: jax.Array,       # (M, K) int8
    w_q: jax.Array,       # (K, N) int8
    s_x: jax.Array,       # (M, 1) f32
    z_x: jax.Array,       # (M, 1) i32
    s_w: jax.Array,       # (1, N) f32
    colsum: jax.Array,    # (1, N) i32  (precomputed at weight-deploy time)
    s_out: jax.Array,     # (M, 1) f32  (ignored unless requant)
    z_out: jax.Array,     # (M, 1) i32
    lo: jax.Array | None = None,   # (M, 1) f32  (fp_clamp only)
    hi: jax.Array | None = None,   # (M, 1) f32
    *,
    requant: bool,
    fp_clamp: bool = False,
    per_nblock: bool = False,
    block: tuple[int, int, int] = (128, 128, 128),
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Raw pallas call; all dims must already be multiples of the block.

    Epilogue modes: ``requant=True`` collapses the int32 accumulator to int8
    with (s_out, z_out); ``fp_clamp=True`` (requires requant=False) emits
    ``out_dtype`` clamped to the PDQ-predicted per-row interval [lo, hi].

    ``per_nblock=True`` makes the epilogue interval per-(row, N-block):
    s_out/z_out/lo/hi must then be shaped (M, N/bn) and are indexed by the
    N-grid coordinate, giving every 128-lane output segment its own
    surrogate grid (the grouped-projection path).
    """
    M, K = x_q.shape
    _, N = w_q.shape
    bm, bn, bk = block
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (
        f"w8a8_matmul_p requires block-multiple shapes: got x_q ({M}, {K}), "
        f"w_q ({K}, {N}) with block ({bm}, {bn}, {bk}); pad the inputs or "
        f"call repro.kernels.ops.w8a8_matmul, which pads for you")
    assert not (requant and fp_clamp), "requant and fp_clamp are exclusive"
    if lo is None:
        lo = jnp.zeros((M, 1 if not per_nblock else N // bn), jnp.float32)
    if hi is None:
        hi = jnp.zeros((M, 1 if not per_nblock else N // bn), jnp.float32)
    epi_cols = N // bn if per_nblock else 1
    for name, op in (("s_out", s_out), ("z_out", z_out), ("lo", lo), ("hi", hi)):
        assert op.shape == (M, epi_cols), (
            f"{name} must be (M, {epi_cols}) with per_nblock={per_nblock}, "
            f"got {op.shape}")
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)

    kern = functools.partial(_kernel, n_k=n_k, requant=requant,
                             fp_clamp=fp_clamp)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # w
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # s_x
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # z_x
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),    # s_w
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),    # colsum
            pl.BlockSpec((bm, epi_cols), lambda i, j, k: (i, 0)),   # s_out
            pl.BlockSpec((bm, epi_cols), lambda i, j, k: (i, 0)),   # z_out
            pl.BlockSpec((bm, epi_cols), lambda i, j, k: (i, 0)),   # lo
            pl.BlockSpec((bm, epi_cols), lambda i, j, k: (i, 0)),   # hi
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8 if requant else out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        name="w8a8_matmul",
        interpret=interpret,
    )(x_q, w_q, s_x, z_x, s_w, colsum, s_out, z_out, lo, hi)


# ---------------------------------------------------------------------------
# SwiGLU + next-prologue epilogue (the serving MLP's fused fast path)
# ---------------------------------------------------------------------------


def _swiglu_kernel(x_ref, w_ref, sx_ref, zx_ref, sw_ref, colsum_ref,
                   lo_ref, hi_ref,
                   o_ref, hswq_ref, osx_ref, os1_ref, os2_ref,
                   acc_ref, stage_ref, *, n_j: int, n_k: int, bn: int, P: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 operands straight into the MXU, int32 accumulation
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...] - zx_ref[...] * colsum_ref[...]          # (bm, bn)
        y = acc.astype(jnp.float32) * (sx_ref[...] * sw_ref[...])
        y = jnp.clip(y, _block_col(lo_ref, j), _block_col(hi_ref, j))
        o_ref[...] = y.astype(o_ref.dtype)
        # stage the clamped fp block at its N-block index: the grid is
        # row-major (j then k fastest within one i), so by (j == n_j-1,
        # k == n_k-1) the whole (bm, N) output row lives in scratch and the
        # SwiGLU pairing + next-layer prologue can run without a second
        # launch.
        stage_ref[j] = y

    @pl.when((k == n_k - 1) & (j == n_j - 1))
    def _swiglu_prologue():
        # PDQ prologue of the w_down projection (ref.pdq_prologue_ref
        # semantics on the (bm, P) rows of hsw = silu(gate) * up), in two
        # passes over bn-wide column chunks so no (bm, P) temporary is
        # live: pass 1 reduces amax/s1/s2, pass 2 quantizes with the row
        # scale.  Lane-padding columns of both segments are exactly 0
        # (zero weights, interval widened to contain 0), so reducing over
        # the padded extent equals reducing over the real d_ff columns.
        n_p = P // bn

        def hsw(c):
            return jax.nn.silu(stage_ref[c]) * stage_ref[n_p + c]   # (bm, bn)

        h = hsw(0)
        amax = jnp.max(jnp.abs(h), axis=-1, keepdims=True)
        s1 = jnp.sum(h, axis=-1, keepdims=True)
        s2 = jnp.sum(h * h, axis=-1, keepdims=True)
        for c in range(1, n_p):
            h = hsw(c)
            amax = jnp.maximum(amax, jnp.max(jnp.abs(h), axis=-1, keepdims=True))
            s1 = s1 + jnp.sum(h, axis=-1, keepdims=True)
            s2 = s2 + jnp.sum(h * h, axis=-1, keepdims=True)
        sx = jnp.maximum(amax, 1e-8) / 127.0
        osx_ref[...] = sx
        os1_ref[...] = s1
        os2_ref[...] = s2
        for c in range(n_p):
            hswq_ref[:, c * bn:(c + 1) * bn] = jnp.clip(
                jnp.round(hsw(c) / sx), -127.0, 127.0).astype(jnp.int8)


# fast memory the fused SwiGLU kernel may use: the compiler's default
# scoped VMEM limit is 16 MiB on v5e, less headroom for its own buffers
_SWIGLU_VMEM_BYTES = 12 * 1024 * 1024


def swiglu_block_rows(M: int, N: int, bn: int, bm: int = 128) -> int:
    """Row block for ``w8a8_swiglu_matmul_p``: at most ``bm``, no taller
    than M rounded up to 32 rows, and small enough that the kernel's fast
    memory - the (bm, N) f32 stage, the double-buffered (bm, N/2) int8
    prologue output and (bm, bn) f32 output tiles - stays within
    ``_SWIGLU_VMEM_BYTES``.  Halves from ``bm`` down to 32."""
    bm = min(bm, max(32, -(-M // 32) * 32))

    def need(b):
        return b * N * 4 + 2 * b * (N // 2) + 2 * b * bn * 4 + b * bn * 4

    while bm > 32 and need(bm) > _SWIGLU_VMEM_BYTES:
        bm //= 2
    assert need(bm) <= _SWIGLU_VMEM_BYTES, (
        f"w8a8_swiglu_matmul_p: N={N} needs {need(bm)} bytes of VMEM even "
        f"at bm={bm}; budget {_SWIGLU_VMEM_BYTES}")
    return bm


def w8a8_swiglu_matmul_p(
    x_q: jax.Array,       # (M, K) int8
    w_q: jax.Array,       # (K, N) int8: [gate | up], each P = N/2 columns
    s_x: jax.Array,       # (M, 1) f32
    z_x: jax.Array,       # (M, 1) i32
    s_w: jax.Array,       # (1, N) f32
    colsum: jax.Array,    # (1, N) i32
    lo: jax.Array,        # (M, N/bn) f32 per-(row, N-block) PDQ interval
    hi: jax.Array,        # (M, N/bn) f32
    *,
    block: tuple[int, int, int] = (128, 128, 128),
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> tuple[jax.Array, ...]:
    """Grouped gate/up W8A8 matmul whose epilogue ALSO computes the SwiGLU
    pairing silu(gate) * up and the next (w_down) projection's PDQ prologue.

    The epilogue stages each clamped (bm, bn) output block in a (bm, N)
    VMEM scratch; at the last (j, k) grid step of a row block the full row
    is resident, so the elementwise pairing and the one-pass prologue
    reduction run in-register - the quantized serving MLP then needs no
    standalone ``pdq_prologue_p`` launch between its two matmuls.

    Requires the two segments to occupy equal column extents P = N/2
    (gate columns [0, P), up columns [P, N) - the ``group_quantize_weights``
    layout for (w_gate, w_up)).  Returns
    (y (M, N) ``out_dtype``, hsw_q (M, P) int8, s_x, s1, s2 each (M, 1)
    f32) with (hsw_q, s_x, s1, s2) = pdq_prologue(hsw) of
    hsw = silu(y[:, :P]) * y[:, P:].  Pick ``bm`` with
    ``swiglu_block_rows`` so the stage fits fast memory.
    """
    M, K = x_q.shape
    _, N = w_q.shape
    bm, bn, bk = block
    assert N % 2 == 0, N
    P = N // 2
    assert M % bm == 0 and K % bk == 0 and N % bn == 0 and P % bn == 0, (
        f"w8a8_swiglu_matmul_p requires block-multiple shapes: got x_q "
        f"({M}, {K}), w_q ({K}, {N}) with block ({bm}, {bn}, {bk}); pad the "
        f"inputs or call repro.kernels.ops.pdq_mlp, which pads for you")
    nb = N // bn
    assert lo.shape == (M, nb) and hi.shape == (M, nb), (lo.shape, hi.shape)
    n_k = K // bk
    grid = (M // bm, nb, n_k)
    kern = functools.partial(_swiglu_kernel, n_j=nb, n_k=n_k, bn=bn, P=P)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # w
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # s_x
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # z_x
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),    # s_w
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),    # colsum
            pl.BlockSpec((bm, nb), lambda i, j, k: (i, 0)),   # lo
            pl.BlockSpec((bm, nb), lambda i, j, k: (i, 0)),   # hi
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),   # y
            pl.BlockSpec((bm, P), lambda i, j, k: (i, 0)),    # hsw_q
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # s_x out
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # s1
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # s2
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), out_dtype),
            jax.ShapeDtypeStruct((M, P), jnp.int8),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((nb, bm, bn), jnp.float32)],
        name="w8a8_swiglu_matmul",
        interpret=interpret,
    )(x_q, w_q, s_x, z_x, s_w, colsum, lo, hi)
