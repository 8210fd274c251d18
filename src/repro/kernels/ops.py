"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy (``set_impl``):
  'auto'   - real Pallas kernel on TPU, jnp reference on other backends
             (interpret-mode Pallas is a correctness tool, not a fast path).
  'kernel' - force the Pallas kernel (interpret=True off-TPU). Used by tests.
  'ref'    - force the pure-jnp oracle.

All wrappers accept arbitrary leading batch dims and handle padding to the
kernel's block multiples.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .act_stats import act_stats_p
from .kv_cache import (cache_scatter_p, cache_scatter_pages_p,
                       decode_attend_i8kv_fused_p, decode_attend_i8kv_p,
                       scatter_rows_block)
from .pdq_prologue import pdq_prologue_p
from .quantize import dequantize_p, quantize_p
from .w8a8_matmul import (swiglu_block_rows, w8a8_matmul_p,
                          w8a8_swiglu_matmul_p)

_IMPL = "auto"


def set_impl(impl: str) -> None:
    global _IMPL
    assert impl in ("auto", "kernel", "ref")
    _IMPL = impl


def _use_kernel() -> bool:
    if _IMPL == "ref":
        return False
    if _IMPL == "kernel":
        return True
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Tensor parallelism (serving): column-split + all-gather epilogue
# ---------------------------------------------------------------------------
#
# Inside a shard_map body the sharded serving engine activates ``tp_shard``:
# every PDQ / fp projection then computes only its device's N-columns and
# all-gathers the result, so the matmul FLOPs (and on TPU the weight
# streaming) split over the mesh axis while the numerics stay bit-exact -
# each output column runs the identical full-K reduction and the identical
# per-row epilogue it runs on one device, and the tiled all-gather merely
# concatenates the column blocks in axis order.  The PDQ prologue is
# intentionally NOT split: its (x_q, s_x, s1, s2) depend on the whole input
# row, are O(K) to compute, and every shard needs them - recomputing
# locally is cheaper than a broadcast.  For the same reason the fused
# SwiGLU gate/up kernel (``pdq_mlp``) runs whole on every shard: the
# w_down prologue it emits needs the full silu(g)*u row.

_TP: tuple[str, int] | None = None     # (mesh axis name, axis size)


@contextlib.contextmanager
def tp_shard(axis_name: str, size: int):
    """Enable N-column tensor parallelism over ``axis_name`` while tracing
    (valid only inside a shard_map body that binds the axis).  size == 1 is
    a no-op."""
    global _TP
    prev = _TP
    _TP = (axis_name, int(size)) if int(size) > 1 else None
    try:
        yield
    finally:
        _TP = prev


def tp_ctx() -> tuple[str, int] | None:
    return _TP


def _tp_cols(a, n_local: int, idx, axis: int):
    return jax.lax.dynamic_slice_in_dim(a, idx * n_local, n_local, axis)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Guarded PDQ fallback (fault tolerance)
# ---------------------------------------------------------------------------
#
# A corrupted int8 epilogue (bad surrogate interval, overflowed requant
# grid, a flipped bit in the weight record) shows up as NaN/Inf in the
# projection output.  With ``pdq_guard`` active while tracing, every PDQ
# fp-out projection checks its result device-side and - per projection,
# per launch - falls back to the plain fp-dequant matmul
# ``x @ (q * scale)`` when any element is non-finite.  The fallback branch
# is pure jnp (no pallas_call), so guarded programs keep the exact kernel
# census of unguarded ones; the finite check is one fused reduction per
# projection.  Engines opt in with ``pdq_fallback=True``.

_PDQ_GUARD = False
_PDQ_FAULT = False      # test hook: corrupt every fast-path result
_PDQ_TEL: "PdqTelemetryCollector | None" = None


@contextlib.contextmanager
def pdq_guard(enable: bool = True):
    """Enable the per-projection PDQ->fp-dequant fallback while tracing."""
    global _PDQ_GUARD
    prev = _PDQ_GUARD
    _PDQ_GUARD = bool(enable)
    try:
        yield
    finally:
        _PDQ_GUARD = prev


class PdqTelemetryCollector:
    """Trace-time accumulator for quantization-health scalars.

    While ``pdq_telemetry`` is active, every PDQ projection appends jnp
    SCALARS here as it traces: the guard's fallback-activation flag (the
    same fused finiteness reduction the guard's ``cond`` already
    computes), int8 clip-saturation hit counts and the elements checked.
    ``summary()`` folds them into ONE (3,) float32 the launch returns
    alongside its tokens - the host reads it in the existing token
    gather, so quantization health costs zero extra round-trips and adds
    no pallas_calls (pure jnp reductions; the kernel census is pinned
    unchanged)."""

    def __init__(self):
        self.fallbacks: list = []
        self.clip_hits: list = []
        self.clip_total: list = []

    def summary(self):
        def tot(xs):
            acc = jnp.float32(0.0)
            for x in xs:
                acc = acc + x
            return acc

        return jnp.stack([tot(self.fallbacks), tot(self.clip_hits),
                          tot(self.clip_total)])


# the summary layout engines unpack: [fallbacks, clip_hits, clip_total]
PDQ_TEL_WIDTH = 3


@contextlib.contextmanager
def pdq_telemetry(enable: bool = True):
    """Collect PDQ health scalars from every projection traced inside
    (nests with ``pdq_guard``/``tp_shard``).  ``enable=False`` yields a
    collector whose summary is zeros - launch signatures stay uniform."""
    global _PDQ_TEL
    col = PdqTelemetryCollector()
    prev = _PDQ_TEL
    _PDQ_TEL = col if enable else None
    try:
        yield col
    finally:
        _PDQ_TEL = prev


def pdq_telemetry_scan(body, init, xs):
    """``lax.scan(body, init, xs)`` for a body that traces PDQ projections
    (the model's layer-stacked blocks).  Collector scalars traced inside a
    scan body must not escape it: each iteration collects into its own
    collector, returns the (3,) summary as a scan output, and the
    layer-summed summary is recorded into the enclosing collector."""
    outer = _PDQ_TEL

    def wrapped(carry, x):
        with pdq_telemetry(outer is not None) as col:
            carry, y = body(carry, x)
            return carry, (y, col.summary())

    carry, (ys, tel) = jax.lax.scan(wrapped, init, xs)
    if outer is not None:
        tel = jnp.sum(tel, axis=0)
        outer.fallbacks.append(tel[0])
        outer.clip_hits.append(tel[1])
        outer.clip_total.append(tel[2])
    return carry, ys


def _tel_clip(y, lo, hi):
    """Record clip saturation of a clamped fp output: elements sitting on
    either interval edge were clipped by the epilogue (or landed exactly
    on the representable boundary, which the rate treats the same)."""
    if _PDQ_TEL is None:
        return
    hits = jnp.sum(((y <= lo) | (y >= hi)).astype(jnp.float32))
    _PDQ_TEL.clip_hits.append(hits)
    _PDQ_TEL.clip_total.append(jnp.float32(y.size))


def _tel_clip_q(y_q):
    """Int8-out flavor: saturation is the grid's edge codes."""
    if _PDQ_TEL is None:
        return
    hits = jnp.sum(((y_q == 127) | (y_q == -128)).astype(jnp.float32))
    _PDQ_TEL.clip_hits.append(hits)
    _PDQ_TEL.clip_total.append(jnp.float32(y_q.size))


@contextlib.contextmanager
def pdq_fault():
    """Test-only: poison every guarded fast-path output with NaN while
    tracing, so the fallback branch is forced to carry the computation."""
    global _PDQ_FAULT
    prev = _PDQ_FAULT
    _PDQ_FAULT = True
    try:
        yield
    finally:
        _PDQ_FAULT = prev


def _fp_dequant_matmul(x, w_q, scale, out_dtype):
    """The always-available fallback precision: dequantize the int8 weight
    and run the projection in fp32.  No PDQ prologue, no requant grid - the
    only state it shares with the fast path is the weight record itself."""
    w = w_q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32).reshape(1, -1)
    return (x.astype(jnp.float32) @ w).astype(out_dtype)


def _guard_pdq(y, x, w_q, scale, out_dtype):
    """y if finite else the fp-dequant fallback (no-op unless pdq_guard)."""
    if not _PDQ_GUARD:
        return y
    if _PDQ_FAULT:
        y = y + jnp.float32(jnp.nan).astype(y.dtype)
    ok = jnp.isfinite(y).all()
    if _PDQ_TEL is not None:
        # the fallback-activation count rides the SAME fused reduction the
        # cond consumes: telemetry reuses it, costing nothing extra
        _PDQ_TEL.fallbacks.append(1.0 - ok.astype(jnp.float32))
    return jax.lax.cond(ok,
                        lambda: y,
                        lambda: _fp_dequant_matmul(x, w_q, scale, out_dtype))


def _pad_to(a: jax.Array, axis: int, mult: int, value=0):
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _norm_row(a, M, dtype):
    """Broadcast a scalar / (M,) / (M,1) quantity to (M, 1)."""
    a = jnp.asarray(a, dtype)
    if a.ndim == 0:
        a = jnp.full((M, 1), a)
    return a.reshape(M, 1)


# ---------------------------------------------------------------------------


def w8a8_matmul(x_q, w_q, s_x, z_x, s_w, s_out=None, z_out=None, *,
                colsum=None, fp_range=None, out_dtype=jnp.float32,
                block=(128, 128, 128)):
    """y = s_x*s_w*(x_q @ w_q - z_x*colsum); requantized int8 iff s_out given.

    x_q: (..., K) int8; w_q: (K, N) int8. s_x/z_x: scalar, (...) or
    (..., 1) per-row; s_w: scalar or (N,) per-channel.

    ``fp_range=(lo, hi)`` (exclusive with s_out) applies the PDQ interval
    clamp inside the epilogue and emits ``out_dtype`` directly.

    Epilogue operands (s_out/z_out/lo/hi) accept two layouts: per-row
    (scalar, (...) or (..., 1)) or per-(row, N-block) - shaped
    (..., N // bn) with bn the N block - which gives each 128-lane output
    segment of a grouped matmul its own surrogate grid (requires N to be a
    multiple of bn; see ``pdq_dense_grouped``).
    """
    lead = x_q.shape[:-1]
    K = x_q.shape[-1]
    N = w_q.shape[-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x_q.reshape(M, K)
    s_w2 = jnp.asarray(s_w, jnp.float32)
    s_w2 = jnp.broadcast_to(s_w2.reshape(1, -1) if s_w2.ndim else s_w2, (1, N)).reshape(1, N)
    requant = s_out is not None
    fp_clamp = fp_range is not None
    assert not (requant and fp_clamp), "fp_range and s_out are exclusive"
    bm, bn, bk = block

    def _is_per_block(a):
        a = jnp.asarray(a)
        return a.ndim == len(lead) + 1 and a.shape[-1] > 1

    epi_in = (s_out if requant else 1.0, z_out if requant else 0,
              fp_range[0] if fp_clamp else 0.0, fp_range[1] if fp_clamp else 0.0)
    per_nblock = any(_is_per_block(a) for a in epi_in)
    if per_nblock:
        assert N % bn == 0, (
            f"per-(row, N-block) epilogue operands require N ({N}) to be a "
            f"multiple of the N block ({bn})")
        nb = N // bn

        def _norm_epi(a, dtype):
            a = jnp.asarray(a, dtype)
            if a.ndim == 0:
                return jnp.full((M, nb), a)
            a = a.reshape(M, -1)
            assert a.shape[1] in (1, nb), (
                f"epilogue operand has {a.shape[1]} columns; expected 1 "
                f"(per-row) or {nb} (per-N-block)")
            return jnp.broadcast_to(a, (M, nb))
    else:
        def _norm_epi(a, dtype):
            return _norm_row(a, M, dtype)

    sx = _norm_row(s_x, M, jnp.float32)
    zx = _norm_row(z_x, M, jnp.int32)
    so = _norm_epi(epi_in[0], jnp.float32)
    zo = _norm_epi(epi_in[1], jnp.int32)
    lo = _norm_epi(epi_in[2], jnp.float32)
    hi = _norm_epi(epi_in[3], jnp.float32)

    if not _use_kernel():
        if per_nblock:
            # expand per-block columns to per-channel (each block spans bn
            # lanes) so the jnp oracle broadcasts them exactly.
            so, zo, lo, hi = (jnp.repeat(a, bn, axis=-1) for a in (so, zo, lo, hi))
        y = ref.w8a8_matmul_ref(x2, w_q, sx, zx, s_w2,
                                so if requant else None, zo if requant else None)
        if fp_clamp:
            y = jnp.clip(y, lo, hi)
        if not requant:
            y = y.astype(out_dtype)
        return y.reshape(*lead, N)

    if colsum is None:
        colsum = jnp.sum(w_q.astype(jnp.int32), axis=0, keepdims=True)
    colsum = colsum.reshape(1, N)
    xp = _pad_to(_pad_to(x2, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w_q, 0, bk), 1, bn)
    pads = dict(axis=0, mult=bm)
    y = w8a8_matmul_p(
        xp, wp,
        _pad_to(sx, **pads, value=1.0), _pad_to(zx, **pads),
        _pad_to(s_w2, 1, bn, value=1.0), _pad_to(colsum, 1, bn),
        _pad_to(so, **pads, value=1.0), _pad_to(zo, **pads),
        _pad_to(lo, **pads), _pad_to(hi, **pads),
        requant=requant, fp_clamp=fp_clamp, per_nblock=per_nblock,
        out_dtype=out_dtype, block=block, interpret=_interpret(),
    )
    return y[:M, :N].reshape(*lead, N)


def pdq_prologue(x, *, block=(128, 512)):
    """Fused serving-path prologue: ONE pass over x (..., K) emits
    (x_q int8 like x, s_x, s1, s2 each shaped (..., 1)).

    Replaces the separate amax / quantize / act_stats passes of the unfused
    path; see kernels/pdq_prologue.py for the dataflow.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)
    if not _use_kernel():
        x_q, s_x, s1, s2 = ref.pdq_prologue_ref(x2)
    else:
        bm, bk = block
        bk = min(bk, max(K, 1))
        Kp = K + (-K) % bk
        # the kernel stages a full (bm, Kp) row block in VMEM: shrink bm
        # for very long rows so the f32 staging stays well under VMEM.
        while bm > 8 and bm * Kp * 4 > 8 * 1024 * 1024:
            bm //= 2
        xp = _pad_to(_pad_to(x2, 1, bk), 0, bm)
        x_q, s_x, s1, s2 = pdq_prologue_p(xp, block=(bm, bk),
                                          interpret=_interpret())
        x_q = x_q[:M, :K]
        s_x, s1, s2 = s_x[:M], s1[:M], s2[:M]
    return (x_q.reshape(*lead, K), s_x.reshape(*lead, 1),
            s1.reshape(*lead, 1), s2.reshape(*lead, 1))


def pdq_interval(wrec, s1, s2):
    """PDQ surrogate interval from the prologue sums (paper Eqs. 8-9 + I(a,b)).

    s1/s2: (..., 1).  Returns (lo, hi, s_out, z_out) per row, where [lo, hi]
    is widened to contain 0 and (s_out, z_out) is the affine int8 grid over
    it.  O(M) scalar math - negligible next to the matmul.

    Grouped records carry (n_seg,) weight stats; the same expression then
    broadcasts (..., 1) x (n_seg,) -> (..., n_seg), pricing every segment's
    interval from the ONE shared (s1, s2) pair - the sharing is exact, not
    approximate, because the moments depend only on the input row.
    """
    mean = wrec["mu_w"] * s1
    sigma = jnp.sqrt(jnp.maximum(wrec["var_w"] * s2, 0.0)) + 1e-8
    lo = jnp.minimum(mean - wrec["alpha"] * sigma, 0.0)
    hi = jnp.maximum(mean + wrec["beta"] * sigma, 0.0)
    s_out = jnp.maximum((hi - lo) / 255.0, 1e-8)
    z_out = -jnp.round(lo / s_out) - 128.0
    return lo, hi, s_out, z_out


def pdq_dense(x, wrec, *, out="fp", out_dtype=None, block=(128, 128, 128),
              prologue_block=(128, 512)):
    """The fused PDQ serving-path dense layer: one prologue + one matmul.

    ``wrec`` is a weight record from ``models.linops.quantize_weight``:
    {'q' (K, N) int8, 'scale' (N,) f32, 'colsum' (1, N) i32,
     'mu_w', 'var_w', 'alpha', 'beta' scalars}.

    out='fp'  : returns y (..., N) in ``out_dtype`` (default f32); the PDQ
                interval is applied as a clamp inside the matmul epilogue,
                matching the requant->dequant path to one int8 step without
                materializing the int8 intermediate.
    out='int8': returns (y_q (..., N) int8, s_out (..., 1) f32,
                z_out (..., 1) i32) for consumers that stay integer.
    """
    assert out in ("fp", "int8"), out
    if out_dtype is None:
        out_dtype = jnp.float32
    x_q, s_x, s1, s2 = pdq_prologue(x, block=prologue_block)
    lo, hi, s_out, z_out = pdq_interval(wrec, s1, s2)
    if out == "int8":
        y_q = w8a8_matmul(x_q, wrec["q"], s_x, 0, wrec["scale"],
                          s_out, z_out.astype(jnp.int32),
                          colsum=wrec["colsum"], block=block)
        _tel_clip_q(y_q)
        return y_q, s_out, z_out.astype(jnp.int32)
    return pdq_dense_from_prologue(x, x_q, s_x, s1, s2, wrec,
                                   out_dtype=out_dtype, block=block)


def pdq_dense_from_prologue(x, x_q, s_x, s1, s2, wrec, *, out_dtype=None,
                            block=(128, 128, 128)):
    """``pdq_dense(out='fp')`` with the prologue already computed upstream.

    The serving decode path fuses the wo projection's prologue into the
    flash-decode attend kernel's output stage (``decode_attend_i8kv`` with
    ``wo_prologue=True``); this entry consumes those (x_q, s_x, s1, s2)
    directly, so the projection costs ONE pallas_call instead of two.  The
    fp ``x`` is read only by the guarded fallback, which recomputes from it
    (``pdq_mlp``'s fused path passes None: it never runs guarded).
    Numerics are identical to ``pdq_dense`` by construction (it is the
    same tail).
    """
    if out_dtype is None:
        out_dtype = jnp.float32
    lo, hi, s_out, z_out = pdq_interval(wrec, s1, s2)
    # clamp to the representable extent of the int8 grid rather than the raw
    # interval, so fp-out matches requant->dequant at the clip boundaries.
    lo_g = (-128.0 - z_out) * s_out
    hi_g = (127.0 - z_out) * s_out
    N = wrec["q"].shape[1]
    if _TP is not None and N % _TP[1] == 0:
        # column-TP: this shard's N-slice only (the interval is per-row, so
        # the epilogue operands need no slicing), then all-gather columns.
        ax, T = _TP
        idx = jax.lax.axis_index(ax)
        Nl = N // T
        wq_l = _tp_cols(wrec["q"], Nl, idx, 1)
        sc_l = _tp_cols(wrec["scale"], Nl, idx, 0)
        y = w8a8_matmul(x_q, wq_l, s_x, 0, sc_l,
                        colsum=_tp_cols(wrec["colsum"], Nl, idx, 1),
                        fp_range=(lo_g, hi_g), out_dtype=out_dtype, block=block)
        # telemetry counts this shard's columns; the engine psums the
        # collector summary over the mesh to recover fleet-wide counts
        _tel_clip(y, lo_g, hi_g)
        # guard BEFORE the all-gather: each shard checks and (if needed)
        # recomputes only its own columns, so one corrupted shard cannot
        # spread non-finite values through the gathered concatenation.
        y = _guard_pdq(y, x, wq_l, sc_l, out_dtype)
        return jax.lax.all_gather(y, ax, axis=y.ndim - 1, tiled=True)
    y = w8a8_matmul(x_q, wrec["q"], s_x, 0, wrec["scale"],
                    colsum=wrec["colsum"], fp_range=(lo_g, hi_g),
                    out_dtype=out_dtype, block=block)
    _tel_clip(y, lo_g, hi_g)
    return _guard_pdq(y, x, wrec["q"], wrec["scale"], out_dtype)


def pdq_dense_grouped(x, grec, *, out="fp", out_dtype=None,
                      block=(128, 128, 128), prologue_block=(128, 512)):
    """Grouped PDQ dense: ONE prologue + ONE wide W8A8 matmul for every
    projection consuming the same input (DESIGN.md "Grouped execution").

    ``grec`` is a record from ``models.linops.group_quantize_weights``:
    sibling weights concatenated along N (each segment padded to the
    128-lane boundary) with per-segment (n_seg,) surrogate stats and a
    static ``segs`` layout.  The prologue's (x_q, s_x, s1, s2) serve every
    segment; ``pdq_interval`` broadcasts to per-(row, segment) grids, which
    the matmul applies per N-block in its epilogue.

    out='fp'  : returns a tuple of per-segment outputs (..., N_i) in
                ``out_dtype`` (default f32).
    out='int8': returns (tuple of per-segment int8 outputs,
                s_out (..., n_seg) f32, z_out (..., n_seg) i32).
    """
    assert out in ("fp", "int8"), out
    if out_dtype is None:
        out_dtype = jnp.float32
    segs = grec["segs"]
    bm, bn, bk = block
    assert all(p % bn == 0 for p in segs.padded), (
        f"grouped segments are padded to 128 lanes; the N block ({bn}) must "
        f"divide every padded extent {segs.padded}")
    reps = np.array([p // bn for p in segs.padded])
    nb = int(reps.sum())
    x_q, s_x, s1, s2 = pdq_prologue(x, block=prologue_block)
    lo, hi, s_out, z_out = pdq_interval(grec, s1, s2)      # (..., n_seg)

    def blockwise(a):
        # per-segment -> per-N-block: segment i spans padded[i]/bn blocks
        return jnp.repeat(a, reps, axis=-1, total_repeat_length=nb)

    bounds = zip(segs.offsets, segs.sizes)
    if out == "int8":
        y_q = w8a8_matmul(x_q, grec["q"], s_x, 0, grec["scale"],
                          blockwise(s_out), blockwise(z_out).astype(jnp.int32),
                          colsum=grec["colsum"], block=block)
        _tel_clip_q(y_q)
        ys = tuple(y_q[..., o:o + n] for o, n in bounds)
        return ys, s_out, z_out.astype(jnp.int32)
    lo_g = (-128.0 - z_out) * s_out
    hi_g = (127.0 - z_out) * s_out
    if _TP is not None and nb % _TP[1] == 0:
        # the N-segments (and their per-(row, N-block) epilogue grids) split
        # along the TP axis in whole 128-lane blocks; the tiled all-gather
        # reassembles the full concatenation before the segment split.
        ax, T = _TP
        idx = jax.lax.axis_index(ax)
        nb_l, Nl = nb // T, segs.total // T
        lo_b, hi_b = blockwise(lo_g), blockwise(hi_g)
        wq_l = _tp_cols(grec["q"], Nl, idx, 1)
        sc_l = _tp_cols(grec["scale"], Nl, idx, 0)
        lo_l = _tp_cols(lo_b, nb_l, idx, lo_b.ndim - 1)
        hi_l = _tp_cols(hi_b, nb_l, idx, hi_b.ndim - 1)
        y = w8a8_matmul(x_q, wq_l, s_x, 0, sc_l,
                        colsum=_tp_cols(grec["colsum"], Nl, idx, 1),
                        fp_range=(lo_l, hi_l),
                        out_dtype=out_dtype, block=block)
        if _PDQ_TEL is not None:
            _tel_clip(y, jnp.repeat(lo_l, bn, axis=-1),
                      jnp.repeat(hi_l, bn, axis=-1))
        y = _guard_pdq(y, x, wq_l, sc_l, out_dtype)
        y = jax.lax.all_gather(y, ax, axis=y.ndim - 1, tiled=True)
        return tuple(y[..., o:o + n] for o, n in bounds)
    y = w8a8_matmul(x_q, grec["q"], s_x, 0, grec["scale"],
                    colsum=grec["colsum"],
                    fp_range=(blockwise(lo_g), blockwise(hi_g)),
                    out_dtype=out_dtype, block=block)
    if _PDQ_TEL is not None:
        _tel_clip(y, jnp.repeat(blockwise(lo_g), bn, axis=-1),
                  jnp.repeat(blockwise(hi_g), bn, axis=-1))
    y = _guard_pdq(y, x, grec["q"], grec["scale"], out_dtype)
    return tuple(y[..., o:o + n] for o, n in bounds)


def pdq_mlp(x, grec, down_rec, *, out_dtype=None, block=(128, 128, 128),
            prologue_block=(128, 512)):
    """Fused quantized SwiGLU MLP: gate/up grouped matmul -> silu(g)*u ->
    w_down, in THREE pallas_calls instead of four.

    The saving comes from ``w8a8_swiglu_matmul_p``: the grouped gate/up
    matmul's epilogue stages the full clamped output row in VMEM, computes
    the SwiGLU pairing in-register, and emits the w_down projection's PDQ
    prologue (hsw_q, s_x, s1, s2) alongside - so no standalone
    ``pdq_prologue_p`` launch runs between the two matmuls (DESIGN.md
    "Decode fast path").

    Under tensor parallelism every shard runs the whole fused gate/up
    kernel (w_down's prologue needs the full silu(g)*u row, which a
    column-split shard does not hold) and only w_down splits its columns,
    so the sharded MLP computes exactly what one device computes.

    Falls back to the exact unfused composition (``pdq_dense_grouped`` +
    jnp silu + ``pdq_dense``) whenever the fused epilogue cannot apply:
    ref/auto-off-TPU mode (bit-identical numerics preserved), an active
    ``pdq_guard`` (the fallback branch needs the guarded gate/up output),
    or a group layout that is not two equal lane-padded segments.
    """
    if out_dtype is None:
        out_dtype = jnp.float32
    segs = grec["segs"]
    bm, bn, bk = block
    fused = (_use_kernel() and not _PDQ_GUARD
             and len(segs.sizes) == 2 and segs.padded[0] == segs.padded[1]
             and segs.padded[0] % bn == 0)
    if not fused:
        g, u = pdq_dense_grouped(x, grec, out="fp", out_dtype=out_dtype,
                                 block=block, prologue_block=prologue_block)
        h = jax.nn.silu(g) * u
        return pdq_dense(h, down_rec, out="fp", out_dtype=out_dtype,
                         block=block, prologue_block=prologue_block)

    lead = x.shape[:-1]
    K = x.shape[-1]
    M = 1
    for d in lead:
        M *= d
    Nt = segs.total
    reps = np.array([p // bn for p in segs.padded])
    nb = int(reps.sum())

    x_q, s_x, s1, s2 = pdq_prologue(x, block=prologue_block)
    lo, hi, s_out, z_out = pdq_interval(grec, s1, s2)           # (..., 2)
    lo_g = (-128.0 - z_out) * s_out
    hi_g = (127.0 - z_out) * s_out

    def blockwise(a):
        return jnp.repeat(a, reps, axis=-1, total_repeat_length=nb)

    # the staging scratch holds a full (bm, Nt) f32 row block: the row
    # block comes from the shape so the stage fits fast memory
    bm = swiglu_block_rows(M, Nt, bn, bm)
    pads = dict(axis=0, mult=bm)
    lo_b = blockwise(lo_g).reshape(M, nb)
    hi_b = blockwise(hi_g).reshape(M, nb)
    y, hsw_q, sxo, s1o, s2o = w8a8_swiglu_matmul_p(
        _pad_to(_pad_to(x_q.reshape(M, K), 0, bm), 1, bk),
        _pad_to(grec["q"], 0, bk),
        _pad_to(_norm_row(s_x, M, jnp.float32), **pads, value=1.0),
        _pad_to(_norm_row(0, M, jnp.int32), **pads),
        grec["scale"].reshape(1, Nt), grec["colsum"].reshape(1, Nt),
        _pad_to(lo_b, **pads), _pad_to(hi_b, **pads),
        block=(bm, bn, bk), interpret=_interpret(), out_dtype=jnp.float32)
    tel = (y[:M], jnp.repeat(lo_b, bn, axis=-1), jnp.repeat(hi_b, bn, axis=-1))
    if _TP is not None and Nt % _TP[1] == 0:
        # every shard ran the whole gate/up matmul: each counts only its
        # own column slice, as a column-split projection does
        idx = jax.lax.axis_index(_TP[0])
        tel = tuple(_tp_cols(a, Nt // _TP[1], idx, 1) for a in tel)
    _tel_clip(*tel)

    dff = down_rec["q"].shape[0]
    return pdq_dense_from_prologue(
        None, hsw_q[:M, :dff].reshape(*lead, dff), sxo[:M].reshape(*lead, 1),
        s1o[:M].reshape(*lead, 1), s2o[:M].reshape(*lead, 1), down_rec,
        out_dtype=out_dtype, block=block)


def pdq_dense_unfused(x, wrec):
    """The pre-fusion serving path, kept as the oracle/baseline: 3 reads of
    x (amax / quantize / act_stats) + requant matmul + jnp dequant.

    ``pdq_dense(out='fp')`` must match this to within one int8 step of the
    predicted grid (tests/test_kernels.py); benchmarks/bench_pdq_dense.py
    times the two against each other.  Returns (y fp32, s_out per-row).
    """
    x32 = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1), 1e-8)
    s_x = amax / 127.0
    x_q = jnp.clip(jnp.round(x32 / s_x[..., None]), -127, 127).astype(jnp.int8)
    s1, s2 = act_stats(x32)
    lo, hi, s_out, z_out = pdq_interval(wrec, s1[..., None], s2[..., None])
    z_out = z_out.astype(jnp.int32)
    y_q = w8a8_matmul(x_q, wrec["q"], s_x[..., None], 0, wrec["scale"],
                      s_out, z_out, colsum=wrec["colsum"])
    y = (y_q.astype(jnp.float32) - z_out.astype(jnp.float32)) * s_out
    return y, s_out


def act_stats(x, gamma: int = 1, *, block=(256, 512)):
    """Fused (sum x, sum x^2) over the last axis; gamma subsamples the
    second-to-last ("position") axis.  Returns arrays shaped like x[..., 0]."""
    if x.ndim > 2 and gamma > 1:
        x = x[..., ::gamma, :]
    lead = x.shape[:-1]
    K = x.shape[-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)
    if not _use_kernel():
        s1, s2 = ref.act_stats_ref(x2)
        return s1.reshape(lead), s2.reshape(lead)
    bm, bk = block
    xp = _pad_to(_pad_to(x2, 0, bm), 1, bk)
    s1, s2 = act_stats_p(xp, block=(bm, bk), interpret=_interpret())
    return s1[:M].reshape(lead), s2[:M].reshape(lead)


def quantize(x, scale, zero_point, *, per_channel: bool = False):
    """Affine int8 quantize. scale/zp: per-row (broadcast over last axis) by
    default, or per-channel (last axis) with per_channel=True."""
    lead = x.shape[:-1]
    N = x.shape[-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, N)
    if per_channel:
        s = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(1, -1), (1, N))
        z = jnp.broadcast_to(jnp.asarray(zero_point, jnp.int32).reshape(1, -1), (1, N))
    else:
        s = _norm_row(scale, M, jnp.float32)
        z = _norm_row(zero_point, M, jnp.int32)
    if not _use_kernel():
        return ref.quantize_ref(x2, s, z).reshape(*lead, N)
    xp = _pad_to(_pad_to(x2, 0, 256), 1, 256)
    sp = _pad_to(s, 1, 256, value=1.0) if per_channel else _pad_to(s, 0, 256, value=1.0)
    zp = _pad_to(z, 1, 256) if per_channel else _pad_to(z, 0, 256)
    q = quantize_p(xp, sp, zp, interpret=_interpret())
    return q[:M, :N].reshape(*lead, N)


def dequantize(q, scale, zero_point, *, per_channel: bool = False, out_dtype=jnp.float32):
    lead = q.shape[:-1]
    N = q.shape[-1]
    M = 1
    for d in lead:
        M *= d
    q2 = q.reshape(M, N)
    if per_channel:
        s = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(1, -1), (1, N))
        z = jnp.broadcast_to(jnp.asarray(zero_point, jnp.int32).reshape(1, -1), (1, N))
    else:
        s = _norm_row(scale, M, jnp.float32)
        z = _norm_row(zero_point, M, jnp.int32)
    if not _use_kernel():
        return ref.dequantize_ref(q2, s, z, out_dtype).reshape(*lead, N)
    qp_ = _pad_to(_pad_to(q2, 0, 256), 1, 256)
    sp = _pad_to(s, 1, 256, value=1.0) if per_channel else _pad_to(s, 0, 256, value=1.0)
    zp_ = _pad_to(z, 1, 256) if per_channel else _pad_to(z, 0, 256)
    y = dequantize_p(qp_, sp, zp_, out_dtype=out_dtype, interpret=_interpret())
    return y[:M, :N].reshape(*lead, N).astype(out_dtype)


def decode_attend_i8kv(q, k_q, v_q, k_scale, v_scale, length, *, layer=None,
                       new=None, bs: int = 256, wo_prologue: bool = False,
                       pro_dtype=None):
    """Batched flash-decode over an int8 KV cache in KERNEL layout.

    q: (B, H, Dh) f32; k_q/v_q: (B, Hkv, S, Dh) int8;
    k_scale/v_scale: (B, Hkv, S) f32; length: (B,) int32.
    Returns (B, H, Dh) f32.

    ``layer`` (a traced or static int) reads a LAYER-STACKED cache
    instead: k_q/v_q (L, B, Hkv, S, Dh), scales (L, B, Hkv, S), and the
    kernel streams layer ``layer``'s tiles straight out of the stack (the
    index is scalar-prefetched), so the decode step never materialises a
    per-layer slice of the cache.

    ``new`` = (slots (B,), k, v (B, Hkv, Dh) int8, k_scale, v_scale
    (B, Hkv) f32) is the step's token: the same launch writes it into the
    cache at (row, ``slots[row]``) before attending it, in place, and the
    call returns (its result, (k_q, v_q, k_scale, v_scale) updated).

    ``wo_prologue=True`` additionally runs the wo projection's PDQ prologue
    over the flattened (H * Dh,) output row inside the attend kernel's
    output stage and returns (o (B, H, Dh) f32, o_q (B, H*Dh) int8,
    s_x, s1, s2 each (B, 1) f32) - feed them to
    ``pdq_dense_from_prologue`` and the quantized wo projection costs one
    launch instead of two.  ``pro_dtype`` (default f32) is the compute
    dtype the unfused path would have cast o to before its prologue; the
    ref path reproduces that cast so numerics stay bit-identical to the
    unfused composition.

    The cache is head-major so the per-step decode path does no layout
    work: ``models.attention.init_cache`` allocates it this way (S rounded
    up to a 128 multiple).  With S % block == 0 the ``_pad_to`` calls below
    are trace-time no-ops; only ragged direct callers pay a one-off pad.
    """
    stacked = layer is not None
    cache = (k_q, v_q, k_scale, v_scale)
    if not stacked:
        cache = tuple(a[None] for a in cache)
        layer = 0
    B, H, Dh = q.shape
    Hkv, S = cache[0].shape[2], cache[0].shape[3]
    G = H // Hkv

    if not _use_kernel():
        if new is not None:
            slots, *tok = new
            at = (layer, jnp.arange(B)[:, None], jnp.arange(Hkv),
                  slots[:, None])
            cache = tuple(a.at[at].set(t) for a, t in zip(cache, tok))
        # jnp oracle keeps the logical (S, Hkv, ...) layout
        k_l, v_l, ks_l, vs_l = (
            jnp.swapaxes(jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
                         1, 2) for a in cache)
        o = jax.vmap(ref.decode_attend_i8kv_ref)(q, k_l, v_l, ks_l, vs_l,
                                                  length)
        if wo_prologue:
            of = o.astype(pro_dtype) if pro_dtype is not None else o
            o = (o, *ref.pdq_prologue_ref(of.reshape(B, H * Dh)))
    else:
        # prefer a scan block that divides S (true whenever the cache came
        # from init_cache, which rounds S to a 128 multiple) over padding
        bss = min(bs, S)
        while bss > 32 and S % bss:
            bss //= 2
        cache = (_pad_to(cache[0], 3, bss), _pad_to(cache[1], 3, bss),
                 _pad_to(cache[2], 3, bss, value=1.0),
                 _pad_to(cache[3], 3, bss, value=1.0))
        kern = decode_attend_i8kv_fused_p if wo_prologue else decode_attend_i8kv_p
        o = kern(q.reshape(B, Hkv, G, Dh), *cache, length,
                 jnp.asarray(layer, jnp.int32), new, bs=bss,
                 interpret=_interpret())
        if new is not None:
            o, cache = o
            cache = (cache[0][:, :, :, :S], cache[1][:, :, :, :S],
                     cache[2][..., :S], cache[3][..., :S])
        if wo_prologue:
            o, oq, sx, s1, s2 = o
            o = (o.reshape(B, H, Dh), oq.reshape(B, H * Dh), sx.reshape(B, 1),
                 s1.reshape(B, 1), s2.reshape(B, 1))
        else:
            o = o.reshape(B, H, Dh)
    if new is None:
        return o
    return o, (cache if stacked else tuple(a[0] for a in cache))


def cache_scatter_rows(dst, src, src_map, *, batch_axis: int = 0, _entry=None):
    """Batched cache-row scatter: out row s = src[src_map[s]] when
    src_map[s] >= 0, else dst[s] kept bit-exactly.  Any dtype (the int8
    kernel-layout KV leaves included) and any trailing shape.

    ``batch_axis=1`` handles stacked per-block cache leaves (n, B, ...):
    the stack is folded into the row axis and src_map is expanded per
    stack entry, so the kernel still sees a flat (rows, R) copy problem
    with no transposes.

    ``_entry`` picks the Pallas launch on the kernel path (slot-row
    ``cache_scatter_p`` by default; ``cache_scatter_pages`` routes the
    paged entry through here - same machinery, page-sized rows).
    """
    src_map = jnp.asarray(src_map, jnp.int32)
    if batch_axis == 1:
        n, B = dst.shape[0], dst.shape[1]
        Bs = src.shape[1]
        m = jnp.where(src_map[None, :] >= 0,
                      src_map[None, :] + Bs * jnp.arange(n)[:, None],
                      -1).reshape(n * B)
        out = cache_scatter_rows(dst.reshape((n * B,) + dst.shape[2:]),
                                 src.reshape((n * Bs,) + src.shape[2:]), m,
                                 _entry=_entry)
        return out.reshape(dst.shape)
    assert batch_axis == 0, batch_axis
    B = dst.shape[0]
    R = 1
    for d in dst.shape[1:]:
        R *= d
    if not _use_kernel():
        take = jnp.take(src, jnp.clip(src_map, 0, src.shape[0] - 1), axis=0)
        keep = (src_map >= 0).reshape((B,) + (1,) * (dst.ndim - 1))
        return jnp.where(keep, take, dst)
    # lane-dense (rows, C, 128) view; pad C to 32 rows only when no legal
    # block divides it (never for power-of-two cache shapes)
    C = -(-R // 128)
    mult = 128 if scatter_rows_block(C, dst.dtype.itemsize) else 32 * 128

    def rows3(a):
        a = _pad_to(a.reshape(a.shape[0], R), 1, mult)
        return a.reshape(a.shape[0], -1, 128)

    entry = cache_scatter_p if _entry is None else _entry
    out = entry(src_map, rows3(dst), rows3(src), interpret=_interpret())
    return out.reshape(B, -1)[:, :R].reshape(dst.shape)


# ---------------------------------------------------------------------------
# Paged KV-cache pool: page-rows views + paged scatter (serve/pages.py's
# device half).  A cache leaf's seq axis is split into fixed-size pages and
# the page index is folded into the batch/row axis, after which every pool
# movement (prefill landing, decode gather, COW copy, spill restore) is the
# SAME row-scatter problem cache_scatter_rows already solves.
# ---------------------------------------------------------------------------


def to_page_rows(x, seq_axis: int, page: int, *, batch_axis: int = 0):
    """Reshape a logical cache leaf to PAGE-ROWS: the seq axis (length S,
    S % page == 0) splits into (S//page, page) and the page index merges
    into the batch axis, giving (..., B * S//page, *page_block) with the
    page block laid out exactly like a physical pool page.  ``batch_axis``
    is 0 for head/tail leaves (B leading) and 1 for stacked block leaves
    (n_blocks, B, ...)."""
    S = x.shape[seq_axis]
    assert S % page == 0, (S, page)
    n_pp = S // page
    split = x.shape[:seq_axis] + (n_pp, page) + x.shape[seq_axis + 1:]
    x = jnp.reshape(x, split)
    lead = batch_axis + 1
    x = jnp.moveaxis(x, seq_axis, lead)          # page index next to batch
    B = x.shape[batch_axis]
    return jnp.reshape(
        x, x.shape[:batch_axis] + (B * n_pp,) + x.shape[lead + 1:])


def from_page_rows(x, shape, seq_axis: int, page: int, *, batch_axis: int = 0):
    """Inverse of ``to_page_rows``: page-rows back to the logical leaf
    layout ``shape``."""
    S = shape[seq_axis]
    n_pp = S // page
    B = shape[batch_axis]
    lead = batch_axis + 1
    x = jnp.reshape(x, x.shape[:batch_axis] + (B, n_pp) + x.shape[lead:])
    x = jnp.moveaxis(x, lead, seq_axis)
    return jnp.reshape(x, shape)


def cache_scatter_pages(dst, src, page_map, *, batch_axis: int = 0):
    """Row scatter over PAGES: ``dst``/``src`` are page-rows arrays (a
    physical pool, or a logical leaf through ``to_page_rows``) and
    ``page_map[p] = q`` moves src page-row q into dst page-row p (-1
    keeps dst bit-exactly).  Kernel path launches
    ``kv_cache.cache_scatter_pages_p`` - the paged front door of the same
    scalar-prefetched scatter machinery."""
    return cache_scatter_rows(dst, src, page_map, batch_axis=batch_axis,
                              _entry=cache_scatter_pages_p)
