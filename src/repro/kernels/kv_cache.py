"""Pallas TPU kernels for the serving KV-cache pool: flash-decode attention
over an int8-quantized cache, and the batched scatter-write the bucketed
prefill scheduler uses to land a whole prefill batch into the pooled cache
in one launch - over whole-sequence slot rows (``cache_scatter_p``) or
fixed-size pages of the paged pool (``cache_scatter_pages_p``).

Beyond-paper extension (DESIGN.md Sec. 2): the KV cache is stored int8 with
PDQ-predicted per-token-per-head scales, halving (vs bf16) the decode
memory-roofline term.  The kernel streams int8 K/V tiles HBM -> VMEM,
dequantizes in-register, and runs the online-softmax recurrence, so the
fp-dequantized cache never exists in HBM.

Layout: one query token, grouped-query attention (H = G * Hkv).
Grid (Hkv, S/bs); m/l/acc live in VMEM scratch across the S dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _attend_step(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                 m_ref, l_ref, acc_ref, *, bs: int, scale: float):
    """One (head, S-block) step of the online-softmax recurrence.

    The per-token K/V scales arrive lane-dense as (1, bs) rows, so they
    scale the (G, bs) logits and probabilities instead of the (bs, Dh)
    tiles: q . (k * ks) == (q . k) * ks per token, and likewise for v."""
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    offs = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = offs < len_ref[...]                                  # (1, bs)

    qb = q_ref[0]                                               # (G, Dh)
    kf = k_ref[0].astype(jnp.float32)                           # (bs, Dh)
    vf = v_ref[0].astype(jnp.float32)

    logits = jax.lax.dot_general(qb, kf, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits * (ks_ref[...] * scale)                     # (G, bs)
    logits = jnp.where(mask, logits, _NEG)

    m_prev = m_ref[...]                                         # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)      # (G, bs)
    corr = jnp.exp(m_prev - m_new)                              # (G, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p * vs_ref[...], vf, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, n_s: int, bs: int, scale: float):
    _attend_step(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                 m_ref, l_ref, acc_ref, bs=bs, scale=scale)

    @pl.when(pl.program_id(1) == n_s - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _attend_specs(G: int, Dh: int, bs: int):
    """BlockSpecs shared by both attend kernels over grid (Hkv, S/bs).  The
    scales come in as (Hkv, 1, S): a head's (1, bs) row is then a legal
    block (second-minor dim equal to the array's)."""
    return [
        pl.BlockSpec((1, 1), lambda h, s: (0, 0)),                # length
        pl.BlockSpec((1, G, Dh), lambda h, s: (h, 0, 0)),         # q
        pl.BlockSpec((1, bs, Dh), lambda h, s: (h, s, 0)),        # k
        pl.BlockSpec((1, bs, Dh), lambda h, s: (h, s, 0)),        # v
        pl.BlockSpec((None, 1, bs), lambda h, s: (h, 0, s)),      # k_scale
        pl.BlockSpec((None, 1, bs), lambda h, s: (h, 0, s)),      # v_scale
    ]


def _check_s(name: str, S: int, bs: int) -> None:
    assert S % bs == 0, (
        f"{name} requires block-multiple shapes: S ({S}) must be a multiple "
        f"of bs ({bs}); pad the cache or call "
        f"repro.kernels.ops.decode_attend_i8kv, which pads for you")


def decode_attend_i8kv_p(
    q: jax.Array,        # (Hkv, G, Dh) f32
    k_q: jax.Array,      # (Hkv, S, Dh) int8
    v_q: jax.Array,      # (Hkv, S, Dh) int8
    k_scale: jax.Array,  # (Hkv, S) f32
    v_scale: jax.Array,  # (Hkv, S) f32
    length: jax.Array,   # (1, 1) int32
    *,
    bs: int = 256,
    interpret: bool = False,
) -> jax.Array:
    Hkv, G, Dh = q.shape
    S = k_q.shape[1]
    bs = min(bs, S)
    _check_s("decode_attend_i8kv_p", S, bs)
    n_s = S // bs
    kern = functools.partial(_kernel, n_s=n_s, bs=bs, scale=1.0 / (Dh ** 0.5))
    return pl.pallas_call(
        kern,
        grid=(Hkv, n_s),
        in_specs=_attend_specs(G, Dh, bs),
        out_specs=pl.BlockSpec((1, G, Dh), lambda h, s: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Hkv, G, Dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(length, q, k_q, v_q, k_scale.reshape(Hkv, 1, S),
      v_scale.reshape(Hkv, 1, S))


def _fused_kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, oq_ref, sx_ref, s1_ref, s2_ref,
                  m_ref, l_ref, acc_ref, oall_ref, *,
                  n_hkv: int, n_s: int, bs: int, scale: float):
    h = pl.program_id(0)
    s = pl.program_id(1)
    _attend_step(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                 m_ref, l_ref, acc_ref, bs=bs, scale=scale)

    @pl.when(s == n_s - 1)
    def _finish():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)       # (G, Dh)
        o_ref[0] = o.astype(o_ref.dtype)
        # stage this head's normalized rows for the output-stage prologue
        oall_ref[h] = o

    @pl.when((s == n_s - 1) & (h == n_hkv - 1))
    def _prologue():
        # output stage: the wo projection's PDQ prologue over the FULL
        # flattened (H * Dh) attention output of this batch row, emitted
        # from the same launch - no separate pdq_prologue pass runs before
        # the wo matmul (see ops.decode_attend_i8kv / DESIGN.md "Decode
        # fast path").  Semantics match ref.pdq_prologue_ref on the
        # flattened row exactly.
        oa = oall_ref[...]                                      # (Hkv, G, Dh)
        amax = jnp.maximum(jnp.max(jnp.abs(oa), axis=(0, 1, 2),
                                   keepdims=True)[0], 1e-8)     # (1, 1)
        sx = amax / 127.0
        sx_ref[...] = sx
        s1_ref[...] = jnp.sum(oa, axis=(0, 1, 2), keepdims=True)[0]
        s2_ref[...] = jnp.sum(oa * oa, axis=(0, 1, 2), keepdims=True)[0]
        oq_ref[...] = jnp.clip(jnp.round(oa / sx), -127.0, 127.0).astype(jnp.int8)


def decode_attend_i8kv_fused_p(
    q: jax.Array,        # (Hkv, G, Dh) f32
    k_q: jax.Array,      # (Hkv, S, Dh) int8
    v_q: jax.Array,      # (Hkv, S, Dh) int8
    k_scale: jax.Array,  # (Hkv, S) f32
    v_scale: jax.Array,  # (Hkv, S) f32
    length: jax.Array,   # (1, 1) int32
    *,
    bs: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """``decode_attend_i8kv_p`` plus the wo projection's fused PDQ prologue
    in the output stage.

    Returns (o (Hkv, G, Dh) f32, o_q (H, Dh) int8, s_x, s1, s2 each (1, 1)
    f32) where (o_q, s_x, s1, s2) are ``pdq_prologue_ref`` of the flattened
    (H * Dh,) output row: everything the downstream W8A8 wo matmul needs,
    with zero extra launches.  The fp ``o`` is still emitted (it is live in
    VMEM anyway) for the guarded-fallback path and fp consumers.
    """
    Hkv, G, Dh = q.shape
    H = Hkv * G
    S = k_q.shape[1]
    bs = min(bs, S)
    _check_s("decode_attend_i8kv_fused_p", S, bs)
    n_s = S // bs
    kern = functools.partial(_fused_kernel, n_hkv=Hkv, n_s=n_s, bs=bs,
                             scale=1.0 / (Dh ** 0.5))
    o, oq, sx, s1, s2 = pl.pallas_call(
        kern,
        grid=(Hkv, n_s),
        in_specs=_attend_specs(G, Dh, bs),
        out_specs=[
            pl.BlockSpec((1, G, Dh), lambda h, s: (h, 0, 0)),     # o
            pl.BlockSpec((Hkv, G, Dh), lambda h, s: (0, 0, 0)),   # o_q
            pl.BlockSpec((1, 1), lambda h, s: (0, 0)),            # s_x
            pl.BlockSpec((1, 1), lambda h, s: (0, 0)),            # s1
            pl.BlockSpec((1, 1), lambda h, s: (0, 0)),            # s2
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Hkv, G, Dh), jnp.float32),
            jax.ShapeDtypeStruct((Hkv, G, Dh), jnp.int8),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
            pltpu.VMEM((Hkv, G, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(length, q, k_q, v_q, k_scale.reshape(Hkv, 1, S),
      v_scale.reshape(Hkv, 1, S))
    return o, oq.reshape(H, Dh), sx, s1, s2


# ---------------------------------------------------------------------------
# Pooled-cache slot scatter (bucketed batched prefill)
# ---------------------------------------------------------------------------


def _scatter_kernel(map_ref, dst_ref, src_ref, out_ref):
    b = pl.program_id(0)
    take = map_ref[b] >= 0

    @pl.when(take)
    def _take():
        out_ref[...] = src_ref[...]

    @pl.when(jnp.logical_not(take))
    def _keep():
        out_ref[...] = dst_ref[...]


_SCATTER_BLOCK_BYTES = 256 * 1024


def scatter_rows_block(C: int, itemsize: int) -> int:
    """Sublane rows per block of a (B, C, 128) scatter: the whole row when
    it fits ``_SCATTER_BLOCK_BYTES``, else the largest divisor of C that is
    a multiple of 32 (legal for every dtype's tiling) and fits; 0 when C
    has none - the caller then pads C to a multiple of 32."""
    cmax = max(_SCATTER_BLOCK_BYTES // (128 * itemsize), 32)
    if C <= cmax:
        return C
    for bc in range(cmax - cmax % 32, 0, -32):
        if C % bc == 0:
            return bc
    return 0


def cache_scatter_p(
    src_map: jax.Array,  # (B,) int32: source row per dst row, or -1 = keep
    dst: jax.Array,      # (B, C, 128) any dtype (int8 kernel-layout KV included)
    src: jax.Array,      # (Bs, C, 128) same dtype
    *,
    interpret: bool = False,
) -> jax.Array:
    """out[b] = src[src_map[b]] if src_map[b] >= 0 else dst[b] (bit-exact).

    One launch scatters a whole prefill batch of cache rows into the pooled
    serving cache.  ``src_map`` is scalar-prefetched so the src BlockSpec
    index map can chase it (clamped to row 0 for passthrough rows - the
    block is still streamed, but the kernel writes the dst copy instead).
    Each row is laid out lane-dense as (C, 128) and blocked along C
    (``scatter_rows_block``), so arbitrarily large KV leaves never exceed
    VMEM and every block satisfies the TPU's (8, 128) tiling.
    """
    B, C, L = dst.shape
    assert L == 128 and src.ndim == 3 and src.shape[1:] == (C, L) \
        and src.dtype == dst.dtype, (dst.shape, src.shape)
    bc = scatter_rows_block(C, dst.dtype.itemsize)
    assert bc and C % bc == 0, (
        f"cache_scatter_p: row extent C={C} has no legal block; pad it to a "
        f"multiple of 32 (ops.cache_scatter_rows does)")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, C // bc),
        in_specs=[
            pl.BlockSpec((1, bc, L), lambda b, r, m: (b, r, 0)),
            pl.BlockSpec((1, bc, L), lambda b, r, m: (jnp.maximum(m[b], 0), r, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, L), lambda b, r, m: (b, r, 0)),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, L), dst.dtype),
        interpret=interpret,
    )(src_map.astype(jnp.int32), dst, src)


def cache_scatter_pages_p(
    page_map: jax.Array,  # (N,) int32: source page-row per pool page, or -1
    dst: jax.Array,       # (N, C, 128) physical page pool, one page per row
    src: jax.Array,       # (M, C, 128) page-rows (a logical cache leaf reshaped)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Paged generalization of ``cache_scatter_p``: rows are fixed-size
    cache PAGES instead of whole-sequence slot rows.

    The scalar-prefetched machinery is identical - the map is prefetched,
    the src BlockSpec chases ``max(page_map[n], 0)``, and -1 entries keep
    the dst page bit-exactly - but the row extent R is one page's elements
    (page_size x heads x head_dim), so a single launch moves an arbitrary
    subset of pool pages with no host round-trip.  Both directions of the
    paged pool ride this one kernel: LANDING a prefill (dst = pool pages,
    src = the prefill batch reshaped to page-rows, map = the allocator's
    page tables) and GATHERING for decode (dst = a zeroed per-slot scratch
    in page-rows, src = pool pages, map = the flattened page tables; -1
    table entries leave the scratch zero, matching the never-written
    region of a slot-row cache bit-exactly).
    """
    return cache_scatter_p(page_map, dst, src, interpret=interpret)
