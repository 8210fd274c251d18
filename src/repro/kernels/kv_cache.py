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

Layout: one query token, grouped-query attention (H = G * Hkv), read
from the model's layer-stacked cache (L, B, Hkv, S, Dh) at a
scalar-prefetched layer index, K and V sequence-minor.  Grid (B, S/bs): a
step holds one S-block of a row for every KV head (``attend_tiling``
sizes the block), and m/l/acc live in VMEM scratch across the S
dimension.  Only the S-blocks a row holds are read and attended: past the
row's last live block (``_last_block``) the index maps repeat that block,
so no DMA is issued, and the step does nothing.  A decode step's launch
also writes the step's token into the stack, in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# VMEM budget of one grid step's K tile of every head (its V tile is as
# large; both are double-buffered, and the step widens both to f32: 8x
# this budget of temporaries at most)
_TILE_BYTES = 512 * 1024


def attend_tiling(S: int, Hkv: int, Dh: int, itemsize: int = 1,
                  bs: int = 256) -> int:
    """The S-block one grid step of the attend holds, from the cache's
    shapes alone: the largest of ``bs``, ``bs / 2``, ... that divides S (a
    ragged S - not a 128 multiple - gets 32, and the caller pads S to it),
    halved down to 128 while the (Hkv, Dh, bs) K tile of every head
    outgrows ``_TILE_BYTES``."""
    bs = min(bs, S)
    while bs > 32 and S % bs:
        bs //= 2
    while (bs > 128 and Hkv * Dh * bs * itemsize > _TILE_BYTES
           and S % (bs // 2) == 0):
        bs //= 2
    return bs


def _attend_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, mask, m_ref, l_ref,
                  acc_ref, write, *, scale: float):
    """One S-block of the online-softmax recurrence for every KV head at
    once: the (Hkv, Dh, bs) K and V tiles, widened, and the lane-dense
    (Hkv, bs) scales, in two head-batched dot_generals.  ``write`` = (hit,
    col, new token refs, K/V staging buffers): in the block that holds the
    slot, patch the tiles and stage them.

    The per-token K/V scales scale the (Hkv, G, bs) logits and
    probabilities instead of the tiles: q . (k * ks) == (q . k) * ks per
    token, and likewise for v."""
    f32 = jnp.float32
    tiles = (k_ref[...].astype(f32), v_ref[...].astype(f32), ks_ref[...],
             vs_ref[...])
    if write is not None:
        hit, col, (kn, vn, ksn, vsn), kbuf, vbuf = write

        def patch(kf, vf, ks, vs):
            return (jnp.where(col[None], kn[...], kf),
                    jnp.where(col[None], vn[...], vf),
                    jnp.where(col, ksn[...], ks), jnp.where(col, vsn[...], vs))
        tiles = jax.lax.cond(hit, patch, lambda *a: a, *tiles)

        @pl.when(hit)
        def _stage():
            kbuf[...] = tiles[0].astype(kbuf.dtype)
            vbuf[...] = tiles[1].astype(vbuf.dtype)
    kf, vf, ks, vs = tiles
    logits = jax.lax.dot_general(q_ref[...], kf, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=f32)
    logits = logits * (ks * scale)[:, None, :]                  # (Hkv, G, bs)
    logits = jnp.where(mask[None], logits, _NEG)

    m_prev = m_ref[...]                                         # (Hkv, G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new) * mask[None].astype(f32)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p * vs[:, None, :], vf, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=f32)
    m_ref[...] = m_new


def _token_copies(layer, b, lo, bs, k_hbm, v_hbm, ks_hbm, vs_hbm, kbuf, vbuf,
                  sbuf, sem):
    """The write's DMAs of S-block [lo, lo + bs) of row b: every head's K
    and V tiles, then their scales.  One semaphore counts all four."""
    window = pl.ds(lo, bs)
    return (pltpu.make_async_copy(kbuf, k_hbm.at[layer, b, :, :, window], sem),
            pltpu.make_async_copy(vbuf, v_hbm.at[layer, b, :, :, window], sem),
            pltpu.make_async_copy(sbuf.at[0], ks_hbm.at[layer, b, :, window],
                                  sem),
            pltpu.make_async_copy(sbuf.at[1], vs_hbm.at[layer, b, :, window],
                                  sem))


def _finish_plain(o, o_ref):
    o_ref[...] = o.astype(o_ref.dtype)


def _finish_fused(o, o_ref, oq_ref, sx_ref, s1_ref, s2_ref):
    o_ref[...] = o.astype(o_ref.dtype)
    # output stage: the wo projection's PDQ prologue over the FULL
    # flattened (H * Dh) attention output of this batch row, emitted from
    # the same launch - no separate pdq_prologue pass runs before the wo
    # matmul (see ops.decode_attend_i8kv / DESIGN.md "Decode fast path").
    # Semantics match ref.pdq_prologue_ref on the flattened row exactly.
    amax = jnp.maximum(jnp.max(jnp.abs(o), axis=(0, 1, 2),
                               keepdims=True)[0], 1e-8)         # (1, 1)
    sx = amax / 127.0
    sx_ref[...] = sx
    s1_ref[...] = jnp.sum(o, axis=(0, 1, 2), keepdims=True)[0]
    s2_ref[...] = jnp.sum(o * o, axis=(0, 1, 2), keepdims=True)[0]
    oq_ref[...] = jnp.clip(jnp.round(o / sx), -127.0, 127.0).astype(jnp.int8)


def _kernel(*refs, bs: int, scale: float, n_out: int, write: bool, finish):
    """Both attend kernels over the operands of ``_attend_call`` in order,
    then the outputs, then the scratch.

    A step attends S-block s of row b for every KV head, if the row holds
    it (``_last_block``).  With the token write, the block holding
    ``slot[b]`` is patched before it is attended, and its patched tiles
    are staged; DMAs then put the block back into the cache: every head's
    K and V tiles and their scales.  They run behind the attention, and
    are collected at the row's end.  The cache operands alias the DMAs'
    destinations, so the write lands in place and the rest of the cache is
    never touched."""
    if write:
        layer_ref, len_ref, slot_ref, *refs = refs
    else:
        layer_ref, len_ref, *refs = refs
        slot_ref = None
    q_ref, k_ref, v_ref, ks_ref, vs_ref, *refs = refs
    news, refs = (refs[:4], refs[4:]) if write else ((), refs)
    outs, refs = refs[:n_out], refs[n_out:]
    hbm, refs = (refs[:4], refs[4:]) if write else ((), refs)
    m_ref, l_ref, acc_ref, *bufs = refs
    b, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    lo = pl.multiple_of(s * bs, bs)
    offs = lo + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = offs < len_ref[b]                                    # (1, bs)
    if write:
        copy = (*hbm, *bufs)
        kbuf, vbuf, sbuf = bufs[:3]
        slot = slot_ref[b]
        hit = (slot >= lo) & (slot < lo + bs)
        col = offs == slot                                      # (1, bs)

    @pl.when(s <= _last_block(b, len_ref, slot_ref, bs))
    def _step():
        _attend_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, mask, m_ref,
                      l_ref, acc_ref,
                      (hit, col, news, kbuf, vbuf) if write else None,
                      scale=scale)
        if write:
            @pl.when(hit)
            def _store():
                sbuf[0] = jnp.where(col, news[2][...], ks_ref[...])
                sbuf[1] = jnp.where(col, news[3][...], vs_ref[...])
                for c in _token_copies(layer_ref[0], b, lo, bs, *copy):
                    c.start()

    @pl.when(s == pl.num_programs(1) - 1)
    def _finish():
        finish(acc_ref[...] / jnp.maximum(l_ref[...], 1e-30), *outs)
        if write:
            start = pl.multiple_of(slot // bs * bs, bs)
            for c in _token_copies(layer_ref[0], b, start, bs, *copy):
                c.wait()


def _row_spec(*tail):
    """One batch row's whole block (the query, the new token, the outputs)."""
    return pl.BlockSpec((None,) + tail, lambda b, s, *_: (b,) + (0,) * len(tail))


def _last_block(b, len_ref, slot_ref, bs: int):
    """The last S-block of row b that a launch attends: the one holding the
    row's last token (-1 for an empty row: none), or, with the token write,
    the slot's if that lies further.  The kernel attends no step past it,
    and the index maps clamp to it (a repeated block index issues no DMA).
    Attending a wholly masked block would add exactly nothing, so where the
    slot lies past the length the clamp changes no result."""
    n = len_ref[b]
    last = jnp.where(n > 0, (n - 1) // bs, -1)
    if slot_ref is not None:
        last = jnp.maximum(last, slot_ref[b] // bs)
    return last


def _check_s(name: str, S: int, bs: int) -> None:
    assert S % bs == 0, (
        f"{name} requires block-multiple shapes: S ({S}) must be a multiple "
        f"of bs ({bs}); pad the cache or call "
        f"repro.kernels.ops.decode_attend_i8kv, which pads for you")


def _attend_call(name, fused, q, k_q, v_q, k_scale, v_scale, length, layer,
                 new, bs, interpret):
    """One launch over grid (B, S/bs) of the LAYER-STACKED cache.

    The layer index and the per-row lengths (and the write slots) are
    scalar-prefetched, and the K/V index maps pick the layer's tiles
    straight out of the (L, B, Hkv, S, Dh) stack, so no per-layer slice of
    the cache is ever materialised.  K and V are read sequence-minor, as
    (Hkv, Dh, bs) tiles of the stack viewed (L, B, Hkv, Dh, S): that is how
    XLA lays out the carried int8 stack (a 64-wide Dh would fill half of
    every 128-lane tile), so the view is a relabelling, not a copy.  The
    scales come as lane-dense (Hkv, bs) blocks of the (Hkv, S) rows."""
    B, Hkv, G, Dh = q.shape
    S = k_q.shape[3]
    bs = attend_tiling(S, Hkv, Dh, k_q.dtype.itemsize, min(bs, S))
    _check_s(name, S, bs)
    assert Hkv * Dh * bs * k_q.dtype.itemsize <= _TILE_BYTES, (
        f"{name}: the ({Hkv}, {Dh}, {bs}) K tile of every head outgrows the "
        f"kernel's {_TILE_BYTES}-byte budget")
    write = new is not None

    def block(b, s, len_ref, *slot_ref):
        # step s reads block s while the row holds it, then its last block
        # again (block 0 for an empty row)
        last = _last_block(b, len_ref, slot_ref[0] if write else None, bs)
        return jnp.maximum(jnp.minimum(s, last), 0)

    tile = pl.BlockSpec((None, None, Hkv, Dh, bs),
                        lambda b, s, l, *r: (l[0], b, 0, 0, block(b, s, *r)))
    scales = pl.BlockSpec((None, None, Hkv, bs),
                          lambda b, s, l, *r: (l[0], b, 0, block(b, s, *r)))
    prefetch = [jnp.reshape(layer, (1,)).astype(jnp.int32),
                length.astype(jnp.int32)]
    args = [q, jnp.swapaxes(k_q, 3, 4), jnp.swapaxes(v_q, 3, 4), k_scale,
            v_scale]
    in_specs = [_row_spec(Hkv, G, Dh), tile, tile, scales, scales]
    if fused:
        out_specs = [_row_spec(Hkv, G, Dh), _row_spec(Hkv, G, Dh),
                     _row_spec(1, 1), _row_spec(1, 1), _row_spec(1, 1)]
        out_shape = [jax.ShapeDtypeStruct((B, Hkv, G, Dh), jnp.float32),
                     jax.ShapeDtypeStruct((B, Hkv, G, Dh), jnp.int8),
                     *[jax.ShapeDtypeStruct((B, 1, 1), jnp.float32)] * 3]
    else:
        out_specs = [_row_spec(Hkv, G, Dh)]
        out_shape = [jax.ShapeDtypeStruct((B, Hkv, G, Dh), jnp.float32)]
    scratch = [pltpu.VMEM((Hkv, G, 1), jnp.float32),
               pltpu.VMEM((Hkv, G, 1), jnp.float32),
               pltpu.VMEM((Hkv, G, Dh), jnp.float32)]
    n_out = len(out_shape)
    aliases = {}
    if write:
        slots, k_new, v_new, ks_new, vs_new = new
        prefetch.append(slots.astype(jnp.int32))
        args += [k_new.astype(jnp.float32)[..., None],
                 v_new.astype(jnp.float32)[..., None],
                 ks_new[..., None], vs_new[..., None]]
        in_specs += [_row_spec(Hkv, Dh, 1), _row_spec(Hkv, Dh, 1),
                     _row_spec(Hkv, 1), _row_spec(Hkv, 1)]
        # the cache operands come back as these outputs, in place
        first = len(prefetch) + 1
        aliases = {first + i: n_out + i for i in range(4)}
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 4
        out_shape += [jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for a in args[1:5]]
        scratch += [pltpu.VMEM((Hkv, Dh, bs), k_q.dtype),
                    pltpu.VMEM((Hkv, Dh, bs), v_q.dtype),
                    pltpu.VMEM((2, Hkv, bs), jnp.float32),
                    pltpu.SemaphoreType.DMA]
    kern = functools.partial(
        _kernel, bs=bs, scale=1.0 / (Dh ** 0.5), n_out=n_out,
        write=write, finish=_finish_fused if fused else _finish_plain)
    outs = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(B, S // bs),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        input_output_aliases=aliases,
        name=name,
        interpret=interpret,
    )(*prefetch, *args)
    o = tuple(outs[:n_out]) if fused else outs[0]
    if not write:
        return o
    kt, vt, ks, vs = outs[n_out:]
    return o, (jnp.swapaxes(kt, 3, 4), jnp.swapaxes(vt, 3, 4), ks, vs)


def decode_attend_i8kv_p(
    q: jax.Array,        # (B, Hkv, G, Dh) f32
    k_q: jax.Array,      # (L, B, Hkv, S, Dh) int8, layer-stacked
    v_q: jax.Array,      # (L, B, Hkv, S, Dh) int8
    k_scale: jax.Array,  # (L, B, Hkv, S) f32
    v_scale: jax.Array,  # (L, B, Hkv, S) f32
    length: jax.Array,   # (B,) int32
    layer: jax.Array,    # () int32: the layer of the stack to attend
    new=None,            # (slots (B,), k, v (B, Hkv, Dh) int8, k_scale,
                         #  v_scale (B, Hkv) f32): write this token first
    *,
    bs: int = 256,
    interpret: bool = False,
):
    """Flash-decode of layer ``layer`` of a layer-stacked int8 KV cache.

    Returns o (B, Hkv, G, Dh) f32.  With ``new`` the step's token is
    written into the layer at (row, ``slots[row]``) by the same launch,
    which attends it, and the result is (o, (k_q, v_q, k_scale, v_scale))
    with the stacks updated in place."""
    return _attend_call("decode_attend_i8kv", False, q, k_q, v_q, k_scale,
                        v_scale, length, layer, new, bs, interpret)


def decode_attend_i8kv_fused_p(
    q: jax.Array,        # (B, Hkv, G, Dh) f32
    k_q: jax.Array,      # (L, B, Hkv, S, Dh) int8, layer-stacked
    v_q: jax.Array,      # (L, B, Hkv, S, Dh) int8
    k_scale: jax.Array,  # (L, B, Hkv, S) f32
    v_scale: jax.Array,  # (L, B, Hkv, S) f32
    length: jax.Array,   # (B,) int32
    layer: jax.Array,    # () int32
    new=None,            # as decode_attend_i8kv_p
    *,
    bs: int = 256,
    interpret: bool = False,
):
    """``decode_attend_i8kv_p`` plus the wo projection's fused PDQ prologue
    in the output stage.

    Returns (o (B, Hkv, G, Dh) f32, o_q (B, Hkv, G, Dh) int8, s_x, s1, s2
    each (B, 1, 1) f32) where (o_q, s_x, s1, s2) are ``pdq_prologue_ref``
    of each row's flattened (H * Dh,) output: everything the downstream
    W8A8 wo matmul needs, with zero extra launches.  The fp ``o`` is still
    emitted (it is live in VMEM anyway) for the guarded-fallback path and
    fp consumers.  With ``new``, as ``decode_attend_i8kv_p``: (that tuple,
    the updated stacks).
    """
    return _attend_call("decode_attend_i8kv_fused", True, q, k_q, v_q,
                        k_scale, v_scale, length, layer, new, bs, interpret)


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
        name="cache_scatter",
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
