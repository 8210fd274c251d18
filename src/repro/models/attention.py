"""Attention variants: GQA (full/sliding-window), MLA, cross-attention.

All functions are pure; KV caches are dict pytrees threaded by the caller.
Training/prefill attention is chunked (flash-style online softmax via
lax.scan) so the (S x S) score matrix never materializes - required at
32k prefill and beyond.

KV caches:
  full   : {'k','v': (B, S, Hkv, Dh), 'len': (B,)}        [optionally int8 + scales]
  window : {'k','v': (B, W, Hkv, Dh), 'len': (B,)}         ring buffer
  mla    : {'ckv': (B, S, r), 'krope': (B, S, dr), 'len': (B,)}
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .layers import (YarnConfig, apply_rope, dense_init, rms_norm, softcap,
                     yarn_softmax_scale)
from .linops import is_quantized, is_segment_view, lin, lin_grouped

NEG = -2.0e30


@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    attn_softcap: float | None = None
    window: int | None = None          # sliding window (local attention)
    quant_kv: str = "none"             # 'none' | 'dynamic' | 'pdq'


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention core
# ---------------------------------------------------------------------------


def chunked_attention(
    q: jax.Array,            # (B, Sq, H, Dh)
    k: jax.Array,            # (B, Sk, Hkv, Dh)
    v: jax.Array,            # (B, Sk, Hkv, Dh)
    q_pos: jax.Array,        # (B, Sq) absolute positions
    k_pos: jax.Array,        # (B, Sk)
    *,
    causal: bool = True,
    window: int | None = None,
    attn_softcap: float | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    parallel_q: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Online-softmax attention; scores exist only per (q_chunk x kv_chunk).

    q/k share head_dim Dh; v may have a different head_dim Dv (MLA).
    ``scale`` multiplies the scores (default Dh^-0.5)."""
    B, Sq, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // Hkv
    if scale is None:
        scale = Dh ** -0.5
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk

    # (B, Sq, H, Dh) -> (nq, B, H, qc, Dh); scale in q.dtype (bf16 stays bf16)
    qc = q.reshape(B, nq, q_chunk, H, Dh).transpose(1, 0, 3, 2, 4) \
        * jnp.asarray(scale, q.dtype)
    qp = q_pos.reshape(B, nq, q_chunk).transpose(1, 0, 2)
    kc = k.reshape(B, nk, kv_chunk, Hkv, Dh).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(B, nk, kv_chunk, Hkv, Dv).transpose(1, 0, 3, 2, 4)
    kp = k_pos.reshape(B, nk, kv_chunk).transpose(1, 0, 2)

    def q_step(_, qx):
        qi, qpi = qx                                  # (B, H, qc, Dh), (B, qc)
        qi = qi.reshape(B, Hkv, G, q_chunk, Dh)

        def kv_step(carry, kx):
            m, l, acc = carry
            ki, vi, kpi = kx                          # (B, Hkv, kc, Dh), (B, kc)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qi, ki,
                           preferred_element_type=jnp.float32)
            s = softcap(s, attn_softcap)
            msk = jnp.ones((B, 1, 1, q_chunk, kv_chunk), bool)
            rel = qpi[:, None, None, :, None] - kpi[:, None, None, None, :]
            if causal:
                msk &= rel >= 0
            if window is not None:
                msk &= rel < window
            s = jnp.where(msk, s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(msk, p, 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), ()

        init = (jnp.full((B, Hkv, G, q_chunk), NEG, jnp.float32),
                jnp.zeros((B, Hkv, G, q_chunk), jnp.float32),
                jnp.zeros((B, Hkv, G, q_chunk, Dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (kc, vc, kp))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, o.reshape(B, H, q_chunk, Dv)

    if parallel_q:
        # q blocks as a batched dim (shardable: sequence parallelism); the
        # online-softmax scan runs only over KV chunks.
        qb = qc.reshape(nq, B, Hkv, G, q_chunk, Dh)

        def kv_step_p(carry, kx):
            m, l, acc = carry
            ki, vi, kpi = kx
            s = jnp.einsum("nbhgqd,bhkd->nbhgqk", qb, ki,
                           preferred_element_type=jnp.float32)
            s = softcap(s, attn_softcap)
            rel = qp[:, :, None, None, :, None] - kpi[None, :, None, None, None, :]
            msk = jnp.ones(rel.shape, bool)
            if causal:
                msk &= rel >= 0
            if window is not None:
                msk &= rel < window
            s = jnp.where(msk, s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(msk, p, 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "nbhgqk,bhkd->nbhgqd", p.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), ()

        init = (jnp.full((nq, B, Hkv, G, q_chunk), NEG, jnp.float32),
                jnp.zeros((nq, B, Hkv, G, q_chunk), jnp.float32),
                jnp.zeros((nq, B, Hkv, G, q_chunk, Dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step_p, init, (kc, vc, kp))
        o = acc / jnp.maximum(l, 1e-30)[..., None]      # (nq,B,Hkv,G,qc,Dv)
        out = o.reshape(nq, B, H, q_chunk, Dv)
    else:
        _, out = jax.lax.scan(q_step, None, (qc, qp))  # (nq, B, H, qc, Dv)
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,            # (B, H, Dh) one token
    k: jax.Array,            # (B, S, Hkv, Dh)
    v: jax.Array,
    q_pos: jax.Array,        # (B,)
    k_pos: jax.Array,        # (B, S) absolute position per slot (-1 = empty)
    *,
    window: int | None = None,
    attn_softcap: float | None = None,
) -> jax.Array:
    B, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, Dh) * jnp.asarray(Dh ** -0.5, q.dtype)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k, preferred_element_type=jnp.float32)
    s = softcap(s, attn_softcap)
    rel = q_pos[:, None] - k_pos                      # (B, S)
    ok = (rel >= 0) & (k_pos >= 0)
    if window is not None:
        ok &= rel < window
    s = jnp.where(ok[:, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------


def gqa_init(key, dims: AttnDims, dtype):
    ks = jax.random.split(key, 4)
    d, H, Hkv, Dh = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    return {
        "wq": dense_init(ks[0], d, H * Dh, dtype),
        "wk": dense_init(ks[1], d, Hkv * Dh, dtype),
        "wv": dense_init(ks[2], d, Hkv * Dh, dtype),
        "wo": dense_init(ks[3], H * Dh, d, dtype),
    }


def init_cache(dims: AttnDims, batch: int, max_len: int, dtype) -> dict[str, Any]:
    Hkv, Dh = dims.n_kv_heads, dims.head_dim
    S = min(max_len, dims.window) if dims.window else max_len
    cache = {
        "pos": jnp.full((batch, S), -1, jnp.int32),
        "len": jnp.zeros((batch,), jnp.int32),
    }
    if dims.quant_kv != "none":
        # int8 caches live in KERNEL layout (B, Hkv, S, Dh) with S rounded
        # up to a 128 multiple: the flash-decode kernel then streams tiles
        # with zero per-step transposes/pads (ops.decode_attend_i8kv).  The
        # padded tail is never written (slots index the logical S from
        # cache['pos']) and always masked (offs >= length).
        Sp = S + (-S) % 128
        cache["k"] = jnp.zeros((batch, Hkv, Sp, Dh), jnp.int8)
        cache["v"] = jnp.zeros((batch, Hkv, Sp, Dh), jnp.int8)
        cache["k_scale"] = jnp.ones((batch, Hkv, Sp), jnp.float32)
        cache["v_scale"] = jnp.ones((batch, Hkv, Sp), jnp.float32)
    else:
        cache["k"] = jnp.zeros((batch, S, Hkv, Dh), dtype)
        cache["v"] = jnp.zeros((batch, S, Hkv, Dh), dtype)
    return cache


def _quant_kv_token(k_new, v_new):
    """Symmetric per-(token, head) int8 quantization of new KV entries."""
    def q(t):
        amax = jnp.max(jnp.abs(t), axis=-1)                     # (B, S, Hkv)
        scale = jnp.maximum(amax, 1e-6) / 127.0
        tq = jnp.clip(jnp.round(t / scale[..., None]), -127, 127).astype(jnp.int8)
        return tq, scale
    kq, ks = q(k_new.astype(jnp.float32))
    vq, vs = q(v_new.astype(jnp.float32))
    return kq, ks, vq, vs


def _cache_write(cache, k_new, v_new, positions, quant: str, layer=None):
    """Write S_new tokens at ring positions (pos % W for windows).

    ``layer`` marks a LAYER-STACKED cache (every leaf (L, B, ...)): the
    tokens land at (layer, row, slot) by one scatter per leaf, so a decode
    step that carries the stack through the layer scan updates it in place
    and rewrites nothing else of the layer."""
    slots = positions % cache["pos"].shape[-1]   # logical length (int8 pads S)
    at = () if layer is None else (layer,)
    bidx = jnp.arange(positions.shape[0])[:, None]
    cache = dict(cache)
    if quant != "none":
        kq, ks, vq, vs = _quant_kv_token(k_new, v_new)
        # kernel-layout cache (B, Hkv, Sp, Dh): advanced indexing brings
        # the (B, S_new) gather dims to the front, so the (B, S_new, Hkv,
        # Dh) update lands without any transpose.
        idx = at + (bidx, slice(None), slots)
        cache["k"] = cache["k"].at[idx].set(kq)
        cache["v"] = cache["v"].at[idx].set(vq)
        cache["k_scale"] = cache["k_scale"].at[idx].set(ks)
        cache["v_scale"] = cache["v_scale"].at[idx].set(vs)
    else:
        idx = at + (bidx, slots)
        cache["k"] = cache["k"].at[idx].set(k_new.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[idx].set(v_new.astype(cache["v"].dtype))
    return _cache_advance(cache, positions, layer)


def _cache_advance(cache, positions, layer=None):
    """Record written positions: ``pos`` at their slots, and ``len``."""
    B = positions.shape[0]
    slots = positions % cache["pos"].shape[-1]
    at = () if layer is None else (layer,)
    cache = dict(cache)
    cache["pos"] = cache["pos"].at[at + (jnp.arange(B)[:, None], slots)].set(
        positions)
    length = jnp.maximum(_at_layer(cache["len"], layer), positions[:, -1] + 1)
    cache["len"] = (length if layer is None
                    else cache["len"].at[layer].set(length))
    return cache


def _clamp_padded(vals, positions, seq_lens):
    """Redirect right-pad rows of a prefill write onto the row's LAST REAL
    token.

    ``seq_lens[b]`` counts the valid leading entries of row b; entries at
    sequence index >= seq_lens[b] are bucket padding.  Rewriting both the
    VALUES and the POSITIONS of pad entries to those of index seq_lens[b]-1
    makes every duplicate scatter slot carry identical data, so the write
    stays deterministic (XLA scatter order is unspecified for duplicate
    indices) and the cache ends up bit-identical to an unpadded prefill:
    pad tokens never exist in it.  Returns (clamped_vals, clamped_pos).
    """
    B, S = positions.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (B, S), 1)
    valid = idx < seq_lens[:, None]
    last = jnp.maximum(seq_lens - 1, 0)                    # (B,)
    bidx = jnp.arange(B)
    out = []
    for v in vals:
        v_last = v[bidx, last][:, None]                    # (B, 1, ...)
        mask = valid.reshape(valid.shape + (1,) * (v.ndim - 2))
        out.append(jnp.where(mask, v, v_last))
    pos = jnp.where(valid, positions, positions[bidx, last][:, None])
    return out, pos


def _cache_kv_float(cache, dtype):
    if "k_scale" in cache:
        S = cache["pos"].shape[1]
        k = cache["k"].astype(jnp.float32) * cache["k_scale"][..., None]
        v = cache["v"].astype(jnp.float32) * cache["v_scale"][..., None]
        # kernel layout (B, Hkv, Sp, Dh) -> logical (B, S, Hkv, Dh)
        k = jnp.transpose(k, (0, 2, 1, 3))[:, :S]
        v = jnp.transpose(v, (0, 2, 1, 3))[:, :S]
        return k.astype(dtype), v.astype(dtype)
    return cache["k"], cache["v"]


def is_gqa_cache(cache) -> bool:
    """A GQA K/V cache (fp, or int8 with its scales; global or a window
    ring), as opposed to an MLA latent, SSM or cross-attention cache."""
    return "k" in cache and set(cache) <= {"k", "v", "k_scale", "v_scale",
                                           "pos", "len"}


def _at_layer(a: jax.Array, layer) -> jax.Array:
    """Layer ``layer`` of a stacked cache leaf; the leaf itself when
    ``layer`` is None (the cache is one layer's)."""
    if layer is None:
        return a
    return jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)


def _valid_k_pos(cache_pos: jax.Array) -> jax.Array:
    """Cache slot positions with empty slots (-1) pushed beyond every real
    query position, so the causal mask of ``chunked_attention`` (which has
    no explicit validity mask) excludes them: rel = q_pos - 2^30 < 0."""
    return jnp.where(cache_pos >= 0, cache_pos, jnp.int32(2 ** 30))


def gqa_apply(
    p,
    dims: AttnDims,
    x: jax.Array,                     # (B, S, d)  [S=1 for decode]
    positions: jax.Array,             # (B, S)
    *,
    mode: str,                        # 'train' | 'prefill' | 'decode'
    cache: dict | None = None,
    causal: bool = True,
    seq_lens: jax.Array | None = None,   # (B,) valid prefix per right-padded row
    chunked: bool = False,            # continuation chunk: attend the cache
    layer: jax.Array | None = None,   # decode: the cache is this layer's stack
):
    B, S, d = x.shape
    H, Hkv, Dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    # Q/K/V consume the same normed input: quantized params run ONE
    # prologue + ONE wide W8A8 matmul for the triple (linops.lin_grouped)
    q, k, v = lin_grouped(x, (p["wq"], p["wk"], p["wv"]))
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    q = apply_rope(q, positions, dims.rope_theta)
    k = apply_rope(k, positions, dims.rope_theta)

    if mode == "train":
        o = chunked_attention(q, k, v, positions, positions, causal=causal,
                              window=dims.window, attn_softcap=dims.attn_softcap)
        return lin(o.reshape(B, S, H * Dh), p["wo"]), None

    assert cache is not None
    if mode == "prefill" and chunked:
        # chunked-prefill continuation: the cache row already holds earlier
        # chunks.  Attend the PRE-write cache (the landed prefix) plus this
        # chunk's own k/v, concatenated - causal over absolute positions,
        # empty slots pushed out of causal range - and only then write the
        # chunk.  The order matters for sliding-window layers, whose ring
        # cache holds exactly the last W positions: writing first would
        # evict keys still inside earlier in-chunk queries' windows.
        # Appending the chunk after the cache slots inserts only
        # exactly-zero (masked) terms into the softmax sums, so fp-cache
        # numerics match an unpadded prefill; an int8 KV cache contributes
        # its dequantized prefix (the same values decode would see) -
        # approximate, documented.
        assert seq_lens is not None
        kf, vf = _cache_kv_float(cache, x.dtype)
        k_all = jnp.concatenate([kf, k.astype(kf.dtype)], axis=1)
        v_all = jnp.concatenate([vf, v.astype(vf.dtype)], axis=1)
        pos_all = jnp.concatenate([_valid_k_pos(cache["pos"]), positions],
                                  axis=1)
        o = chunked_attention(q, k_all, v_all, positions, pos_all,
                              causal=causal, window=dims.window,
                              attn_softcap=dims.attn_softcap,
                              q_chunk=S, kv_chunk=k_all.shape[1],
                              parallel_q=True)
        (kc, vc), pos_c = _clamp_padded((k, v), positions, seq_lens)
        cache = _cache_write(cache, kc, vc, pos_c, dims.quant_kv)
        return lin(o.reshape(B, S, H * Dh), p["wo"]), cache
    if mode == "prefill":
        if seq_lens is None:
            cache = _cache_write(cache, k, v, positions, dims.quant_kv)
        else:
            # bucketed prefill: pads attend nothing (causal mask, pad
            # positions exceed every real q position) but must not WRITE -
            # clamp their k/v/positions onto the last real token instead.
            (kc, vc), pos_c = _clamp_padded((k, v), positions, seq_lens)
            cache = _cache_write(cache, kc, vc, pos_c, dims.quant_kv)
        o = chunked_attention(q, k, v, positions, positions, causal=causal,
                              window=dims.window, attn_softcap=dims.attn_softcap,
                              parallel_q=True)
        return lin(o.reshape(B, S, H * Dh), p["wo"]), cache

    # decode: S == 1.  With ``layer`` the cache is the whole layer stack,
    # carried through the layer scan: the token is written in place and
    # the layer is read where it lies (the fp path's dynamic slice fuses
    # into its dot).
    q1 = q[:, 0]                                            # (B, H, Dh)
    if "k_scale" in cache and dims.attn_softcap is None and dims.window is None:
        # the int8-KV flash-decode kernel (ref off-TPU) writes the token
        # into the stack itself, in the launch that attends it
        kq, ks, vq, vs = _quant_kv_token(k, v)
        slots = positions[:, 0] % cache["pos"].shape[-1]
        new = (slots, kq[:, 0], vq[:, 0], ks[:, 0], vs[:, 0])
        cache = _cache_advance(cache, positions, layer)
        kv = (cache["k"], cache["v"], cache["k_scale"], cache["v_scale"])
        length = _at_layer(cache["len"], layer)
        fused = is_quantized(p["wo"]) and not is_segment_view(p["wo"])
        o, kv = ops.decode_attend_i8kv(
            q1.astype(jnp.float32), *kv, length, layer=layer, new=new,
            wo_prologue=fused, pro_dtype=x.dtype if fused else None)
        cache = dict(cache, k=kv[0], v=kv[1], k_scale=kv[2], v_scale=kv[3])
        if fused:
            # the attend kernel's output stage also ran the wo projection's
            # PDQ prologue over the flattened row, so the quantized wo
            # costs one W8A8 launch instead of prologue+matmul
            o, o_q, s_x, s1, s2 = o
            y = ops.pdq_dense_from_prologue(
                o.reshape(B, 1, H * Dh).astype(x.dtype),
                o_q.reshape(B, 1, H * Dh),
                s_x.reshape(B, 1, 1), s1.reshape(B, 1, 1), s2.reshape(B, 1, 1),
                p["wo"], out_dtype=x.dtype)
            return y, cache
        o = o.astype(x.dtype)
    else:
        cache = _cache_write(cache, k, v, positions, dims.quant_kv, layer)
        one = {n: _at_layer(a, layer) for n, a in cache.items()}
        kf, vf = _cache_kv_float(one, x.dtype)
        o = decode_attention(q1, kf, vf, positions[:, 0], one["pos"],
                             window=dims.window, attn_softcap=dims.attn_softcap)
    return lin(o.reshape(B, 1, H * Dh), p["wo"]), cache


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder); no rope, bidirectional over memory
# ---------------------------------------------------------------------------


def cross_init(key, dims: AttnDims, dtype):
    return gqa_init(key, dims, dtype)


def cross_apply(p, dims: AttnDims, x, memory_kv, memory_mask=None):
    """x: (B, Sq, d); memory_kv: precomputed (k, v) each (B, Sm, Hkv, Dh)."""
    B, Sq, _ = x.shape
    H, Hkv, Dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    q = lin(x, p["wq"]).reshape(B, Sq, H, Dh)
    k, v = memory_kv
    Sm = k.shape[1]
    qpos = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    kpos = jnp.broadcast_to(jnp.arange(Sm)[None], (B, Sm))
    o = chunked_attention(q, k, v, qpos, kpos, causal=False, window=None)
    return lin(o.reshape(B, Sq, H * Dh), p["wo"])


def cross_memory(p, dims: AttnDims, memory):
    """Precompute cross-attention K/V from encoder output (B, Sm, d)."""
    B, Sm, _ = memory.shape
    Hkv, Dh = dims.n_kv_heads, dims.head_dim
    # wk/wv share the encoder memory input (wq reads the decoder stream, so
    # cross params group only this pair - see linops.CROSS_SIBLING_SETS)
    k, v = lin_grouped(memory, (p["wk"], p["wv"]))
    return k.reshape(B, Sm, Hkv, Dh), v.reshape(B, Sm, Hkv, Dh)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank q/kv with compressed KV cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLADims:
    d_model: int
    n_heads: int
    q_lora: int | None                 # None: one wq, no query compression
    kv_lora: int
    qk_nope: int
    qk_rope: int
    v_head: int
    rope_theta: float = 10_000.0
    yarn: YarnConfig | None = None

    @property
    def scale(self) -> float:
        """Softmax scale over the (qk_nope + qk_rope)-wide query-key dot."""
        return yarn_softmax_scale(self.qk_nope + self.qk_rope, self.yarn)


def mla_init(key, m: MLADims, dtype):
    ks = jax.random.split(key, 7)
    H = m.n_heads
    dq = H * (m.qk_nope + m.qk_rope)
    if m.q_lora is None:
        q = {"wq": dense_init(ks[0], m.d_model, dq, dtype)}
    else:
        q = {"wq_a": dense_init(ks[0], m.d_model, m.q_lora, dtype),
             "q_norm": jnp.zeros((m.q_lora,), dtype),
             "wq_b": dense_init(ks[1], m.q_lora, dq, dtype)}
    return {
        **q,
        "wkv_a": dense_init(ks[2], m.d_model, m.kv_lora + m.qk_rope, dtype),
        "kv_norm": jnp.zeros((m.kv_lora,), dtype),
        "wk_b": dense_init(ks[3], m.kv_lora, H * m.qk_nope, dtype),
        "wv_b": dense_init(ks[4], m.kv_lora, H * m.v_head, dtype),
        "wo": dense_init(ks[5], H * m.v_head, m.d_model, dtype),
    }


def mla_init_cache(m: MLADims, batch: int, max_len: int, dtype):
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora), dtype),
        "krope": jnp.zeros((batch, max_len, m.qk_rope), dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def _mla_qkv(p, m: MLADims, x, positions):
    B, S, _ = x.shape
    H = m.n_heads
    # the two input-side projections share x -> one grouped call
    if "wq" in p:
        q, kv = lin_grouped(x, (p["wq"], p["wkv_a"]))
    else:
        qa, kv = lin_grouped(x, (p["wq_a"], p["wkv_a"]))
        q = lin(rms_norm(qa, p["q_norm"]), p["wq_b"])
    q = q.reshape(B, S, H, m.qk_nope + m.qk_rope)
    q_nope, q_rope = q[..., : m.qk_nope], q[..., m.qk_nope:]
    q_rope = apply_rope(q_rope, positions, m.rope_theta, m.yarn)
    ckv = rms_norm(kv[..., : m.kv_lora], p["kv_norm"])
    krope = apply_rope(kv[..., None, m.kv_lora:], positions, m.rope_theta,
                       m.yarn)[..., 0, :]
    return q_nope, q_rope, ckv, krope


def mla_apply(p, m: MLADims, x, positions, *, mode: str, cache=None,
              seq_lens=None, chunked: bool = False):
    B, S, _ = x.shape
    H = m.n_heads
    q_nope, q_rope, ckv, krope = _mla_qkv(p, m, x, positions)

    if mode == "prefill" and chunked:
        # chunked-prefill continuation: land this chunk's compressed stream
        # in the cache, then run the EXPANDED attention path against the
        # whole buffer - wk_b/wv_b re-expand the stored ckv, which holds
        # exactly the values an unchunked prefill computed, so the per-head
        # k/v match the unchunked path (the absorbed decode formulation
        # would associate the matmuls differently).
        assert cache is not None and seq_lens is not None
        (ckv_c, krope_c), pos_c = _clamp_padded((ckv, krope), positions,
                                                seq_lens)
        bidx = jnp.arange(B)[:, None]
        cache = dict(cache)
        cache["ckv"] = cache["ckv"].at[bidx, pos_c].set(
            ckv_c.astype(cache["ckv"].dtype))
        cache["krope"] = cache["krope"].at[bidx, pos_c].set(
            krope_c.astype(cache["krope"].dtype))
        cache["pos"] = cache["pos"].at[bidx, pos_c].set(pos_c)
        cache["len"] = jnp.maximum(cache["len"], pos_c[:, -1] + 1)
        Sb = cache["ckv"].shape[1]
        k_nope = lin(cache["ckv"], p["wk_b"]).reshape(B, Sb, H, m.qk_nope)
        v = lin(cache["ckv"], p["wv_b"]).reshape(B, Sb, H, m.v_head)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(cache["krope"][:, :, None],
                                      (B, Sb, H, m.qk_rope))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        with jax.named_scope("mla_attend"):
            o = chunked_attention(q, k, v, positions,
                                  _valid_k_pos(cache["pos"]), causal=True,
                                  q_chunk=S, kv_chunk=Sb, scale=m.scale)
        return lin(o.reshape(B, S, H * m.v_head), p["wo"]), cache

    if mode in ("train", "prefill"):
        # expanded path: materialize per-head k/v from the compressed stream
        k_nope = lin(ckv, p["wk_b"]).reshape(B, S, H, m.qk_nope)
        v = lin(ckv, p["wv_b"]).reshape(B, S, H, m.v_head)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope[:, :, None], (B, S, H, m.qk_rope))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        with jax.named_scope("mla_attend"):
            o = chunked_attention(q, k, v, positions, positions, causal=True,
                                  scale=m.scale)
        y = lin(o.reshape(B, S, H * m.v_head), p["wo"])
        if mode == "train":
            return y, None
        ckv_c, krope_c, pos_c = ckv, krope, positions
        if seq_lens is not None:   # bucketed prefill: no pad entries (see _clamp_padded)
            (ckv_c, krope_c), pos_c = _clamp_padded((ckv, krope), positions,
                                                    seq_lens)
        bidx = jnp.arange(B)[:, None]
        cache = dict(cache)
        cache["ckv"] = cache["ckv"].at[bidx, pos_c].set(ckv_c.astype(cache["ckv"].dtype))
        cache["krope"] = cache["krope"].at[bidx, pos_c].set(krope_c.astype(cache["krope"].dtype))
        cache["pos"] = cache["pos"].at[bidx, pos_c].set(pos_c)
        cache["len"] = jnp.maximum(cache["len"], pos_c[:, -1] + 1)
        return y, cache

    # decode (absorbed): attention runs entirely in the compressed space.
    bidx = jnp.arange(B)[:, None]
    cache = dict(cache)
    cache["ckv"] = cache["ckv"].at[bidx, positions].set(ckv.astype(cache["ckv"].dtype))
    cache["krope"] = cache["krope"].at[bidx, positions].set(krope.astype(cache["krope"].dtype))
    cache["pos"] = cache["pos"].at[bidx, positions].set(positions)
    cache["len"] = jnp.maximum(cache["len"], positions[:, -1] + 1)

    with jax.named_scope("mla_attend"):
        o = _mla_absorbed_attend(p, m, q_nope[:, 0], q_rope[:, 0], cache,
                                 positions, x.dtype)
    return lin(o.reshape(B, 1, H * m.v_head), p["wo"]), cache


def _dequant(w) -> jax.Array:
    """An fp (or dequantized f32) view of a projection weight."""
    if is_quantized(w):
        return w["q"].astype(jnp.float32) * w["scale"][None, :]
    return w


def _mla_absorbed_attend(p, m: MLADims, q_nope, q_rope, cache, positions,
                         dtype):
    """One decode token's attention in the compressed space: wk_b folds
    into the query, wv_b into the output.  q_nope (B, H, qk_nope), q_rope
    (B, H, qk_rope); returns (B, H, v_head)."""
    H = m.n_heads
    wk_b = _dequant(p["wk_b"]).reshape(m.kv_lora, H, m.qk_nope)
    q_eff = jnp.einsum("bhn,rhn->bhr", q_nope, wk_b)                # (B, H, r)
    s = (jnp.einsum("bhr,bsr->bhs", q_eff, cache["ckv"],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", q_rope, cache["krope"],
                      preferred_element_type=jnp.float32)) * m.scale
    ok = (cache["pos"] <= positions[:, :1]) & (cache["pos"] >= 0)
    s = jnp.where(ok[:, None, :], s, NEG)
    prob = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", prob.astype(cache["ckv"].dtype), cache["ckv"],
                     preferred_element_type=jnp.float32)            # (B, H, r)
    wv_b = _dequant(p["wv_b"]).reshape(m.kv_lora, H, m.v_head)
    return jnp.einsum("bhr,rhv->bhv", ctx.astype(dtype), wv_b)
