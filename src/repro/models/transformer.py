"""Decoder-only LM assembly: head + scanned pattern blocks + tail.

The repeated pattern blocks run under lax.scan over stacked params (compile
time stays O(pattern), not O(n_layers)); head/tail layers are unrolled.
Caches are threaded through the scan as xs/ys, except in decode, where the
layer-stacked GQA K/V caches (fp or int8, global or a local-window ring)
ride the scan carry with the layer index as xs: each layer writes its
token in place at (layer, row, slot) and reads its layer where it lies, so
no step slices, writes back or copies a whole layer of cache.  MLA latent,
SSM and cross-attention caches keep xs/ys.  ``mode`` is one of
'train' | 'prefill' | 'decode'.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.ad_checkpoint import checkpoint_name

from repro.kernels import ops

from . import context
from .attention import (AttnDims, gqa_apply, gqa_init, init_cache,
                        is_gqa_cache, mla_apply, mla_init, mla_init_cache)
from .config import ArchConfig
from .layers import embed_init, mlp_apply, mlp_init, rms_norm, softcap
from .moe import moe_ffn_dense_masked, moe_ffn_tokens, moe_init
from .ssm import ssm_apply, ssm_init, ssm_init_cache

MLADimsFields = ("d_model", "n_heads", "q_lora", "kv_lora", "qk_nope", "qk_rope",
                 "v_head", "rope_theta")


def _attn_dims(cfg: ArchConfig, kind: str) -> AttnDims:
    return AttnDims(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd, rope_theta=cfg.rope_theta, attn_softcap=cfg.attn_softcap,
        window=cfg.window if kind == "local" else None, quant_kv=cfg.quant_kv)


def _mla_dims(cfg: ArchConfig):
    from .attention import MLADims
    m = cfg.mla
    return MLADims(d_model=cfg.d_model, n_heads=cfg.n_heads, q_lora=m.q_lora,
                   kv_lora=m.kv_lora, qk_nope=m.qk_nope, qk_rope=m.qk_rope,
                   v_head=m.v_head, rope_theta=cfg.rope_theta)


def _is_moe(cfg: ArchConfig, kind: str) -> bool:
    return cfg.moe is not None and kind != "global_dense"


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------


def layer_init(key, cfg: ArchConfig, kind: str, dtype):
    if kind == "mamba":
        k1, = jax.random.split(key, 1)
        return {"norm": jnp.zeros((cfg.d_model,), dtype),
                "ssm": ssm_init(k1, cfg.ssm, dtype)}
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mla is not None and kind in ("global", "global_dense"):
        attn = mla_init(k1, _mla_dims(cfg), dtype)
    else:
        attn = gqa_init(k1, _attn_dims(cfg, kind), dtype)
    p = {"attn_norm": jnp.zeros((cfg.d_model,), dtype), "attn": attn,
         "ffn_norm": jnp.zeros((cfg.d_model,), dtype)}
    if _is_moe(cfg, kind):
        p["ffn"] = moe_init(k2, cfg.d_model, cfg.moe, dtype)
    else:
        d_ff = cfg.d_ff if kind != "global_dense" else (cfg.moe.d_ff_dense
                                                        if cfg.moe else cfg.d_ff)
        p["ffn"] = mlp_init(k2, cfg.d_model, d_ff or cfg.d_ff, dtype)
    if cfg.family == "encdec":
        p["cross_norm"] = jnp.zeros((cfg.d_model,), dtype)
        p["cross"] = gqa_init(k3, _attn_dims(cfg, "global"), dtype)
    return p


def layer_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int, dtype,
                mem_len: int = 0):
    if kind == "mamba":
        return ssm_init_cache(cfg.ssm, batch, dtype)
    if cfg.mla is not None and kind in ("global", "global_dense"):
        return mla_init_cache(_mla_dims(cfg), batch, max_len, dtype)
    c = init_cache(_attn_dims(cfg, kind), batch, max_len, dtype)
    if cfg.family == "encdec":
        Sm = max(mem_len, 1)
        c["cross_k"] = jnp.zeros((batch, Sm, cfg.n_kv_heads, cfg.hd), dtype)
        c["cross_v"] = jnp.zeros((batch, Sm, cfg.n_kv_heads, cfg.hd), dtype)
    return c


def _apply_ffn(p_ffn, cfg: ArchConfig, kind: str, h: jax.Array, mode: str,
               seq_lens=None):
    """Returns (y, aux).  ``seq_lens`` (B,) marks the valid prefix of
    right-padded bucketed-prefill rows: pad tokens are masked out of MoE
    routing so they cannot claim expert capacity (DESIGN.md Sec. 4)."""
    if not _is_moe(cfg, kind):
        return mlp_apply(p_ffn, h), jnp.float32(0.0)
    B, S, d = h.shape
    x2 = h.reshape(B * S, d)
    mask = None
    if seq_lens is not None:
        mask = (jax.lax.broadcasted_iota(jnp.int32, (B, S), 1)
                < seq_lens[:, None]).reshape(B * S)
    ctx = context.get_context()
    routed = {k: p_ffn[k] for k in ("router", "we_gate", "we_up", "we_down")}
    use_ep = ctx is not None and mode in ("train", "prefill")
    if ctx is None:
        fn = moe_ffn_tokens if mode in ("train", "prefill") else moe_ffn_dense_masked
        y, aux = fn(routed, x2, cfg.moe, axis_name=None, token_mask=mask)
    elif use_ep:
        def f(rp, xt, mt):
            yy, ax = moe_ffn_tokens(rp, xt, cfg.moe, axis_name=ctx.expert_axis,
                                    token_mask=mt)
            return yy, jax.lax.pmean(ax, ctx.token_axes)
        if mask is None:
            mask = jnp.ones((B * S,), bool)
        y, aux = jax.shard_map(
            f, mesh=ctx.mesh,
            in_specs=(context.moe_param_specs(routed), P(ctx.token_axes, None),
                      P(ctx.token_axes)),
            out_specs=(P(ctx.token_axes, None), P()),
            check_vma=False,
        )(routed, x2, mask)
    else:
        def f(rp, xt, mt):
            yy, ax = moe_ffn_dense_masked(rp, xt, cfg.moe,
                                          axis_name=ctx.expert_axis,
                                          token_mask=mt)
            return yy, jax.lax.pmean(ax, ctx.data_axes)
        if mask is None:
            mask = jnp.ones((B * S,), bool)
        y, aux = jax.shard_map(
            f, mesh=ctx.mesh,
            in_specs=(context.moe_param_specs(routed), P(ctx.data_axes, None),
                      P(ctx.data_axes)),
            out_specs=(P(ctx.data_axes, None), P()),
            check_vma=False,
        )(routed, x2, mask)
    y = checkpoint_name(y, "moe_out")
    y = y.reshape(B, S, d)
    if cfg.moe.n_shared:
        y = y + mlp_apply(p_ffn["shared"], h)
    if cfg.moe.dense_residual:
        y = y + mlp_apply(p_ffn["dense"], h)
    return y, aux


def layer_apply(p, cfg: ArchConfig, kind: str, h, positions, *, mode: str,
                cache=None, memory=None, causal: bool = True, seq_lens=None,
                chunked: bool = False, layer=None):
    """Returns (h, new_cache, aux).  ``seq_lens`` (B,) marks the valid
    prefix of right-padded bucketed-prefill rows (None = no padding);
    ``chunked`` marks a chunked-prefill continuation (the cache rows
    already hold earlier chunks, which attention must see); ``layer``
    marks a GQA ``cache`` as the whole layer stack, of which this is
    layer ``layer`` (decode)."""
    eps = cfg.norm_eps
    if kind == "mamba":
        y, new_cache = ssm_apply(p["ssm"], cfg.ssm, rms_norm(h, p["norm"], eps),
                                 mode=mode, cache=cache, seq_lens=seq_lens)
        return h + y, new_cache, jnp.float32(0.0)

    xin = rms_norm(h, p["attn_norm"], eps)
    if cfg.mla is not None and kind in ("global", "global_dense"):
        a, new_cache = mla_apply(p["attn"], _mla_dims(cfg), xin, positions,
                                 mode=mode, cache=cache, seq_lens=seq_lens,
                                 chunked=chunked)
    else:
        a, new_cache = gqa_apply(p["attn"], _attn_dims(cfg, kind), xin, positions,
                                 mode=mode, cache=cache, causal=causal,
                                 seq_lens=seq_lens, chunked=chunked,
                                 layer=layer)
    a = checkpoint_name(a, "attn_out")
    h = h + a

    if cfg.family == "encdec":
        from .attention import cross_apply, cross_memory
        dims = _attn_dims(cfg, "global")
        if mode == "train":
            mem_kv = cross_memory(p["cross"], dims, memory)
        elif mode == "prefill":
            mem_kv = cross_memory(p["cross"], dims, memory)
            new_cache = dict(new_cache)
            new_cache["cross_k"], new_cache["cross_v"] = mem_kv
        else:
            mem_kv = (cache["cross_k"], cache["cross_v"])
            new_cache = dict(new_cache)
            new_cache["cross_k"], new_cache["cross_v"] = mem_kv
        c = cross_apply(p["cross"], dims, rms_norm(h, p["cross_norm"], eps), mem_kv)
        h = h + c

    f, aux = _apply_ffn(p["ffn"], cfg, kind, rms_norm(h, p["ffn_norm"], eps),
                        mode, seq_lens=seq_lens if mode == "prefill" else None)
    return h + f, new_cache, aux


# ---------------------------------------------------------------------------
# full stacks
# ---------------------------------------------------------------------------


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def lm_init(key, cfg: ArchConfig):
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {"embed": embed_init(keys[0], cfg.vocab, cfg.d_model, dtype)}
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    params["head"] = tuple(
        layer_init(k, cfg, kind, dtype)
        for k, kind in zip(jax.random.split(keys[1], max(len(cfg.head), 1)), cfg.head))
    params["tail"] = tuple(
        layer_init(k, cfg, kind, dtype)
        for k, kind in zip(jax.random.split(keys[2], max(len(cfg.tail), 1)), cfg.tail))
    if "shared" in cfg.pattern or "shared" in cfg.head or "shared" in cfg.tail:
        params["shared_block"] = layer_init(keys[3], cfg, "global", dtype)

    def one_block(k):
        ks = jax.random.split(k, len(cfg.pattern))
        return tuple(
            layer_init(ks[j], cfg, kind, dtype) if kind != "shared" else {}
            for j, kind in enumerate(cfg.pattern))

    params["blocks"] = jax.vmap(one_block)(jax.random.split(keys[4], cfg.n_blocks))

    if cfg.family == "encdec":
        def enc_block(k):
            return layer_init(k, dataclass_enc(cfg), "global", dtype)
        params["enc_blocks"] = jax.vmap(enc_block)(
            jax.random.split(keys[5], cfg.enc_layers))
        params["enc_norm"] = jnp.zeros((cfg.d_model,), dtype)
    return params


def dataclass_enc(cfg: ArchConfig) -> ArchConfig:
    """Encoder layers: plain bidirectional attention + dense FFN."""
    import dataclasses
    return dataclasses.replace(cfg, family="lm", moe=None, mla=None)


def lm_init_caches(cfg: ArchConfig, batch: int, max_len: int, mem_len: int = 0):
    dtype = _dtype(cfg)
    caches: dict[str, Any] = {
        "head": tuple(layer_cache(cfg, k, batch, max_len, dtype, mem_len) for k in cfg.head),
        "tail": tuple(layer_cache(cfg, k, batch, max_len, dtype, mem_len) for k in cfg.tail),
    }

    def stack(tree):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_blocks, *x.shape)).copy(), tree)

    caches["blocks"] = tuple(
        stack(layer_cache(cfg, kind if kind != "shared" else "global",
                          batch, max_len, dtype, mem_len))
        for kind in cfg.pattern)
    return caches


def _encoder_apply(params, cfg: ArchConfig, frames: jax.Array):
    """Bidirectional encoder over stub frame embeddings (B, Sm, d)."""
    ecfg = dataclass_enc(cfg)
    B, Sm, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(Sm)[None], (B, Sm))
    h = frames

    def body(carry, block_p):
        hh = carry
        hh, _, _ = layer_apply(block_p, ecfg, "global", hh, positions,
                               mode="train", cache=None, causal=False)
        return hh, ()

    h, _ = ops.pdq_telemetry_scan(body, h, params["enc_blocks"])
    return rms_norm(h, params["enc_norm"], cfg.norm_eps)


def lm_apply(params, cfg: ArchConfig, *, tokens=None, positions, mode: str,
             caches=None, frames=None, patches=None, seq_lens=None,
             chunked: bool = False):
    """Returns (h_final, new_caches, aux_sum).

    tokens: (B, S) int32 (text); patches: (B, Pimg, d) stub embeddings
    prepended to the sequence (VLM); frames: (B, Sm, d) encoder input
    (encdec family); seq_lens: (B,) valid-prefix lengths (in full-sequence
    index space, patches included) when rows are right-padded to a bucket
    length - pad entries then never reach any cache or recurrent state.
    ``chunked`` marks a chunked-prefill continuation: ``positions`` are
    then absolute (offset by the tokens already landed in ``caches``) and
    attention runs against the cache buffer (see serve/engine.py).
    """
    dtype = _dtype(cfg)
    from .layers import embed_apply
    h = embed_apply(params["embed"], tokens).astype(dtype)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, dtype)
    if patches is not None:
        h = jnp.concatenate([patches.astype(dtype), h], axis=1)

    memory = None
    if cfg.family == "encdec":
        assert frames is not None or (caches is not None and mode == "decode")
        if frames is not None:
            memory = _encoder_apply(params, cfg, frames.astype(dtype))

    aux_total = jnp.float32(0.0)
    new_caches: dict[str, Any] = {"head": [], "tail": [], "blocks": None}

    for i, kind in enumerate(cfg.head):
        c = caches["head"][i] if caches else None
        h, nc, aux = layer_apply(params["head"][i], cfg, kind, h, positions,
                                 mode=mode, cache=c, memory=memory,
                                 seq_lens=seq_lens, chunked=chunked)
        new_caches["head"].append(nc)
        aux_total += aux

    shared_p = params.get("shared_block")
    # decode carries each pattern position's stacked GQA K/V cache through
    # the scan and updates it in place (module docstring); every other
    # cache rides xs/ys, one layer's slice per iteration
    blocks_c = caches["blocks"] if caches else (None,) * len(cfg.pattern)
    in_place = tuple(mode == "decode" and c is not None and is_gqa_cache(c)
                     for c in blocks_c)
    stack = tuple(c if ip else None for c, ip in zip(blocks_c, in_place))
    xs_c = tuple(None if ip else c for c, ip in zip(blocks_c, in_place))

    def block_body(carry, xs):
        (hh, aux_acc), stack = carry
        block_p, block_c, layer = xs
        ncs, stack = [], list(stack)
        for j, kind in enumerate(cfg.pattern):
            pj = shared_p if kind == "shared" else block_p[j]
            ip = in_place[j]
            cj = stack[j] if ip else block_c[j]
            hh, ncj, aux = layer_apply(pj, cfg, kind if kind != "shared" else "global",
                                       hh, positions, mode=mode, cache=cj,
                                       memory=memory, seq_lens=seq_lens,
                                       chunked=chunked,
                                       layer=layer if ip else None)
            if ip:
                stack[j], ncj = ncj, None
            ncs.append(ncj if ncj is not None else ())
            aux_acc = aux_acc + aux
        return ((hh, aux_acc), tuple(stack)), tuple(ncs)

    body = block_body
    if mode == "train" and cfg.remat == "full":
        body = jax.checkpoint(block_body, prevent_cse=False)
    elif mode == "train" and cfg.remat == "save_heavy":
        policy = jax.checkpoint_policies.save_only_these_names(
            "moe_out", "attn_out")
        body = jax.checkpoint(block_body, prevent_cse=False, policy=policy)

    xs = (params["blocks"], xs_c, jnp.arange(cfg.n_blocks, dtype=jnp.int32))
    ((h, aux_total), stack), blocks_nc = ops.pdq_telemetry_scan(
        body, ((h, aux_total), stack), xs)
    new_caches["blocks"] = tuple(st if ip else nc for st, nc, ip
                                 in zip(stack, blocks_nc, in_place))

    for i, kind in enumerate(cfg.tail):
        c = caches["tail"][i] if caches else None
        h, nc, aux = layer_apply(params["tail"][i], cfg, kind, h, positions,
                                 mode=mode, cache=c, memory=memory,
                                 seq_lens=seq_lens, chunked=chunked)
        new_caches["tail"].append(nc)
        aux_total += aux

    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    new_caches["head"] = tuple(new_caches["head"])
    new_caches["tail"] = tuple(new_caches["tail"])
    return h, (new_caches if mode != "train" else None), aux_total


def lm_logits(params, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    logits = h @ params["embed"]["embedding"].T.astype(h.dtype)
    return softcap(logits.astype(jnp.float32), cfg.logit_softcap)
