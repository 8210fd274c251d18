"""Shared neural-net building blocks (pure JAX, pytree params).

Params are nested dicts; leaf names are the contract the sharding rules in
``repro.distributed.sharding`` key on - do not rename casually.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .linops import _common_group, is_quantized, is_segment_view, lin, lin_grouped


def uniform_init(key, shape, scale, dtype):
    return jax.random.uniform(key, shape, dtype=jnp.float32, minval=-scale,
                              maxval=scale).astype(dtype)


def dense_init(key, d_in, d_out, dtype):
    return uniform_init(key, (d_in, d_out), (3.0 / d_in) ** 0.5, dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (``rope_scaling`` of type "yarn")."""
    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(scale: float, mscale: float) -> float:
    """m(s, a) = 0.1 a ln s + 1 (1 for s <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_ramp_bounds(dim: int, base: float, y: YarnConfig) -> tuple[int, int]:
    """(low, high): the rotary pairs below ``low`` keep their frequency,
    those from ``high`` on are interpolated by ``factor``."""
    def corr(r):
        return dim * math.log(y.original_max / (2 * math.pi * r)) / (
            2 * math.log(base))
    return (max(math.floor(corr(y.beta_fast)), 0),
            min(math.ceil(corr(y.beta_slow)), dim - 1))


def yarn_freqs(dim: int, base: float, y: YarnConfig) -> jax.Array:
    """The (dim/2,) YaRN inverse frequencies: a linear ramp between the
    plain frequencies and those divided by ``factor``."""
    extra = rope_freqs(dim, base)
    low, high = yarn_ramp_bounds(dim, base, y)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / y.factor * ramp + extra * (1.0 - ramp)


def yarn_softmax_scale(head_dim: int, y: YarnConfig | None) -> float:
    """head_dim^-0.5, times m(factor, mscale_all_dim)^2 under YaRN."""
    m = 1.0 if y is None else yarn_mscale(y.factor, y.mscale_all_dim)
    return head_dim ** -0.5 * m * m


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn: YarnConfig | None = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq).  ``yarn``
    swaps in YaRN's frequencies and scales cos and sin by
    m(factor, mscale) / m(factor, mscale_all_dim)."""
    dh = x.shape[-1]
    if yarn is None:
        freqs = rope_freqs(dh, theta)                          # (dh/2,)
    else:
        freqs = yarn_freqs(dh, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, dh/2)
    sin = jnp.sin(angles)[..., None, :]                        # (..., seq, 1, dh/2)
    cos = jnp.cos(angles)[..., None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        sin, cos = sin * m, cos * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------


def mlp_init(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def mlp_apply(p, x):
    # gate/up consume the same normed input: quantized params run ONE
    # prologue + ONE wide W8A8 matmul for the pair, and when w_down is
    # quantized too the gate/up matmul's epilogue also computes
    # silu(g) * u and w_down's PDQ prologue in-kernel (ops.pdq_mlp)
    grec = _common_group((p["w_gate"], p["w_up"]))
    if (grec is not None and is_quantized(p["w_down"])
            and not is_segment_view(p["w_down"])):
        return ops.pdq_mlp(x, grec, p["w_down"], out_dtype=x.dtype)
    g, u = lin_grouped(x, (p["w_gate"], p["w_up"]))
    return lin(jax.nn.silu(g) * u, p["w_down"])


# ---------------------------------------------------------------------------
# Embedding / unembedding with chunked cross-entropy
# ---------------------------------------------------------------------------


def embed_init(key, vocab, d_model, dtype):
    return {"embedding": 0.02 * jax.random.normal(key, (vocab, d_model), jnp.float32).astype(dtype)}


def embed_apply(p, tokens):
    return jnp.take(p["embedding"], tokens, axis=0)


def unembed_logits(p, h, softcap_val: float | None = None):
    logits = h @ p["embedding"].T
    return softcap(logits.astype(jnp.float32), softcap_val)


def chunked_xent_loss(
    embedding: jax.Array,       # (V, d)
    h: jax.Array,               # (B, S, d) final hidden states
    labels: jax.Array,          # (B, S) int32
    *,
    chunk: int = 512,
    logit_softcap: float | None = None,
) -> jax.Array:
    """Cross-entropy without materializing the full (B, S, V) logits.

    Scans over sequence chunks; each chunk's logits exist only inside the
    (rematerialized) scan body.  Essential at vocab >= 100k x seq 4k.
    """
    B, S, d = h.shape
    chunk = min(chunk, S)
    n_chunks = S // chunk
    h_c = h[:, : n_chunks * chunk].reshape(B, n_chunks, chunk, d).swapaxes(0, 1)
    y_c = labels[:, : n_chunks * chunk].reshape(B, n_chunks, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, xs):
        hc, yc = xs                                   # (B, chunk, d), (B, chunk)
        logits = softcap((hc @ embedding.T).astype(jnp.float32), logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold), ()

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (h_c, y_c))
    return total / (B * n_chunks * chunk)
