"""The dense decoder family: the program's ``stablelm`` block, one scanned
stack of layers.  A configuration file without a ``family`` key is this.

Everything the harness needs of one architecture, from its configuration
file alone:

  arch_config(config)           the program's ``ArchConfig``, every width
                                taken from the file
  make_params(config, seed)     random weights on the device, in one jitted
                                call, in the program's tree layout
  reference_logits(config, bits)  the plain forward: ``jax.numpy``, float32,
                                ``Precision.HIGHEST``; ``bits`` > 0 rounds as
                                the control does
  prefill_flops(config, p)      model FLOPs of one prompt of ``p`` tokens
  decode_flops(config, context) model FLOPs of one output token

Weights.  The tree (``weights.check_layout`` holds the program to it):

  embed/embedding (V, d); final_norm (d,); head (); tail ();
  blocks[0]: attn_norm, ffn_norm (L, d); attn/wq, wk, wv (L, d, H*Dh),
             attn/wo (L, H*Dh, d); ffn/w_gate, w_up (L, d, F), ffn/w_down (L, F, d)

Projections are uniform in +-sqrt(3 / fan_in) (unit output variance), the
embedding is normal with standard deviation 0.02, and the norm gains (the
model scales by 1 + gain) are normal with standard deviation 0.1, so that a
norm applied wrongly shows in the logits.

Reference.  The block is the one the configuration states (its ``assumed``
list names where it departs from the published model): RMSNorm with gain
``1 + w``, rotary embedding on the whole head (halves rotated), causal
softmax attention, SwiGLU, the output head tied to the embedding.  The
control (``bits`` > 0) rounds every projection's weights (per output
channel) and inputs (per token), and the attention keys and values (per
token and head), to ``bits``-bit integers.

FLOPs.  The arithmetic of ``launch/model_flops.py`` (2 x the matmul
parameters per token, plus causal attention scores and values, 4 x layers x
heads x head_dim per query-key pair), kept here so a change to the program
cannot change the yardstick.  The output head counts only where a token is
sampled: once per prompt, once per output token.
"""
from __future__ import annotations

import dataclasses
import functools

from chipbench.reference import fq


def dims(config: dict) -> dict:
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {"L": int(config["num_hidden_layers"]), "d": d, "H": h,
            "Hkv": int(config["num_key_value_heads"]), "Dh": d // h,
            "F": int(config["intermediate_size"]),
            "V": int(config["vocab_size"]), "dtype": config["torch_dtype"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["norm_eps"])}


# ------------------------------------------------------------ the program
def arch_config(config: dict):
    """The program's model configuration, with every width the file states."""
    from repro.configs import get_config
    base = get_config(config["arch"])
    m = {"n_layers": config["num_hidden_layers"],
         "d_model": config["hidden_size"],
         "n_heads": config["num_attention_heads"],
         "n_kv_heads": config["num_key_value_heads"],
         "head_dim": config["hidden_size"] // config["num_attention_heads"],
         "d_ff": config["intermediate_size"],
         "vocab": config["vocab_size"],
         "rope_theta": float(config["rope_theta"]),
         "norm_eps": float(config["norm_eps"]),
         "dtype": config["torch_dtype"],
         "quant_kv": config["serve"]["kv"]}
    return dataclasses.replace(base, **m).validate()


# ---------------------------------------------------------------- weights
@functools.lru_cache(maxsize=None)
def _maker(L, d, H, Hkv, Dh, F, V, dtype):
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)

    def uni(k, shape, fan_in):
        s = (3.0 / fan_in) ** 0.5
        return jax.random.uniform(k, shape, jnp.float32, -s, s).astype(dt)

    def make(key):
        ks = jax.random.split(key, 11)
        block = {
            "attn_norm": (0.1 * jax.random.normal(ks[0], (L, d))).astype(dt),
            "attn": {"wq": uni(ks[1], (L, d, H * Dh), d),
                     "wk": uni(ks[2], (L, d, Hkv * Dh), d),
                     "wv": uni(ks[3], (L, d, Hkv * Dh), d),
                     "wo": uni(ks[4], (L, H * Dh, d), H * Dh)},
            "ffn_norm": (0.1 * jax.random.normal(ks[5], (L, d))).astype(dt),
            "ffn": {"w_gate": uni(ks[6], (L, d, F), d),
                    "w_up": uni(ks[7], (L, d, F), d),
                    "w_down": uni(ks[8], (L, F, d), F)},
        }
        return {"embed": {"embedding": (0.02 * jax.random.normal(
                    ks[9], (V, d))).astype(dt)},
                "final_norm": (0.1 * jax.random.normal(ks[10], (d,))).astype(dt),
                "head": (), "tail": (), "blocks": (block,)}

    return jax.jit(make)


def make_params(config: dict, seed: int):
    import jax
    m = dims(config)
    make = _maker(m["L"], m["d"], m["H"], m["Hkv"], m["Dh"], m["F"], m["V"],
                  m["dtype"])
    return jax.block_until_ready(make(jax.random.PRNGKey(seed % (1 << 32))))


# -------------------------------------------------------------- reference
def reference_logits(config: dict, bits: int):
    """``logits(params, tokens) -> (B, S, V)`` float32; the same function
    object for the same widths and ``bits``."""
    m = dims(config)
    return _logits(m["L"], m["d"], m["H"], m["Hkv"], m["Dh"], m["F"], m["V"],
                   m["theta"], m["eps"], int(bits))


@functools.lru_cache(maxsize=None)
def _logits(L, d, H, Hkv, Dh, F, V, theta, eps, bits):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def lin(x, w):
        w = w.astype(f32)
        if bits:
            x, w = fq(x, bits, -1), fq(w, bits, 0)
        return jnp.einsum("bsk,kn->bsn", x, w, precision=hi)

    def rms(x, g):
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                * (1.0 + g.astype(f32)))

    def rope(x, pos):
        freqs = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=f32) / Dh))
        ang = pos[:, None].astype(f32) * freqs               # (S, Dh/2)
        sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def logits(params, tokens):
        B, S = tokens.shape
        pos = jnp.arange(S)
        h = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(f32)
        causal = pos[:, None] >= pos[None, :]

        def layer(h, p):
            a, f = p["attn"], p["ffn"]
            x = rms(h, p["attn_norm"])
            q = rope(lin(x, a["wq"]).reshape(B, S, H, Dh), pos)
            k = rope(lin(x, a["wk"]).reshape(B, S, Hkv, Dh), pos)
            v = lin(x, a["wv"]).reshape(B, S, Hkv, Dh)
            if bits:
                k, v = fq(k, bits, -1), fq(v, bits, -1)
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) * Dh ** -0.5
            s = jnp.where(causal, s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                           precision=hi)
            h = h + lin(o.reshape(B, S, H * Dh), a["wo"])
            x = rms(h, p["ffn_norm"])
            g = lin(x, f["w_gate"])
            u = lin(x, f["w_up"])
            return h + lin(jax.nn.silu(g) * u, f["w_down"]), None

        h, _ = jax.lax.scan(layer, h, params["blocks"][0])
        h = rms(h, params["final_norm"])
        emb = params["embed"]["embedding"].astype(f32)
        return jnp.einsum("bsd,vd->bsv", h, emb, precision=hi)

    return logits


# ------------------------------------------------------------------ FLOPs
def _flop_dims(cfg):
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    hkv = int(cfg["num_key_value_heads"])
    dh = d // h
    f = int(cfg["intermediate_size"])
    n_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    return int(cfg["num_hidden_layers"]), n_layer, h * dh, d * int(cfg["vocab_size"])


def prefill_flops(cfg, prompt_len: int) -> float:
    L, n_layer, hd, head = _flop_dims(cfg)
    p = int(prompt_len)
    return (2.0 * L * n_layer * p + 4.0 * L * hd * p * (p + 1) / 2
            + 2.0 * head)


def decode_flops(cfg, context: int) -> float:
    """One output token whose query attends ``context`` keys."""
    L, n_layer, hd, head = _flop_dims(cfg)
    return 2.0 * L * n_layer + 4.0 * L * hd * int(context) + 2.0 * head
