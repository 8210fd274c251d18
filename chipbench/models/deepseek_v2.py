"""The DeepSeek-V2 family: latent attention (MLA) without query compression,
YaRN rotary, leading dense layers, then layers of routed experts with shared
experts, of which this chip holds a share.

Everything the harness needs of the architecture, from its configuration
file alone (keys as in the published ``config.json``):

  arch_config(config)           the program's ``ArchConfig``, every width
                                taken from the file
  make_params(config, seed)     random weights on the device, in one jitted
                                call, in the program's tree layout
  reference_logits(config, bits)  the plain forward: ``jax.numpy``, float32,
                                ``Precision.HIGHEST``; ``bits`` > 0 rounds as
                                the control does
  prefill_flops(config, p)      model FLOPs of one prompt of ``p`` tokens
  decode_flops(config, context) model FLOPs of one output token

The share.  ``n_routed_experts`` counts the experts this chip holds, the
ids ``held_expert_offset`` on; ``n_routed_experts_published`` is the
router's width.  The router scores every expert and picks its top
``num_experts_per_tok``; an assignment to an expert held elsewhere adds
nothing, here and in the program alike.  Shared experts apply to every
token.

Weights.  The tree (``weights.check_layout`` holds the program to it), with
K = dense layers, L = the rest, H heads, r = kv_lora_rank, n/p/v the nope,
rope and value head widths, E held experts of width F, S shared width:

  embed/embedding (V, d); final_norm (d,); tail ();
  head[i] (one per dense layer): attn_norm, ffn_norm (d,); attn/wq
    (d, H(n+p)), wkv_a (d, r+p), kv_norm (r,), wk_b (r, H n), wv_b
    (r, H v), wo (H v, d); ffn/w_gate, w_up (d, I), w_down (I, d)
  blocks[0]: the same attention and norms stacked (L, ...); ffn/router
    (L, d, E_published) float32, we_gate, we_up (L, E, d, F), we_down
    (L, E, F, d), shared/w_gate, w_up (L, d, S), shared/w_down (L, S, d)

Projections and the router are uniform in +-sqrt(3 / fan_in), the
embedding is normal with standard deviation 0.02, the norm gains (applied
as 1 + gain) normal with standard deviation 0.1.  The routed experts' down
projections are E_published / k times wider (64 / 6): a random router's
top-k gates are each of the order of k / E_published, so a token's
assignment to a held expert adds about as much as the shared experts do,
and held experts computed wrongly (left out, taken at the wrong ids, or
with renormalised gates) show in the logits; at unit width they add a few
hundredths of the layer's output, under the rounding of int8.

Reference.  The expanded (not absorbed) attention: q = x wq split into
nope and rope parts, the latent c = RMSNorm(x wkv_a[:r]) and the shared
rope key x wkv_a[r:], per-head keys c wk_b (+ the rope key) and values
c wv_b, causal softmax at (n + p)^-1/2 m(factor, mscale_all_dim)^2.  YaRN
frequencies and the cos/sin factor m(factor, mscale) / m(factor,
mscale_all_dim) with m(s, a) = 0.1 a ln s + 1, rotary on halves.  Experts
are SwiGLU; the gates are the top-k softmax probabilities times
``routed_scaling_factor``, renormalised only if ``norm_topk_prob``.  The
output head is tied to the embedding.  The configuration's ``assumed``
names each departure from the published model.  The control (``bits`` > 0)
rounds every projection's weights (per output channel) and inputs (per
token), and the latent and rope key (per token), to ``bits``-bit integers;
the router stays float32, as the program keeps it.

FLOPs.  The share's work: 2 x the matmul parameters a token runs through
(attention counted in its expanded form, the router, the shared experts,
the dense layers' FFN, and of the held experts the top-k x held / published
experts a token uses under uniform routing: 6 x 8 / 64 = 0.75 of one),
plus causal attention, 2 x heads x (n + p + v) per query-key pair and
layer.  The output head counts only where a token is sampled.
"""
from __future__ import annotations

import dataclasses
import functools
import math

from chipbench.reference import fq

# what the program and the reference implement; anything else is refused
_FIXED = {"hidden_act": "silu", "scoring_func": "softmax",
          "topk_method": "greedy", "n_group": 1, "topk_group": 1,
          "moe_layer_freq": 1, "attention_bias": False}


def dims(config: dict) -> dict:
    for k, v in _FIXED.items():
        if config.get(k) != v:
            raise ValueError(f"{k}={config.get(k)!r}: only {v!r} is "
                             "implemented")
    if config.get("q_lora_rank") is not None:
        raise ValueError("q_lora_rank: only null (no query compression) is "
                         "implemented")
    rs = config["rope_scaling"]
    if rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {rs!r}: only yarn is implemented")
    L = int(config["num_hidden_layers"])
    K = int(config["first_k_dense_replace"])
    F = int(config["moe_intermediate_size"])
    return {"L": L - K, "K": K, "d": int(config["hidden_size"]),
            "H": int(config["num_attention_heads"]),
            "r": int(config["kv_lora_rank"]),
            "n": int(config["qk_nope_head_dim"]),
            "p": int(config["qk_rope_head_dim"]),
            "v": int(config["v_head_dim"]),
            "I": int(config["intermediate_size"]), "F": F,
            "S": int(config["n_shared_experts"]) * F,
            "E": int(config["n_routed_experts"]),
            "E_pub": int(config["n_routed_experts_published"]),
            "off": int(config.get("held_expert_offset", 0)),
            "k": int(config["num_experts_per_tok"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "route_scale": float(config["routed_scaling_factor"]),
            "V": int(config["vocab_size"]), "dtype": config["torch_dtype"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "yarn": (float(rs["factor"]),
                     int(rs["original_max_position_embeddings"]),
                     float(rs["beta_fast"]), float(rs["beta_slow"]),
                     float(rs["mscale"]), float(rs["mscale_all_dim"]))}


# ------------------------------------------------------------ the program
def arch_config(config: dict):
    """The program's model configuration, with every width the file states."""
    from repro.configs import get_config
    from repro.models.config import MLAConfig
    from repro.models.layers import YarnConfig
    m = dims(config)
    base = get_config(config["arch"])
    factor, orig, bf, bs, ms, ms_all = m["yarn"]
    moe = dataclasses.replace(
        base.moe, n_experts=m["E_pub"], top_k=m["k"], d_ff_expert=m["F"],
        n_shared=int(config["n_shared_experts"]), d_ff_dense=m["S"],
        router_scale=m["route_scale"], norm_topk=m["norm_topk"],
        n_held=m["E"], held_offset=m["off"])
    return dataclasses.replace(
        base, n_layers=m["K"] + m["L"], d_model=m["d"], n_heads=m["H"],
        n_kv_heads=int(config["num_key_value_heads"]), d_ff=m["I"],
        vocab=m["V"], head=("global_dense",) * m["K"], pattern=("global",),
        tail=(), mla=MLAConfig(q_lora=None, kv_lora=m["r"], qk_nope=m["n"],
                               qk_rope=m["p"], v_head=m["v"]),
        moe=moe, rope_theta=m["theta"],
        yarn=YarnConfig(factor=factor, original_max=orig, beta_fast=bf,
                        beta_slow=bs, mscale=ms, mscale_all_dim=ms_all),
        norm_eps=m["eps"], dtype=m["dtype"],
        quant_kv=config["serve"]["kv"]).validate()


# ---------------------------------------------------------------- weights
def _key(m: dict) -> tuple:
    return tuple(m[k] for k in ("L", "K", "d", "H", "r", "n", "p", "v", "I",
                                "F", "S", "E", "E_pub", "V", "dtype"))


@functools.lru_cache(maxsize=None)
def _maker(L, K, d, H, r, n, p, v, I, F, S, E, E_pub, V, dtype, top_k):
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)

    def uni(k, shape, fan_in, to=dt, wider=1.0):
        s = wider * (3.0 / fan_in) ** 0.5
        return jax.random.uniform(k, shape, jnp.float32, -s, s).astype(to)

    def gain(k, shape):
        return (0.1 * jax.random.normal(k, shape)).astype(dt)

    def attn(ks, lead):
        return {"wq": uni(ks[0], lead + (d, H * (n + p)), d),
                "wkv_a": uni(ks[1], lead + (d, r + p), d),
                "kv_norm": gain(ks[2], lead + (r,)),
                "wk_b": uni(ks[3], lead + (r, H * n), r),
                "wv_b": uni(ks[4], lead + (r, H * v), r),
                "wo": uni(ks[5], lead + (H * v, d), H * v)}

    def mlp(ks, lead, width):
        return {"w_gate": uni(ks[0], lead + (d, width), d),
                "w_up": uni(ks[1], lead + (d, width), d),
                "w_down": uni(ks[2], lead + (width, d), width)}

    def dense_layer(k):
        ks = jax.random.split(k, 11)
        return {"attn_norm": gain(ks[0], (d,)), "attn": attn(ks[1:7], ()),
                "ffn_norm": gain(ks[7], (d,)), "ffn": mlp(ks[8:11], (), I)}

    def make(key):
        ks = jax.random.split(key, 18)
        lead = (L,)
        block = {
            "attn_norm": gain(ks[0], (L, d)), "attn": attn(ks[1:7], lead),
            "ffn_norm": gain(ks[7], (L, d)),
            "ffn": {"router": uni(ks[8], (L, d, E_pub), d, jnp.float32),
                    "we_gate": uni(ks[9], (L, E, d, F), d),
                    "we_up": uni(ks[10], (L, E, d, F), d),
                    "we_down": uni(ks[11], (L, E, F, d), F,
                                   wider=E_pub / top_k),
                    "shared": mlp(ks[12:15], lead, S)},
        }
        head = tuple(dense_layer(k) for k in jax.random.split(ks[15], K))
        return {"embed": {"embedding": (0.02 * jax.random.normal(
                    ks[16], (V, d))).astype(dt)},
                "final_norm": gain(ks[17], (d,)),
                "head": head, "tail": (), "blocks": (block,)}

    return jax.jit(make)


def make_params(config: dict, seed: int):
    import jax
    m = dims(config)
    make = _maker(*_key(m), m["k"])
    return jax.block_until_ready(make(jax.random.PRNGKey(seed % (1 << 32))))


# -------------------------------------------------------------- reference
def yarn_tables(p: int, theta: float, yarn: tuple):
    """(inverse frequencies (p/2,), cos/sin factor, softmax factor) of YaRN
    as DeepSeek-V2's published modelling code computes them."""
    import numpy as np
    factor, orig, beta_fast, beta_slow, mscale, mscale_all = yarn

    def m(s, a):
        return 1.0 if s <= 1 else 0.1 * a * math.log(s) + 1.0

    def corr(rot):
        return p * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), p - 1)
    extra = 1.0 / theta ** (np.arange(0, p, 2, dtype=np.float64) / p)
    inter = extra / factor
    ramp = np.clip((np.arange(p // 2) - low) / (high - low or 1e-3), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)
    return (inv.astype(np.float32), m(factor, mscale) / m(factor, mscale_all),
            m(factor, mscale_all) ** 2)


def reference_logits(config: dict, bits: int):
    """``logits(params, tokens) -> (B, S, V)`` float32; the same function
    object for the same widths and ``bits``."""
    m = dims(config)
    return _logits(_key(m), m["off"], m["k"], m["norm_topk"],
                   m["route_scale"], m["theta"], m["eps"], m["yarn"],
                   int(bits))


@functools.lru_cache(maxsize=None)
def _logits(key, off, top_k, norm_topk, route_scale, theta, eps, yarn, bits):
    import jax
    import jax.numpy as jnp
    L, K, d, H, r, n, p, v, I, F, S, E, E_pub, V, _ = key
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    inv_freq, cs_scale, sm_scale = yarn_tables(p, theta, yarn)
    scale = (n + p) ** -0.5 * sm_scale

    def lin(x, w):
        w = w.astype(f32)
        if bits:
            x, w = fq(x, bits, -1), fq(w, bits, -2)
        return jnp.einsum("...k,kn->...n", x, w, precision=hi)

    def rms(x, g):
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                * (1.0 + g.astype(f32)))

    def rope(x, pos):                       # x (B, S, h, p)
        ang = pos[:, None].astype(f32) * inv_freq           # (S, p/2)
        sin = jnp.sin(ang)[:, None] * cs_scale
        cos = jnp.cos(ang)[:, None] * cs_scale
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def swiglu(x, f):
        return lin(jax.nn.silu(lin(x, f["w_gate"])) * lin(x, f["w_up"]),
                   f["w_down"])

    def attention(x, a, pos, causal):
        B, T = x.shape[:2]
        q = lin(x, a["wq"]).reshape(B, T, H, n + p)
        kv = lin(x, a["wkv_a"])
        c = rms(kv[..., :r], a["kv_norm"])
        kr = rope(kv[..., None, r:], pos)                    # (B, T, 1, p)
        if bits:
            c, kr = fq(c, bits, -1), fq(kr, bits, -1)
        qn, qr = q[..., :n], rope(q[..., n:], pos)
        kn = lin(c, a["wk_b"]).reshape(B, T, H, n)
        vv = lin(c, a["wv_b"]).reshape(B, T, H, v)
        s = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn, precision=hi)
             + jnp.einsum("bqhd,bkd->bhqk", qr, kr[:, :, 0], precision=hi))
        s = jnp.where(causal, s * scale, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv,
                       precision=hi)
        return lin(o.reshape(B, T, H * v), a["wo"])

    def experts(x, f):
        """The held experts' part: each token's gates on experts ``off`` ..
        ``off + E - 1``, over every held expert's SwiGLU."""
        probs = jax.nn.softmax(jnp.einsum("btd,de->bte", x, f["router"],
                                          precision=hi), -1)
        g, ids = jax.lax.top_k(probs, top_k)
        if norm_topk:
            g = g / g.sum(-1, keepdims=True)
        g = g * route_scale
        held = off + jnp.arange(E)
        w = jnp.sum(g[..., None] * (ids[..., None] == held), axis=-2)
        wg, wu, wd = (f[k].astype(f32) for k in ("we_gate", "we_up",
                                                 "we_down"))
        xe = x
        if bits:
            xe, wg, wu, wd = (fq(x, bits, -1), fq(wg, bits, -2),
                              fq(wu, bits, -2), fq(wd, bits, -2))
        h = (jax.nn.silu(jnp.einsum("btd,edf->btef", xe, wg, precision=hi))
             * jnp.einsum("btd,edf->btef", xe, wu, precision=hi))
        if bits:
            h = fq(h, bits, -1)
        return jnp.einsum("btef,efd->btd", h * w[..., None], wd, precision=hi)

    def logits(params, tokens):
        B, T = tokens.shape
        pos = jnp.arange(T)
        causal = pos[:, None] >= pos[None, :]
        h = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(f32)
        for lp in params["head"]:
            h = h + attention(rms(h, lp["attn_norm"]), lp["attn"], pos, causal)
            h = h + swiglu(rms(h, lp["ffn_norm"]), lp["ffn"])

        def layer(h, lp):
            h = h + attention(rms(h, lp["attn_norm"]), lp["attn"], pos, causal)
            x = rms(h, lp["ffn_norm"])
            f = lp["ffn"]
            return h + experts(x, f) + swiglu(x, f["shared"]), None

        h, _ = jax.lax.scan(layer, h, params["blocks"][0])
        h = rms(h, params["final_norm"])
        emb = params["embed"]["embedding"].astype(f32)
        return jnp.einsum("bsd,vd->bsv", h, emb, precision=hi)

    return logits


# ------------------------------------------------------------------ FLOPs
def _flop_dims(config):
    m = dims(config)
    d, H, r, n, p, v = (m[k] for k in ("d", "H", "r", "n", "p", "v"))
    attn = d * H * (n + p) + d * (r + p) + r * H * (n + v) + H * v * d
    used = m["k"] * m["E"] / m["E_pub"]         # held experts a token uses
    moe = d * m["E_pub"] + 3 * d * m["S"] + used * 3 * d * m["F"]
    params = m["K"] * (attn + 3 * d * m["I"]) + m["L"] * (attn + moe)
    pair = 2 * (m["K"] + m["L"]) * H * (n + p + v)
    return params, pair, d * m["V"]


def prefill_flops(cfg, prompt_len: int) -> float:
    params, pair, head = _flop_dims(cfg)
    q = int(prompt_len)
    return 2.0 * params * q + pair * q * (q + 1) / 2 + 2.0 * head


def decode_flops(cfg, context: int) -> float:
    """One output token whose query attends ``context`` keys."""
    params, pair, head = _flop_dims(cfg)
    return 2.0 * params + pair * int(context) + 2.0 * head
