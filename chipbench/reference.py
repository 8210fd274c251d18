"""The plain reference, and the comparison that decides ``correct``.

A decoder written out in ``jax.numpy`` at float32 with the matmul precision
set to ``highest``, taking nothing from the program: the weights are made
again from the seed by ``weights.make_params``.  The block is the one the
configuration states (its ``assumed`` list names where it departs from the
published model): RMSNorm with gain ``1 + w``, rotary embedding on the whole
head (halves rotated), causal softmax attention, SwiGLU, the output head tied
to the embedding.

What is compared: each served request's prompt and served tokens are run
through the reference once, teacher-forced, and at every served position the
gap ``max(reference logits) - reference logit of the served token`` is read.
A greedy server that computes the model right serves, at each position, a
token the reference ranks first or within rounding of first; the widest gap
over the sample is the number compared with the configuration's limit.

The control (``control=True``) is the reference computed one precision
lower, in the program's place: every projection's weights (per output
channel) and inputs (per token), and the attention keys and values (per
token and head), rounded to the configuration's ``control_bits``.  At each
position the token it puts first is read with the same gap.
"""
from __future__ import annotations

import functools

import numpy as np

ROWS_PER_BLOCK = 4


def _fq(x, bits: int, axis: int):
    """Symmetric round-to-nearest onto ``bits``-bit integers along ``axis``."""
    import jax.numpy as jnp
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


@functools.lru_cache(maxsize=None)
def _programs(L, d, H, Hkv, Dh, F, V, theta, eps, bits):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def lin(x, w):
        w = w.astype(f32)
        if bits:
            x, w = _fq(x, bits, -1), _fq(w, bits, 0)
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
                k, v = _fq(k, bits, -1), _fq(v, bits, -1)
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

    @jax.jit
    def gaps(params, tokens, targets):
        lg = logits(params, tokens)
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
        return best - got, jnp.argmax(lg, -1) == targets

    @jax.jit
    def top(params, tokens):
        return jnp.argmax(logits(params, tokens), -1).astype(jnp.int32)

    return gaps, top


def _rows(seqs, S: int):
    """Teacher-forced rows: input prompt + tokens[:-1], targets the served
    tokens at positions P - 1 ... P + n - 2."""
    n = len(seqs)
    nb = -(-n // ROWS_PER_BLOCK) * ROWS_PER_BLOCK
    tokens = np.zeros((nb, S), np.int32)
    targets = np.zeros((nb, S), np.int32)
    mask = np.zeros((nb, S), bool)
    for i, sq in enumerate(seqs):
        p, t = list(sq["prompt"]), list(sq["tokens"])
        seq = p + t[:-1]
        if len(seq) > S:
            raise ValueError(f"sequence of {len(seq)} tokens exceeds {S}")
        tokens[i, :len(seq)] = seq
        targets[i, len(p) - 1:len(p) - 1 + len(t)] = t
        mask[i, len(p) - 1:len(p) - 1 + len(t)] = True
    return tokens, targets, mask


def compare(params, config: dict, seqs: list, control: bool = False) -> dict:
    import jax

    from chipbench.weights import dims
    m = dims(config)
    key = (m["L"], m["d"], m["H"], m["Hkv"], m["Dh"], m["F"], m["V"],
           m["theta"], m["eps"])
    gaps_fn, _ = _programs(*key, 0)
    S = int(config["serve"]["max_len"])
    tokens, targets, mask = _rows(seqs, S)
    out = {"n_seqs": len(seqs), "n_tokens": int(mask.sum())}
    if not seqs:
        return dict(out, gap_max=None, gap_mean=None, argmax_agree=None)

    def blocks(fn, *arrs):
        res = []
        for i in range(0, tokens.shape[0], ROWS_PER_BLOCK):
            sl = slice(i, i + ROWS_PER_BLOCK)
            res.append(jax.device_get(fn(params, *(a[sl] for a in arrs))))
        return res

    with jax.default_matmul_precision("highest"):
        got = blocks(gaps_fn, tokens, targets)
        gap = np.concatenate([g for g, _ in got])[mask]
        agree = np.concatenate([a for _, a in got])[mask]
        out.update(gap_max=float(gap.max()), gap_mean=float(gap.mean()),
                   argmax_agree=float(agree.mean()))
        if control:
            _, top_fn = _programs(*key, int(config["check"]["control_bits"]))
            ctrl = np.concatenate(blocks(top_fn, tokens))
            cg = np.concatenate([g for g, _ in blocks(gaps_fn, tokens, ctrl)])
            cg = cg[mask]
            out.update(control_gap_max=float(cg.max()),
                       control_gap_mean=float(cg.mean()))
    return out
