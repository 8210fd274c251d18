"""The comparison with the plain reference that decides ``correct``.

The reference forward is the configuration's family's
(``models/<family>.py``: ``reference_logits``), written out in ``jax.numpy``
at float32 with the matmul precision set to ``highest``, taking nothing from
the program: the weights are made again from the seed by the family's
``make_params``.

What is compared: each served request's prompt and served tokens are run
through the reference once, teacher-forced, and at every served position the
gap ``max(reference logits) - reference logit of the served token`` is read.
A greedy server that computes the model right serves, at each position, a
token the reference ranks first or within rounding of first; the widest gap
over the sample is the number compared with the configuration's limit.

The control (``control=True``) is the reference computed one precision
lower, in the program's place: the family's forward with its rounding
(``fq``) at the configuration's ``control_bits``.  At each position the
token it puts first is read with the same gap.
"""
from __future__ import annotations

import functools

import numpy as np

ROWS_PER_BLOCK = 4


def fq(x, bits: int, axis: int):
    """Symmetric round-to-nearest onto ``bits``-bit integers along ``axis``:
    the control's rounding, which every family's reference applies."""
    import jax.numpy as jnp
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


@functools.lru_cache(maxsize=None)
def _programs(logits):
    """The gap and the first-ranked token of a family's reference forward."""
    import jax
    import jax.numpy as jnp

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


def compare(model, params, config: dict, seqs: list,
            control: bool = False) -> dict:
    """Gaps of the served ``seqs`` under ``model``'s reference (the family
    module, ``bench.load_model``); with ``control`` also the control's."""
    import jax

    gaps_fn, _ = _programs(model.reference_logits(config, 0))
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
            _, top_fn = _programs(model.reference_logits(
                config, int(config["check"]["control_bits"])))
            ctrl = np.concatenate(blocks(top_fn, tokens))
            cg = np.concatenate([g for g, _ in blocks(gaps_fn, tokens, ctrl)])
            cg = cg[mask]
            out.update(control_gap_max=float(cg.max()),
                       control_gap_mean=float(cg.mean()))
    return out
