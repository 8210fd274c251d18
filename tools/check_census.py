"""Kernel-launch census lint: fail CI if a pallas_call count regresses.

The PDQ execution contract is a LAUNCH BUDGET, not just numerics: the
quantized GQA block must trace to a pinned number of ``pallas_call``s
per mode, because every extra launch is a lost fusion (a standalone PDQ
prologue, an unfused attend, a split QKV triple) that quietly multiplies
serving cost long before any parity test notices.  The pins live in
scattered jaxpr tests too (tests/test_hlo_and_linops.py), but those run
in the tier-1 jobs; this tool runs in the LINT job so a census
regression fails in minutes, with the table printed, before any heavy
suite spins up.

Pinned table (DESIGN.md "Decode fast path" documents the breakdown):

  decode_fp      7   prologue+matmul for the QKV triple and for wo,
                     flash-decode attend, fused SwiGLU MLP triple
                     (gate/up epilogue computes silu(g)*u AND w_down's
                     prologue)
  decode_int8kv  7   the int8-KV attend's output stage emits wo's PDQ
                     prologue (decode_attend_i8kv_fused_p), so wo costs
                     one W8A8 matmul launch; the same launch writes the
                     step's K/V token into the cache
  prefill        7   same budget at S>1: the fusions are mode-agnostic
  lin_quantized  2   one PDQ prologue + one W8A8 matmul per quantized
                     projection outside the fused blocks

Run from the repo root: ``python tools/check_census.py``.  Exits
non-zero on any mismatch - HIGHER means a lost fusion; LOWER means a
new fusion landed and the table (and the jaxpr tests) must be re-pinned
in the same change.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.attention import AttnDims, gqa_apply, gqa_init, init_cache
from repro.models.layers import mlp_apply, mlp_init, rms_norm
from repro.models.linops import lin, quantize_param_tree, quantize_weight

PINS = {
    "decode_fp": 7,
    "decode_int8kv": 7,
    "prefill": 7,
    "lin_quantized": 2,
}


def count_pallas_calls(jaxpr) -> int:
    """Recursively count pallas_call eqns in a (Closed)Jaxpr."""
    if hasattr(jaxpr, "jaxpr"):              # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    n += count_pallas_calls(sub)
    return n


def _block_setup(quant_kv: str):
    dims = AttnDims(d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
                    quant_kv=quant_kv)
    key = jax.random.PRNGKey(0)
    params = {"attn": gqa_init(key, dims, jnp.float32),
              "attn_norm": jnp.zeros((256,)),
              "ffn_norm": jnp.zeros((256,)),
              "ffn": mlp_init(jax.random.fold_in(key, 1), 256, 512,
                              jnp.float32)}
    return dims, quantize_param_tree(params), init_cache(dims, 8, 64,
                                                         jnp.float32)


def block_census(quant_kv: str, mode: str) -> int:
    """Trace one full quantized GQA block (attn norm -> QKV -> attend ->
    wo, ffn norm -> gate/up -> down) under kernel impl; count launches."""
    dims, qp, cache = _block_setup(quant_kv)

    def block(p, h, cache, positions, seq_lens):
        a, cache = gqa_apply(p["attn"], dims, rms_norm(h, p["attn_norm"]),
                             positions, mode=mode, cache=cache,
                             seq_lens=seq_lens)
        h = h + a
        return h + mlp_apply(p["ffn"], rms_norm(h, p["ffn_norm"])), cache

    S = 1 if mode == "decode" else 16
    h = jnp.ones((8, S, 256))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (8, S))
    seq_lens = jnp.full((8,), S, jnp.int32)
    ops.set_impl("kernel")
    try:
        if mode == "decode":
            jaxpr = jax.make_jaxpr(
                lambda p, h, c, pos: block(p, h, c, pos, None))(
                    qp, h, cache, pos)
        else:
            jaxpr = jax.make_jaxpr(block)(qp, h, cache, pos, seq_lens)
    finally:
        ops.set_impl("auto")
    return count_pallas_calls(jaxpr)


def lin_census() -> int:
    """One quantized projection outside the fused blocks."""
    w = quantize_weight(0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                                (256, 128)))
    x = jnp.ones((8, 256))
    ops.set_impl("kernel")
    try:
        jaxpr = jax.make_jaxpr(lambda x: lin(x, w))(x)
    finally:
        ops.set_impl("auto")
    return count_pallas_calls(jaxpr)


def main() -> int:
    got = {
        "decode_fp": block_census("none", "decode"),
        "decode_int8kv": block_census("dynamic", "decode"),
        "prefill": block_census("none", "prefill"),
        "lin_quantized": lin_census(),
    }
    failed = False
    for name, pin in PINS.items():
        mark = "ok" if got[name] == pin else "REGRESSED"
        failed |= got[name] != pin
        print(f"census: {name:14s} {got[name]:2d} pallas_calls "
              f"(pinned {pin}) {mark}")
    if failed:
        print("census: FAIL - a pallas_call count moved off the pinned "
              "table. Higher = a lost fusion (fix it); lower = a new "
              "fusion (re-pin this table AND the jaxpr tests in "
              "tests/test_hlo_and_linops.py in the same change).")
        return 1
    print("census: all launch budgets hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
