"""Per-step decode latency: the N-step fused decode block vs single-step.

Each cell serves the same decode-heavy workload (4 slots in lockstep,
max_new=33: one prefill token + 32 decode tokens per request) through
two engines on the same warm params:

n1     : ``decode_steps=1`` - one host dispatch, one (slots, 1) backhaul
         and one scheduler round per decoded token (the pre-fast-path
         engine).
fused  : ``decode_steps=N`` - N decode steps run inside one ``lax.scan``
         per dispatch, cache state staying on device; the host sees one
         (slots, N) token block per round.

Both engines are warmed first (prefill AND decode compiled), so the
timed window isolates steady-state decode.  Wall-clock per-step latency
(``*_step_ms``) and token throughput are RECORDED informationally - on
the 2-core CI hosts the wall clock mostly measures host Python + XLA
CPU overlap, which is exactly what the fused block amortizes, but it is
too noisy to gate.

The GATED ``speedup`` is host dispatches per decoded token, n1/fused -
a deterministic scheduler quantity read from ``engine.stats``
(``decode_steps`` counts dispatches, ``decode_tokens`` consumed
tokens).  With every row running full blocks it is EXACTLY N, asserted
per cell; a scheduler regression that splits blocks (lost budget math,
early flushes) fails the cell before the geomean gate even runs.  Every
cell also asserts token-for-token parity between the two engines - the
fast path is not allowed to buy its dispatch reduction with a single
changed token.

Cells: N in {4, 16} x {fp, int8} KV x {slot-row, paged} layout.
Writes BENCH_decode.json next to this file; ``--quick`` runs the N=4
cells only and ``--compare <baseline.json>`` fails on a >25% geomean
regression (see _compare.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _compare import compare

from repro.configs import reduced_config
from repro.models import build_model
from repro.serve import Request, ServeConfig, build_engine

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "BENCH_decode.json")
ARCH = "stablelm-1.6b"
SLOTS = 4
MAX_NEW = 33            # 1 prefill token + 32 decode tokens per request


def _workload(cfg, seed: int = 0, uid0: int = 0):
    rng = np.random.default_rng(seed)
    return [Request(uid=uid0 + i,
                    prompt=rng.integers(0, cfg.vocab, 7).astype(np.int32),
                    max_new=MAX_NEW) for i in range(SLOTS)]


def _serve(cfg, params, n: int, paged: bool) -> dict:
    """One warmed engine at decode_steps=n: tokens, dispatch stats, wall."""
    eng = build_engine(ServeConfig(
        slots=SLOTS, max_len=64, buckets=(8,), temperature=0.9,
        decode_steps=n, paged=paged, page_size=16),
        cfg=cfg, params=params)
    eng.run(_workload(cfg, seed=9, uid0=1000))     # compile prefill + decode
    base_steps = eng.stats["decode_steps"]
    base_tokens = eng.stats["decode_tokens"]
    reqs = _workload(cfg)
    t0 = time.perf_counter()
    eng.run(reqs)
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    assert eng.stats["decode_compiles"] == 1
    dispatches = eng.stats["decode_steps"] - base_steps
    tokens = eng.stats["decode_tokens"] - base_tokens
    # lockstep rows running full blocks: the accounting is deterministic
    assert tokens == SLOTS * (MAX_NEW - 1), (tokens, n)
    assert dispatches == (MAX_NEW - 1) // n, (dispatches, n)
    return {"tokens": {r.uid: list(map(int, r.generated)) for r in reqs},
            "dispatches": dispatches, "decode_tokens": tokens,
            "wall_s": dt, "steps": (MAX_NEW - 1)}


def bench_cell(cfg, params, *, n: int, kv: str, layout: str) -> dict:
    paged = layout == "paged"
    n1 = _serve(cfg, params, 1, paged)
    fused = _serve(cfg, params, n, paged)
    assert fused["tokens"] == n1["tokens"], \
        f"N={n} {kv}/{layout}: fused decode changed the served tokens"
    out = {"n": n, "kv": kv, "layout": layout,
           "decode_tokens": fused["decode_tokens"]}
    for tag, r in (("n1", n1), ("fused", fused)):
        out[f"{tag}_dispatches"] = r["dispatches"]
        out[f"{tag}_dispatch_per_tok"] = r["dispatches"] / r["decode_tokens"]
        out[f"{tag}_step_ms"] = 1e3 * r["wall_s"] / r["steps"]
        out[f"{tag}_tok_s"] = r["decode_tokens"] / r["wall_s"]
    # deterministic gate: host-dispatch reduction per decoded token
    out["speedup"] = out["n1_dispatch_per_tok"] / out["fused_dispatch_per_tok"]
    assert out["speedup"] == n, (out["speedup"], n)
    out["wall_speedup"] = n1["wall_s"] / fused["wall_s"]   # informational
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="N=4 cells only / CI smoke")
    ap.add_argument("--out", default=None)
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="fail on >25%% speedup regression vs this baseline")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    cells = []
    for kv in ("fp", "int8"):
        cfg = reduced_config(ARCH)
        if kv == "int8":
            cfg = dataclasses.replace(cfg, quant_kv="dynamic")
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        for layout in ("slotrow", "paged"):
            # quick cells ride in the full sweep so CI smoke runs
            # intersect the committed baseline (see --compare)
            for n in ((4,) if args.quick else (4, 16)):
                cell = bench_cell(cfg, params, n=n, kv=kv, layout=layout)
                cells.append(cell)
                print(f"kv={kv:4s} layout={layout:7s} N={n:2d}  "
                      f"n1 {cell['n1_step_ms']:6.2f} ms/step  "
                      f"fused {cell['fused_step_ms']:6.2f} ms/step "
                      f"(wall x{cell['wall_speedup']:.2f})  "
                      f"dispatch/tok {cell['n1_dispatch_per_tok']:.3f} -> "
                      f"{cell['fused_dispatch_per_tok']:.3f}  "
                      f"x{cell['speedup']:.0f}")

    out = {
        "meta": {
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "arch": ARCH,
            "jax": jax.__version__,
            "quick": bool(args.quick),
        },
        "cells": cells,
    }
    out_path = args.out or OUT
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"wrote {out_path}")
    if args.compare:
        sys.exit(compare(out, args.compare, keys=("n", "kv", "layout")))


if __name__ == "__main__":
    main()
