"""Fused vs. unfused PDQ dense timing at serving shapes.

fused   : ops.pdq_dense - ONE prologue kernel (x read once) + ONE W8A8
          matmul with the fp-out interval epilogue.
unfused : the pre-fusion serving path - separate amax / quantize /
          act_stats passes over x, requant matmul, jnp dequant.

Writes ``BENCH_pdq_dense.json`` (fused/unfused wall-clock per cell plus
environment metadata) next to this file so subsequent PRs have a perf
trajectory to defend.  Shapes: M in {8, 64, 256} x K=N in {2048, 4096,
8192} plus the CI smoke cells; ``--quick`` shrinks the sweep to the smoke
cells only, and ``--compare <baseline.json>`` fails on a >25% speedup
regression against the committed JSON (see _compare.py).

Dispatch follows ``ops.set_impl`` 'auto': real Pallas kernels on TPU, the
jnp oracle elsewhere (interpret-mode Pallas is a correctness tool, not a
timing target) - the JSON records which path ran.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _compare import compare
from _timing import median_time

from repro.kernels import ops
from repro.models.linops import quantize_weight

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "BENCH_pdq_dense.json")


def bench_cell(M: int, K: int, N: int, iters: int) -> dict:
    key = jax.random.PRNGKey(M + K + N)
    w = 0.05 * jax.random.normal(key, (K, N))
    rec = quantize_weight(w)
    x = jax.random.normal(jax.random.fold_in(key, 1), (M, K))

    fused = jax.jit(lambda t: ops.pdq_dense(t, rec, out="fp"))
    unfused = jax.jit(lambda t: ops.pdq_dense_unfused(t, rec)[0])
    t_fused = median_time(fused, x, iters)
    t_unfused = median_time(unfused, x, iters)
    return {"M": M, "K": K, "N": N,
            "fused_ms": t_fused * 1e3, "unfused_ms": t_unfused * 1e3,
            "speedup": t_unfused / t_fused}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / few iters (CI smoke)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="fail on >25%% speedup regression vs this baseline")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    # ms-scale 2048 cells anchor the smoke comparison - the sub-ms cells
    # alone are within timer noise of a shared CI runner
    quick_spec = ([(m, kn) for kn in (512, 1024) for m in (8, 64)]
                  + [(8, 2048), (64, 2048)])
    if args.quick:
        cells_spec, iters = quick_spec, args.iters or 9
    else:
        # the quick cells ride along so CI smoke runs intersect the
        # committed baseline (see --compare)
        full = [(m, kn) for kn in (2048, 4096, 8192) for m in (8, 64, 256)]
        cells_spec = list(dict.fromkeys(quick_spec + full))
        iters = args.iters or 9

    cells = []
    for m, kn in cells_spec:
        cell = bench_cell(m, kn, kn, iters)
        cells.append(cell)
        print(f"M={m:4d} K=N={kn:5d}  fused {cell['fused_ms']:9.3f} ms  "
              f"unfused {cell['unfused_ms']:9.3f} ms  "
              f"x{cell['speedup']:.2f}")

    out = {
        "meta": {
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "impl": "kernel" if jax.default_backend() == "tpu" else "ref",
            "jax": jax.__version__,
            "iters": iters,
            "quick": bool(args.quick),
        },
        "cells": cells,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")
    if args.compare:
        sys.exit(compare(out, args.compare, keys=("M", "K", "N")))


if __name__ == "__main__":
    main()
