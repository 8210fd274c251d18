"""Grouped vs per-projection PDQ QKV timing at serving shapes.

grouped  : ops.pdq_dense_grouped over a group_quantize_weights record of a
           GQA Q/K/V triple - ONE prologue (x read once) + ONE wide W8A8
           matmul with the per-(row, segment) interval epilogue.
per_proj : three independent ops.pdq_dense calls on the same input - the
           PR-1 fused path dispatched once per projection (3 prologue
           reads of x, 3 skinny matmuls).

Shapes mirror a GQA decode step: K = d_model, N_q = d_model,
N_k = N_v = d_model / 4 (4:1 GQA), B in {8, 64, 256}, d_model in
{2048, 4096}.  Writes ``BENCH_grouped_qkv.json`` next to this file (the
stable path the perf trajectory tracks); ``--quick`` shrinks the sweep
for CI smoke and ``--compare <baseline.json>`` fails on a >25% speedup
regression against the committed JSON (see _compare.py).

Dispatch follows ``ops.set_impl`` 'auto': real Pallas kernels on TPU, the
jnp oracle elsewhere - the JSON records which path ran.
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
from repro.models.linops import group_quantize_weights, quantize_weight

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "BENCH_grouped_qkv.json")


def bench_cell(B: int, d_model: int, iters: int) -> dict:
    key = jax.random.PRNGKey(B + d_model)
    n_kv = max(d_model // 4, 128)
    sizes = (d_model, n_kv, n_kv)           # Q, K, V extents (4:1 GQA)
    ws = [0.05 * jax.random.normal(jax.random.fold_in(key, i), (d_model, n))
          for i, n in enumerate(sizes)]
    grec = group_quantize_weights(ws)
    recs = [quantize_weight(w) for w in ws]
    x = jax.random.normal(jax.random.fold_in(key, 9), (B, d_model))

    grouped = jax.jit(lambda t: ops.pdq_dense_grouped(t, grec, out="fp"))
    per_proj = jax.jit(lambda t: tuple(ops.pdq_dense(t, r, out="fp")
                                       for r in recs))
    t_grouped = median_time(grouped, x, iters)
    t_per_proj = median_time(per_proj, x, iters)
    return {"B": B, "d_model": d_model, "sizes": list(sizes),
            "grouped_ms": t_grouped * 1e3, "per_proj_ms": t_per_proj * 1e3,
            "speedup": t_per_proj / t_grouped}


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
    quick_cells = [(8, 512), (64, 1024), (8, 2048), (64, 2048)]
    if args.quick:
        cells_spec, iters = quick_cells, args.iters or 9
    else:
        # the quick cells ride along so CI smoke runs intersect the
        # committed baseline (see --compare)
        full = [(b, d) for d in (2048, 4096) for b in (8, 64, 256)]
        cells_spec = list(dict.fromkeys(quick_cells + full))
        iters = args.iters or 9

    cells = []
    for b, d in cells_spec:
        cell = bench_cell(b, d, iters)
        cells.append(cell)
        print(f"B={b:4d} d_model={d:5d}  grouped {cell['grouped_ms']:9.3f} ms  "
              f"per-proj {cell['per_proj_ms']:9.3f} ms  "
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
        sys.exit(compare(out, args.compare, keys=("B", "d_model")))


if __name__ == "__main__":
    main()
