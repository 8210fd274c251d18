"""Bucketed batched prefill vs. per-request prefill ingest timing, plus
the mesh-distributed data-parallel ingest scaling sweep.

Single-device mode (default, BENCH_serve.json):

bucketed : ServeEngine's admission scheduler - prompts right-padded to a
           static bucket set, ONE multi-slot prefill_many per same-bucket
           group, one fused cache_scatter into the pooled cache; at most
           len(buckets) prefill executables per engine lifetime.
legacy   : the pre-PR-3 path - one batch-of-1 prefill per request at the
           EXACT prompt length, so XLA compiles a fresh executable per
           distinct length and the PDQ pipeline runs at batch 1.

Each cell serves a mixed-length workload end to end (max_new=1 completes
at prefill, so the wall-clock is pure ingest) on a FRESH engine, compile
time included -
recompiles per prompt length are precisely the serving cost the bucket
design removes, so they belong in the measurement.  ``speedup`` is
ingest-throughput bucketed/legacy (prompt tokens per second).

Mesh mode (``--mesh DxM``, BENCH_serve_sharded.json): ShardedServeEngine
ingest throughput at data=1 vs data=D (model axis and per-replica batch
fixed), STEADY-STATE - each engine is warmed on a small workload first so
the measurement isolates the data-parallel scaling, not compile time.
``speedup`` is the tok/s ratio data=D over data=1.  On CPU the required
virtual devices are forced automatically (env set before jax imports).

Multi-process mode (``--mesh DxM --multiproc N``,
BENCH_serve_multihost.json): the SAME logical mesh served by the
single-process ShardedServeEngine (measured inline) vs the
``jax.distributed`` MultiHostServeEngine over N spawned processes.  The
GATED ``speedup`` is the per-round ingest-capacity ratio multihost /
single-process: the coordinator protocol must reproduce the
single-process schedule exactly (same admits per round), so the
deterministic expectation is 1.0 and any routing/protocol regression
(idle replicas, extra rounds) fails the gate.  Wall-clock tok/s for both
engines is recorded informationally - on a 2-core CI host all processes
share the cores, so the wall ratio measures coordination overhead plus
core contention, not replica concurrency.

Paged mode (``--paged``, BENCH_serve_paged.json): max concurrent users
at a FIXED persistent-pool byte budget.  The slot-row engine reserves a
full max_len row per slot, so its concurrency is slots = pool_bytes /
row_bytes; the paged engine spends the SAME byte budget on a page pool
and admits until the pages (not the rows) run out, so short-lived
requests pack many more concurrent users into the budget.  Each cell
serves a short-request workload through both engines (the paged pool is
sized DOWN to fit inside the slot-row engine's measured pool bytes,
asserted) and the GATED ``speedup`` is the ratio of peak concurrently
admitted users paged / slot-row - a deterministic scheduler quantity, fp
and int8-KV cells both.

Writes the JSON next to this file; ``--quick`` runs the CI smoke cells
only and ``--compare <baseline.json>`` fails on a >25% geomean speedup
regression (see _compare.py).
"""
from __future__ import annotations

import os
import sys


from repro.launch.mesh import bootstrap_mesh_env

bootstrap_mesh_env(sys.argv)

import argparse
import json
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _compare import compare

from repro.configs import reduced_config
from repro.launch.mesh import make_serve_mesh, parse_mesh
from repro.serve import Request, ServeConfig, build_engine

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "BENCH_serve.json")
OUT_SHARDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_serve_sharded.json")
OUT_MULTIHOST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_serve_multihost.json")
OUT_PAGED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_serve_paged.json")
ARCH = "stablelm-1.6b"
MULTIPROC_TIMEOUT = 1200       # hard cap on the spawned process pair (s)


def _workload(cfg, requests: int, max_prompt: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(2, max_prompt + 1, requests)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                    max_new=1) for i, L in enumerate(lens)], int(lens.sum())


def bench_cell(cfg, params, requests: int, slots: int, max_prompt: int) -> dict:
    buckets = (8, 16, 32, 64)
    out = {"requests": requests, "slots": slots, "max_prompt": max_prompt}
    for tag, batched in (("bucketed", True), ("legacy", False)):
        reqs, prompt_tokens = _workload(cfg, requests, max_prompt)
        eng = build_engine(ServeConfig(
            slots=slots, max_len=max(buckets) + 8, buckets=buckets,
            batch_prefill=batched), cfg=cfg, params=params)
        t0 = time.perf_counter()
        eng.run(reqs)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        out[f"{tag}_s"] = dt
        out[f"{tag}_tok_s"] = prompt_tokens / dt
        out[f"{tag}_prefill_compiles"] = eng.stats["prefill_compiles"]
    # _compare.py convention: 'speedup' is the dimensionless trajectory pin
    out["speedup"] = out["legacy_s"] / out["bucketed_s"]
    return out


def bench_telemetry_cell(cfg, params, requests: int, slots: int,
                         max_prompt: int,
                         trace_out: str | None = None) -> dict:
    """Telemetry-overhead A/B: the identical mixed prefill+decode workload
    through an instrumented engine (telemetry=True; span capture too when
    ``--trace-out`` asks for the sample trace) and a bare one
    (telemetry=False, the ServeConfig A/B switch).

    The GATED ``speedup`` is per-round token capacity instrumented/bare:
    tokens landed (prompt + decoded) per device launch.  The hook points
    observe timings but never touch admission, sampling or launch
    shapes, so the deterministic expectation is exactly 1.0 - the cell
    hard-asserts the <=2% overhead budget from DESIGN.md
    "Observability", and a telemetry change that alters the schedule (an
    extra host sync, a blocking collection) fails here rather than in
    production.  Wall tok/s both ways is recorded informationally: the
    2-core CI hosts' wall clock is far noisier than 2%.
    """
    buckets = (8, 16, 32)
    out = {"requests": requests, "slots": slots, "max_prompt": max_prompt}
    tokens_served: dict[str, list] = {}
    for tag, instrumented in (("on", True), ("off", False)):
        rng = np.random.default_rng(3)
        lens = rng.integers(2, max_prompt + 1, requests)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                        max_new=8) for i, L in enumerate(lens)]
        eng = build_engine(ServeConfig(
            slots=slots, max_len=max(buckets) + 16, buckets=buckets,
            telemetry=instrumented,
            trace=instrumented and trace_out is not None),
            cfg=cfg, params=params)
        warm = [Request(uid=1000 + i, prompt=p, max_new=2) for i, p in
                enumerate(r.prompt for r in reqs[:slots])]
        eng.run(warm)                   # compile prefill AND decode
        base_rounds = (eng.stats["prefill_batches"]
                       + eng.stats["decode_steps"])
        base_tokens = (eng.stats["prefill_tokens"]
                       + eng.stats["decode_tokens"])
        t0 = time.perf_counter()
        eng.run(reqs)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        tokens_served[tag] = [list(map(int, r.generated)) for r in reqs]
        rounds = (eng.stats["prefill_batches"] + eng.stats["decode_steps"]
                  - base_rounds)
        tokens = (eng.stats["prefill_tokens"] + eng.stats["decode_tokens"]
                  - base_tokens)
        out[f"{tag}_tok_s"] = sum(len(t) for t in tokens_served[tag]) / dt
        out[f"{tag}_rounds"] = rounds
        out[f"{tag}_tokens_per_round"] = tokens / rounds
        if instrumented and trace_out:
            eng.tel.tracer.write(trace_out)
            print(f"wrote sample trace ({len(eng.tel.tracer.events())} "
                  f"spans) -> {trace_out}")
    assert tokens_served["on"] == tokens_served["off"], \
        "telemetry changed the served tokens"
    out["speedup"] = out["on_tokens_per_round"] / out["off_tokens_per_round"]
    assert 0.98 <= out["speedup"] <= 1.02, \
        f"telemetry overhead gate: capacity ratio {out['speedup']} " \
        f"outside [0.98, 1.02]"
    return out


def _mesh_workload(cfg, requests: int, lo: int, hi: int, seed: int = 0):
    """Uniform-bucket prompts (lo, hi]: one prefill executable per engine."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo + 1, hi + 1, requests)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                    max_new=1) for i, L in enumerate(lens)], int(lens.sum())


def bench_mesh_cell(cfg, params, *, data_hi: int, model: int, spr: int,
                    max_prompt: int, requests: int) -> dict:
    """Data-parallel ingest scaling at fixed model size and fixed
    per-replica pool shape (``spr`` slots each): the same
    ``requests``-request workload through a data=1 and a data=data_hi
    engine.

    The GATED quantity (``speedup``) is per-round ingest capacity, read
    from ``engine.stats``: real prompt tokens landed per admission round
    (= per SPMD prefill launch + cache scatter).  It is what the
    coordinator design controls - one round must fill every replica's
    free slots, so capacity scales ~data x; a routing/assignment
    regression (replicas left idle, extra rounds) shows up immediately,
    and the measure is deterministic, which a CI gate needs.

    Wall-clock tok/s for both engines is RECORDED alongside
    (``d*_tok_s``) but not gated: on the 2-core CI hosts this tree
    targets, all virtual devices share the same two cores, so the wall
    ratio measures host core saturation (observed anywhere between ~1x
    and ~2.5x run-to-run), not replica concurrency.  On hardware with >=
    ``data`` cores/chips the wall ratio tracks the capacity ratio.
    """
    out = {"requests": requests, "spr": spr, "max_prompt": max_prompt,
           "model": model, "data_hi": data_hi}
    per_round = {}
    for data in (1, data_hi):
        eng = build_engine(ServeConfig(
            mesh=make_serve_mesh(data, model), slots_per_replica=spr,
            max_len=max_prompt + 32, buckets=(max_prompt,)),
            cfg=cfg, params=params)
        cell = _ingest_cell(eng, cfg, lo=max_prompt // 2, hi=max_prompt,
                            requests=requests)
        tag = f"d{data}"
        out[f"{tag}_tok_s"] = cell["tok_s"]
        out[f"{tag}_rounds"] = cell["rounds"]
        per_round[data] = cell["tokens_per_round"]
        out[f"{tag}_tokens_per_round"] = per_round[data]
    out["speedup"] = per_round[data_hi] / per_round[1]
    return out


def run_mesh_sweep(args, cfg, params) -> dict:
    data, model = parse_mesh(args.mesh)
    # (spr, max_prompt, requests); the quick cell rides in the full sweep
    # so CI smoke runs intersect the committed baseline
    quick_spec = [(8, 256, 64)]
    cells_spec = quick_spec if args.quick else list(dict.fromkeys(
        quick_spec + [(4, 256, 64), (8, 128, 64)]))
    cells = []
    for spr, max_prompt, requests in cells_spec:
        cell = bench_mesh_cell(cfg, params, data_hi=data, model=model,
                               spr=spr, max_prompt=max_prompt,
                               requests=requests)
        cells.append(cell)
        print(f"spr={spr} max_prompt={max_prompt:3d} model={model} "
              f"requests={requests:3d}  "
              f"d1 {cell['d1_tok_s']:8.0f} tok/s ({cell['d1_rounds']} rounds)"
              f"  d{data} {cell[f'd{data}_tok_s']:8.0f} tok/s "
              f"({cell[f'd{data}_rounds']} rounds)  "
              f"capacity x{cell['speedup']:.2f}")
    return {"cells": cells,
            "keys": ("requests", "spr", "max_prompt", "model", "data_hi")}


def _multiproc_cells(quick: bool):
    """(spr, max_prompt, requests); the quick cell rides in the full sweep
    so CI smoke runs intersect the committed baseline."""
    quick_spec = [(4, 64, 24)]
    return quick_spec if quick else list(dict.fromkeys(
        quick_spec + [(2, 64, 24), (4, 32, 24)]))


def _ingest_cell(eng, cfg, *, lo: int, hi: int, requests: int) -> dict:
    """Steady-state ingest through an already-built engine: warm run to
    compile, then one measured run; reports tokens landed per admission
    round (the deterministic scheduler quantity) and wall tok/s."""
    n_slots = eng.slots
    warm, _ = _mesh_workload(cfg, n_slots, lo, hi, seed=7)
    eng.run(warm)
    base_batches = eng.stats["prefill_batches"]
    base_tokens = eng.stats["prefill_tokens"]
    reqs, prompt_tokens = _mesh_workload(cfg, requests, lo, hi)
    t0 = time.perf_counter()
    eng.run(reqs)
    jax.block_until_ready(eng.caches)
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    rounds = eng.stats["prefill_batches"] - base_batches
    tokens = eng.stats["prefill_tokens"] - base_tokens
    return {"tok_s": prompt_tokens / dt, "rounds": rounds,
            "tokens_per_round": tokens / rounds}


def _tree_bytes(tree) -> int:
    return sum(np.dtype(x.dtype).itemsize * int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(tree))


def _peak_users(eng, reqs) -> tuple[int, float]:
    """Drain ``reqs`` through the engine round by round (the run() loop,
    instrumented): peak concurrently active requests + wall seconds."""
    eng.pending.extend(reqs)
    peak = 0
    t0 = time.perf_counter()
    while eng.pending or any(r is not None for r in eng.active):
        eng._admit(None)
        peak = max(peak, sum(r is not None for r in eng.active))
        eng.step()
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    return peak, dt


def bench_paged_cell(cfg, params, *, requests: int, max_len: int,
                     page_size: int, kv: str) -> dict:
    """Max concurrent users at a fixed persistent-pool byte budget.

    The budget is the slot-row engine's measured pool bytes (4 slots x
    max_len rows).  The paged engine gets the same budget spent on pages:
    its pool_pages is sized so its persistent pool fits INSIDE the
    budget (asserted), with scheduler rows (slots) no longer tied to
    row reservations.  The workload is short requests (one page of live
    context each), so concurrency is limited by reserved bytes on the
    slot-row engine and by actual usage on the paged one.
    """
    from repro.models import build_model

    slot_slots = 4
    buckets = (8, 16, 32)
    ref = build_engine(ServeConfig(slots=slot_slots, max_len=max_len,
                                   buckets=buckets), cfg=cfg, params=params)
    budget = _tree_bytes(ref.caches)

    # the paged pool's bytes are affine in pool_pages (page leaves scale
    # with pages, flat leaves with slots): probe two shapes to solve for
    # the largest pool_pages fitting the budget
    paged_slots = requests
    mem_len = 8 if cfg.family == "encdec" else 0
    po = build_model(cfg).paged_cache(paged_slots, max_len, mem_len,
                                     page_size)
    bytes_at = lambda p: _tree_bytes(jax.eval_shape(lambda: po.init(p)))
    per_page = bytes_at(3) - bytes_at(2)
    base = bytes_at(2) - 2 * per_page
    pool_pages = int((budget - base) // per_page)
    assert pool_pages >= 2, "budget too small for a page pool"

    eng = build_engine(ServeConfig(
        slots=paged_slots, max_len=max_len, buckets=buckets, paged=True,
        page_size=page_size, pool_pages=pool_pages), cfg=cfg, params=params)
    paged_bytes = _tree_bytes(eng.caches)
    assert paged_bytes <= budget, (paged_bytes, budget)

    def workload(seed=0):
        rng = np.random.default_rng(seed)
        lens = rng.integers(8, page_size - 8, requests)
        return [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                        max_new=4) for i, L in enumerate(lens)]

    slot_peak, slot_s = _peak_users(ref, workload())
    paged_peak, paged_s = _peak_users(eng, workload())
    return {"requests": requests, "max_len": max_len,
            "page_size": page_size, "kv": kv,
            "pool_bytes": budget, "paged_pool_bytes": paged_bytes,
            "pool_pages": pool_pages,
            "slotrow_peak_users": slot_peak, "paged_peak_users": paged_peak,
            "slotrow_s": slot_s, "paged_s": paged_s,
            # deterministic scheduler quantity: concurrently admitted
            # users at the same persistent-pool byte budget
            "speedup": paged_peak / slot_peak}


def run_paged_sweep(args) -> dict:
    """fp + int8-KV cells (int8 halves the per-token KV bytes, so the
    budget buys twice the rows on BOTH engines - the gated ratio pins
    that paging keeps its packing advantage in the quantized layout)."""
    import dataclasses

    from repro.models import build_model

    # (requests, max_len, page_size); quick == full: cells are seconds
    cells_spec = [(24, 256, 32)]
    cells = []
    for kv in ("fp", "int8"):
        cfg = reduced_config(ARCH)
        if kv == "int8":
            cfg = dataclasses.replace(cfg, quant_kv="dynamic")
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        for requests, max_len, page_size in cells_spec:
            cell = bench_paged_cell(cfg, params, requests=requests,
                                    max_len=max_len, page_size=page_size,
                                    kv=kv)
            cells.append(cell)
            print(f"kv={kv:4s} requests={requests:3d} max_len={max_len} "
                  f"page={page_size}  pool {cell['pool_bytes']/1e6:6.1f}MB  "
                  f"slot-row {cell['slotrow_peak_users']:2d} users  "
                  f"paged {cell['paged_peak_users']:2d} users "
                  f"({cell['pool_pages']} pages)  "
                  f"x{cell['speedup']:.2f}")
    return {"cells": cells,
            "keys": ("requests", "max_len", "page_size", "kv")}


def run_multiproc_child(args, cfg, params) -> None:
    """One jax.distributed process of the --multiproc sweep (spawned by the
    parent with --process-id).  The coordinator (process 0) measures every
    cell and writes the partial JSON the parent merges."""
    from repro.launch.mesh import make_serve_mesh, parse_mesh

    data, model = parse_mesh(args.mesh)
    out = []
    for spr, max_prompt, requests in _multiproc_cells(args.quick):
        eng = build_engine(ServeConfig(
            mesh=make_serve_mesh(data, model), slots_per_replica=spr,
            max_len=max_prompt + 32, buckets=(max_prompt,),
            multihost=True), cfg=cfg, params=params)
        if jax.process_index() == 0:
            cell = _ingest_cell(eng, cfg, lo=max_prompt // 2, hi=max_prompt,
                                requests=requests)
            eng.stop_workers()
            out.append(cell)
        else:
            eng.serve_worker()
    if jax.process_index() == 0:
        with open(args.multiproc_out, "w") as f:
            json.dump(out, f)


def run_multiproc_sweep(args, cfg, params) -> dict:
    """Parent: measure the single-process ShardedServeEngine inline, spawn
    the N-process pair to measure MultiHostServeEngine on the same logical
    mesh, and gate the per-round capacity ratio."""
    import subprocess
    import sys as _sys
    import tempfile

    from repro.launch.mesh import (make_serve_mesh, parse_mesh,
                                   pick_coordinator,
                                   strip_forced_device_count)

    data, model = parse_mesh(args.mesh)
    singles = []
    for spr, max_prompt, requests in _multiproc_cells(args.quick):
        eng = build_engine(ServeConfig(
            mesh=make_serve_mesh(data, model), slots_per_replica=spr,
            max_len=max_prompt + 32, buckets=(max_prompt,)),
            cfg=cfg, params=params)
        singles.append(_ingest_cell(eng, cfg, lo=max_prompt // 2,
                                    hi=max_prompt, requests=requests))

    env = dict(os.environ)
    env["XLA_FLAGS"] = strip_forced_device_count(env.get("XLA_FLAGS", ""))
    with tempfile.TemporaryDirectory() as td:
        mp_out = os.path.join(td, "mp.json")
        child_argv = [_sys.executable, os.path.abspath(__file__),
                      "--mesh", args.mesh, "--multiproc", str(args.multiproc),
                      # --num-processes sizes the child's forced device
                      # count in bootstrap_mesh_env (D*M // N per process)
                      "--num-processes", str(args.multiproc),
                      "--coordinator", pick_coordinator(args.coordinator),
                      "--multiproc-out", mp_out]
        if args.quick:
            child_argv.append("--quick")
        procs = [subprocess.Popen(child_argv + ["--process-id", str(i)],
                                  env=env)
                 for i in range(args.multiproc)]
        try:
            for p in procs:
                p.wait(timeout=MULTIPROC_TIMEOUT)
        finally:
            for p in procs:
                p.kill()
        for i, p in enumerate(procs):
            if p.returncode != 0:
                raise RuntimeError(f"multiproc bench process {i} exited "
                                   f"{p.returncode}")
        with open(mp_out) as f:
            multis = json.load(f)

    cells = []
    for (spr, max_prompt, requests), sp, mp in zip(
            _multiproc_cells(args.quick), singles, multis):
        cell = {"requests": requests, "spr": spr, "max_prompt": max_prompt,
                "nprocs": args.multiproc,
                "sp_tok_s": sp["tok_s"],
                "sp_tokens_per_round": sp["tokens_per_round"],
                "mp_tok_s": mp["tok_s"],
                "mp_rounds": mp["rounds"],
                "mp_tokens_per_round": mp["tokens_per_round"],
                # the coordinator protocol must reproduce the single-process
                # schedule exactly: capacity ratio 1.0, deterministic
                "speedup": mp["tokens_per_round"] / sp["tokens_per_round"]}
        cells.append(cell)
        print(f"spr={spr} max_prompt={max_prompt:3d} requests={requests:3d}  "
              f"single-proc {cell['sp_tok_s']:8.0f} tok/s  "
              f"{args.multiproc}-proc {cell['mp_tok_s']:8.0f} tok/s "
              f"({cell['mp_rounds']} rounds)  "
              f"capacity x{cell['speedup']:.2f}")
    return {"cells": cells,
            "keys": ("requests", "spr", "max_prompt", "nprocs")}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small cells / CI smoke")
    ap.add_argument("--out", default=None)
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="fail on >25%% speedup regression vs this baseline")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="data-parallel ingest scaling sweep on a DxM mesh "
                         "(ShardedServeEngine; data=1 vs data=D)")
    ap.add_argument("--multiproc", type=int, default=0, metavar="N",
                    help="with --mesh: compare the single-process sharded "
                         "engine vs MultiHostServeEngine over N "
                         "jax.distributed processes")
    ap.add_argument("--paged", action="store_true",
                    help="max-concurrent-users sweep at a fixed "
                         "persistent-pool byte budget: paged KV pool vs "
                         "slot-row, fp and int8 KV")
    ap.add_argument("--num-processes", type=int, default=None,
                    help=argparse.SUPPRESS)   # accepted for env bootstrap symmetry
    ap.add_argument("--process-id", type=int, default=None,
                    help=argparse.SUPPRESS)   # child mode (set by the parent)
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed coordinator for --multiproc "
                         "(default: a free local port)")
    ap.add_argument("--multiproc-out", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a sample Perfetto trace from the "
                         "instrumented engine of the telemetry A/B cell "
                         "(single-device sweep only)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import refuse_local_processes
    if args.multiproc and args.process_id is None:
        # the parent measures in-process before spawning: on a TPU host it
        # would hold the chips its children need
        refuse_local_processes("--multiproc")
    enable_compile_cache()

    if args.process_id is not None:
        # --multiproc child: join the jax.distributed job BEFORE any
        # device query, then follow the coordinator
        if not args.coordinator:
            raise SystemExit("a --process-id child needs an explicit "
                             "--coordinator HOST:PORT")
        from repro.launch.mesh import init_distributed
        init_distributed(args.coordinator, args.multiproc, args.process_id)

    cfg = reduced_config(ARCH)
    from repro.models import build_model
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    if args.process_id is not None:
        run_multiproc_child(args, cfg, params)
        return

    if args.paged:
        sweep = run_paged_sweep(args)
        out = {
            "meta": {
                "backend": jax.default_backend(),
                "device": str(jax.devices()[0]),
                "arch": ARCH,
                "jax": jax.__version__,
                "quick": bool(args.quick),
            },
            "cells": sweep["cells"],
        }
        out_path = args.out or OUT_PAGED
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
        print(f"wrote {out_path}")
        if args.compare:
            sys.exit(compare(out, args.compare, keys=sweep["keys"]))
        return

    if args.mesh and args.multiproc:
        sweep = run_multiproc_sweep(args, cfg, params)
        out = {
            "meta": {
                "backend": jax.default_backend(),
                "device": str(jax.devices()[0]),
                "arch": ARCH,
                "jax": jax.__version__,
                "mesh": args.mesh,
                "nprocs": args.multiproc,
                "quick": bool(args.quick),
            },
            "cells": sweep["cells"],
        }
        out_path = args.out or OUT_MULTIHOST
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
        print(f"wrote {out_path}")
        if args.compare:
            sys.exit(compare(out, args.compare, keys=sweep["keys"]))
        return

    if args.mesh:
        sweep = run_mesh_sweep(args, cfg, params)
        out = {
            "meta": {
                "backend": jax.default_backend(),
                "device": str(jax.devices()[0]),
                "arch": ARCH,
                "jax": jax.__version__,
                "mesh": args.mesh,
                "quick": bool(args.quick),
            },
            "cells": sweep["cells"],
        }
        out_path = args.out or OUT_SHARDED
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
        print(f"wrote {out_path}")
        if args.compare:
            sys.exit(compare(out, args.compare, keys=sweep["keys"]))
        return

    # (requests, slots, max_prompt); quick cells ride in the full sweep so
    # CI smoke runs intersect the committed baseline (see --compare)
    quick_spec = [(12, 4, 32), (8, 4, 16)]
    if args.quick:
        cells_spec = quick_spec
    else:
        cells_spec = list(dict.fromkeys(
            quick_spec + [(24, 4, 32), (24, 8, 64), (48, 8, 64)]))

    cells = []
    for requests, slots, max_prompt in cells_spec:
        cell = bench_cell(cfg, params, requests, slots, max_prompt)
        cells.append(cell)
        print(f"requests={requests:3d} slots={slots} max_prompt={max_prompt:3d}  "
              f"bucketed {cell['bucketed_s']:6.2f}s "
              f"({cell['bucketed_prefill_compiles']} compiles)  "
              f"legacy {cell['legacy_s']:6.2f}s "
              f"({cell['legacy_prefill_compiles']} compiles)  "
              f"x{cell['speedup']:.2f}")

    # telemetry-overhead A/B (distinct cell key; quick AND full, so the
    # <=2% gate runs on every CI smoke)
    cell = bench_telemetry_cell(cfg, params, 16, 4, 32,
                                trace_out=args.trace_out)
    cells.append(cell)
    print(f"telemetry A/B requests= 16 slots=4 max_prompt= 32  "
          f"on {cell['on_tok_s']:7.0f} tok/s  "
          f"off {cell['off_tok_s']:7.0f} tok/s  "
          f"capacity x{cell['speedup']:.2f} (gate [0.98, 1.02])")

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
        sys.exit(compare(out, args.compare,
                         keys=("requests", "slots", "max_prompt")))


if __name__ == "__main__":
    main()
