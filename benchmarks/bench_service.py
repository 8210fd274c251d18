"""Serving front-door overload bench: the streaming service under a
deterministic 3x-capacity open-loop storm, vs batch ``run()``.

Each cell drives a ``ServeService`` over a fresh ``ServeEngine`` with a
burst-injection fault plan: ``per_round`` requests hit the admission
queue at the top of every scheduler round for ``rounds`` rounds - about
3x the engine's slot capacity, so the bounded queue sheds most of the
offered load.  Because bursts are keyed on the scheduler round (never
wall-clock), the shed/accept split and the full schedule replay exactly.

The GATED ``speedup`` is the round-capacity ratio

    (accepted tokens / service rounds) / (same requests / batch rounds)

where the denominator re-runs exactly the accepted request set through
batch ``engine.run()`` on a fresh engine.  Both schedules are
round-deterministic, so the ratio is timer-noise-free: it measures how
much per-round capacity the continuous-admission loop loses to ingress
handling (watermark checks, cancel scans, deadline sweeps) relative to
the batch scheduler on identical work.  A regression here means the
front door started costing rounds, not just microseconds.

Recorded informationally per cell (wall-clock, varies by host):
``accepted_tok_s`` (end-to-end accepted-token throughput),
``ttft_p50_ms``/``ttft_p99_ms`` (submit -> first token, from the
TokenStream timestamps of a streamed follow-up wave against the warm
service), and ``shed_rate`` (fraction of offered requests refused at the
watermark - deterministic, so drift flags an admission change even
before the gate trips).

``--quick`` runs the CI smoke cell only; ``--compare <baseline.json>``
fails on a >25% geomean regression (see _compare.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _compare import compare

from repro.configs import reduced_config
from repro.distributed.fault import FaultPlan
from repro.serve import Request, ServeConfig, ServeService, build_engine

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "BENCH_service.json")
ARCH = "stablelm-1.6b"


def _engine(cfg, params, slots, fault=None):
    return build_engine(ServeConfig(slots=slots, max_len=64, buckets=(8,),
                                    fault=fault), cfg=cfg, params=params)


def _wait(pred, timeout=900.0):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise TimeoutError("bench condition not reached")
        time.sleep(0.01)


def bench_cell(cfg, params, *, slots: int, watermark: int, rounds: int,
               per_round: int, max_new: int) -> dict:
    out = {"slots": slots, "watermark": watermark, "rounds": rounds,
           "per_round": per_round}

    # --- overload soak: deterministic burst storm through the service
    burst = {r: [[3 + (r + i) % 6, max_new] for i in range(per_round)]
             for r in range(rounds)}
    plan = FaultPlan(burst_rounds=burst)
    eng = _engine(cfg, params, slots, fault=plan.injector())
    svc = ServeService(eng, max_pending=watermark).start()
    offered = rounds * per_round
    t0 = time.perf_counter()
    # every offered request terminal (monotonic counters: no transient
    # window mid queue-to-slot handoff, unlike polling pending/active)
    _wait(lambda: eng.stats["shed"] + eng.stats["completed"] == offered)
    wall = time.perf_counter() - t0
    svc.stop()
    accepted = list(eng.finished)
    acc_tokens = sum(len(r.generated) for r in accepted)
    assert eng.stats["shed"] + eng.stats["completed"] == offered
    out["offered"] = offered
    out["accepted"] = len(accepted)
    out["shed_rate"] = eng.stats["shed"] / offered
    out["service_rounds"] = eng._round
    out["accepted_tok_s"] = acc_tokens / wall
    svc_per_round = acc_tokens / eng._round

    # --- batch reference: the SAME accepted set through run()
    ref = _engine(cfg, params, slots)
    copies = [Request(uid=r.uid, prompt=np.asarray(r.prompt),
                      max_new=r.max_new) for r in accepted]
    ref.run(copies)
    assert all(c.done and c.error is None for c in copies)
    assert ([tuple(c.generated) for c in copies]
            == [tuple(r.generated) for r in accepted]), \
        "service streams diverged from batch run()"
    out["batch_rounds"] = ref._round
    batch_per_round = acc_tokens / ref._round
    # gated: per-round capacity kept by the continuous-admission loop
    out["speedup"] = svc_per_round / batch_per_round

    # --- TTFT wave: streamed submits against the warm service
    eng2 = _engine(cfg, params, slots)
    svc2 = ServeService(eng2, max_pending=watermark).start()
    rng = np.random.default_rng(1)
    streams = []
    for i in range(2 * slots):
        streams.append(svc2.submit(
            rng.integers(0, cfg.vocab, 4 + i % 5).astype(np.int32),
            max_new=max_new))
    for s in streams:
        s.result(timeout=900)
    svc2.stop()
    ttft = sorted(1e3 * (s.first_token_at - s.submitted_at)
                  for s in streams if s.first_token_at is not None)
    out["ttft_p50_ms"] = ttft[len(ttft) // 2]
    out["ttft_p99_ms"] = ttft[min(len(ttft) - 1,
                                  int(0.99 * (len(ttft) - 1)))]
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke cell only")
    ap.add_argument("--out", default=None)
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="fail on >25%% speedup regression vs this baseline")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = reduced_config(ARCH)
    from repro.models import build_model
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    cells = [dict(slots=2, watermark=4, rounds=40, per_round=6, max_new=4)]
    if not args.quick:
        cells += [dict(slots=4, watermark=8, rounds=60, per_round=12,
                       max_new=4),
                  dict(slots=4, watermark=16, rounds=60, per_round=12,
                       max_new=8)]

    results = []
    for c in cells:
        cell = bench_cell(cfg, params, **c)
        print(f"slots={cell['slots']} watermark={cell['watermark']} "
              f"rounds={cell['rounds']}x{cell['per_round']}: "
              f"shed={cell['shed_rate']:.2f} "
              f"speedup={cell['speedup']:.3f} "
              f"acc={cell['accepted_tok_s']:.1f} tok/s "
              f"ttft p50={cell['ttft_p50_ms']:.1f}ms "
              f"p99={cell['ttft_p99_ms']:.1f}ms")
        results.append(cell)

    out = {
        "meta": {
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "arch": ARCH,
            "jax": jax.__version__,
            "quick": bool(args.quick),
        },
        "cells": results,
    }
    out_path = args.out or OUT
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"wrote {out_path}")
    if args.compare:
        sys.exit(compare(out, args.compare,
                         keys=("slots", "watermark", "rounds", "per_round")))


if __name__ == "__main__":
    main()
