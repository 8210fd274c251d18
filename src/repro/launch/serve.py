"""Serving driver: bucketed batched prefill + continuous batching with the
PDQ-int8 path, single-device, mesh-distributed, or multi-process.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --requests 8 --max-new 16 [--int8] [--int8-kv] \
        [--buckets 32,64,128] [--legacy-prefill] [--chunked-prefill] \
        [--mesh 4x2] [--slots-per-replica 2] [--num-processes 2]

``--mesh DxM`` serves over a ('data', 'model') device mesh
(ShardedServeEngine: slots data-parallel across D replicas, projection
columns tensor-parallel across M shards).  On the CPU backend
(``JAX_PLATFORMS=cpu``) the driver forces enough virtual devices
automatically - this line must run before jax imports, hence the early
environ bootstrap below.

``--num-processes N`` additionally splits the mesh over N OS processes
joined by ``jax.distributed`` (MultiHostServeEngine).  On the CPU backend
this process becomes a LAUNCHER that spawns N children (each re-runs this
driver with --process-id i), streams their output, and exits non-zero the
moment any child dies - so a hung or crashed worker is an actionable
failure, not a silent stall.  Child 0 is the serving coordinator; it
prints per-process admit/occupancy stats at the end.  On a TPU host the
launcher refuses (one process holds the chips): start one child per host
by hand with explicit --process-id/--coordinator.

A canned-trace run exits non-zero when any request failed in a launch
(kind ``exec`` or ``nonfinite``), even though request isolation kept the
engine serving.
"""
from __future__ import annotations

import sys


from repro.launch.mesh import bootstrap_mesh_env

bootstrap_mesh_env(sys.argv)

import argparse
import collections
import os
import signal
import subprocess
import threading
import time

import numpy as np

# typed child exit codes the launcher knows how to explain (keep in sync
# with repro.distributed.fault - imported lazily there to avoid pulling
# jax into the launcher before the children's env is set up)
_EXIT_MEANING = {87: "deadline watchdog fired (hung collective/dead peer)",
                 41: "fault-injection kill"}


def build_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--int8", action="store_true", help="PDQ int8 weights")
    ap.add_argument("--int8-kv", action="store_true", help="int8 KV cache")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="decode tokens fused per host dispatch (the N-step "
                         "decode fast path; 1 = classic per-token launches)")
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="max prompt length (lengths are drawn in [1, this])")
    ap.add_argument("--buckets", default="32,64,128,256",
                    help="comma-separated prefill length buckets")
    ap.add_argument("--legacy-prefill", action="store_true",
                    help="per-request prefill baseline (recompiles per "
                         "distinct prompt length)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="split prompts beyond the largest bucket into "
                         "bucket-sized chunks instead of rejecting them")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool: fixed-size pages + indirection "
                         "tables instead of slot rows (prefix sharing, "
                         "preempt-and-requeue under pressure)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="physical pages per replica (--paged; default "
                         "sizes the pool for slot-row parity)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable copy-on-write prompt-prefix sharing "
                         "(--paged)")
    ap.add_argument("--spill", action="store_true",
                    help="spill preempted pages to host memory for warm "
                         "resume (--paged, single-device only)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve over a data x model device mesh "
                         "(ShardedServeEngine)")
    ap.add_argument("--slots-per-replica", type=int, default=None,
                    help="cache slots per data-parallel replica "
                         "(default: --slots)")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="split --mesh over N jax.distributed processes "
                         "(spawns the children unless --process-id is set)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this child's jax.distributed process index "
                         "(set by the --num-processes launcher)")
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed coordinator address (default: the "
                         "launcher picks a free local port; a hand-started "
                         "child must be given one explicitly)")
    ap.add_argument("--launch-timeout", type=float, default=None,
                    help="per-launch deadline (seconds) for multi-process "
                         "collectives; a hung rendezvous exits with the "
                         "typed watchdog code instead of blocking forever")
    ap.add_argument("--snapshot", default=None, metavar="PATH",
                    help="write the scheduler drain record here on "
                         "preemption (SIGTERM) or fleet failure")
    ap.add_argument("--resume", default=None, metavar="PATH",
                    help="requeue the unfinished requests of a previous "
                         "run's --snapshot record instead of generating a "
                         "fresh trace")
    ap.add_argument("--pdq-fallback", action="store_true",
                    help="guard every PDQ projection with a per-launch "
                         "fp-dequant fallback on non-finite output")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve an HTTP front door instead of a canned "
                         "trace: POST /v1/completions (SSE streaming), "
                         "GET /healthz, GET /v1/stats; 0 picks a free port "
                         "(printed on startup).  SIGTERM/SIGINT drain, "
                         "snapshot (--snapshot) and exit cleanly")
    ap.add_argument("--max-pending", type=int, default=32,
                    help="HTTP admission watermark: submits past this many "
                         "queued requests are shed with 429 + Retry-After")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace-event JSON (Perfetto-"
                         "loadable) of request/phase spans here on exit; "
                         "with --num-processes the coordinator writes ONE "
                         "merged trace with a process row per jax process")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable the metrics registry + lifecycle timing "
                         "(the <=2%% overhead A/B switch; /metrics then "
                         "renders empty)")
    return ap.parse_args(argv)


def _fmt_ms(seconds: float) -> str:
    return f"{seconds * 1e3:.1f}ms"


def report_telemetry(eng, args) -> None:
    """Drain/exit printout: latency histogram summaries (p50/p90/p99) and
    the shed rate, plus the --trace-out write.  Shared by the canned-trace
    and --http exits."""
    summ = eng.tel.summary()
    for key, label in (("ttft", "ttft"), ("per_token", "per-token"),
                       ("queue_wait", "queue wait")):
        s = summ.get(key)
        if s and s["count"]:
            print(f"  {label}: n={s['count']} p50={_fmt_ms(s['p50'])} "
                  f"p90={_fmt_ms(s['p90'])} p99={_fmt_ms(s['p99'])}")
    shed = eng.stats.get("shed", 0)
    served = len(eng.finished)
    if shed:
        print(f"  shed: {shed} requests "
              f"({shed / max(shed + served, 1):.1%} of submitted)")
    if args.trace_out:
        eng.tel.tracer.write(args.trace_out)
        n = len(eng.tel.tracer.events())
        print(f"  trace: {n} spans -> {args.trace_out}", flush=True)


def _tee_stderr(proc, ring) -> threading.Thread:
    """Stream a child's stderr to ours while keeping the tail in ``ring``
    (the launcher's post-mortem: WHAT the dead process last said)."""

    def pump():
        for line in iter(proc.stderr.readline, ""):
            ring.append(line.rstrip("\n"))
            sys.stderr.write(line)
            sys.stderr.flush()
        proc.stderr.close()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    return t


def spawn_processes(args, argv) -> int:
    """Launcher mode: spawn one child per process, fail fast and LOUD.

    Children share this terminal's stdout (their prints are the
    per-process log); stderr is teed through a per-child ring buffer.
    The first child to exit non-zero takes the fleet down: remaining
    children are terminated, and the launcher reports WHICH process died,
    its exit code (decoded for the typed watchdog/fault-injection codes)
    and the last lines it wrote to stderr - so CI sees an actionable
    post-mortem instead of a bare non-zero exit or a 6-hour hang.

    A SIGTERM to the launcher is forwarded to the coordinator child
    (process 0) only: it drains, snapshots (with --snapshot) and releases
    the workers through the command protocol, so the whole fleet exits
    cleanly."""
    from repro.launch.mesh import (pick_coordinator, refuse_local_processes,
                                   strip_forced_device_count)
    refuse_local_processes("--num-processes")
    env = dict(os.environ)
    env["XLA_FLAGS"] = strip_forced_device_count(env.get("XLA_FLAGS", ""))
    coordinator = pick_coordinator(args.coordinator)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", *argv,
         "--coordinator", coordinator, "--process-id", str(i)], env=env,
        stderr=subprocess.PIPE, text=True)
        for i in range(args.num_processes)]
    rings = [collections.deque(maxlen=20) for _ in procs]
    tees = [_tee_stderr(p, r) for p, r in zip(procs, rings)]
    live = dict(enumerate(procs))

    def forward_term(signum, frame):
        # SIGINT rides the same path as SIGTERM: forward to the
        # coordinator child BEFORE any reaping - it drains, snapshots and
        # releases the workers through the command protocol, and the
        # launcher's poll loop then collects everyone's clean exit
        if 0 in live:
            live[0].send_signal(signal.SIGTERM)     # coordinator drains

    prev = signal.signal(signal.SIGTERM, forward_term)
    prev_int = signal.signal(signal.SIGINT, forward_term)
    try:
        while live:
            time.sleep(0.2)
            for i, p in list(live.items()):
                rc = p.poll()
                if rc is None:
                    continue
                del live[i]
                if rc != 0:
                    meaning = _EXIT_MEANING.get(rc)
                    why = f" [{meaning}]" if meaning else ""
                    print(f"serve launcher: process {i} died with exit code "
                          f"{rc}{why}; terminating {len(live)} remaining",
                          file=sys.stderr, flush=True)
                    for t in tees:
                        t.join(timeout=2)
                    tail = list(rings[i])
                    if tail:
                        print(f"serve launcher: last stderr of process {i}:",
                              file=sys.stderr)
                        for line in tail:
                            print(f"  [proc {i}] {line}", file=sys.stderr)
                        sys.stderr.flush()
                    for q in live.values():
                        q.terminate()
                    for q in live.values():
                        q.wait()
                    return rc
        return 0
    finally:
        signal.signal(signal.SIGTERM, prev)
        signal.signal(signal.SIGINT, prev_int)
        for t in tees:
            t.join(timeout=2)


def serve_http(args, eng, multiproc: bool) -> None:
    """``--http`` mode: the streaming front door (serve/service.py +
    serve/frontend.py) drives the scheduler continuously; requests arrive
    over HTTP instead of a canned trace.  SIGTERM and SIGINT both route
    through ``request_drain()``: the loop stops at a round boundary,
    unfinished streams get a typed ``drain`` finish, the snapshot is
    written (--snapshot), and a later ``--resume`` run regenerates the
    interrupted work token-exactly."""
    import asyncio

    from repro.serve import HttpFrontend, ServeService

    svc = ServeService(eng, max_pending=args.max_pending)
    if args.resume:
        from repro.distributed.fault import load_snapshot
        from repro.serve import resume_requests
        done, reqs = resume_requests(load_snapshot(args.resume))
        eng.pending.extend(reqs)       # headless requeue: no client holds
        print(f"resuming {len(reqs)} unfinished requests "   # these streams
              f"({len(done)} already finished) from {args.resume}",
              flush=True)
    svc.start()

    async def amain():
        fe = await HttpFrontend(svc, port=args.http).start()
        print(f"serving HTTP on 127.0.0.1:{fe.port} "
              f"(watermark {args.max_pending})", flush=True)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()

        def on_signal():
            svc.request_drain()
            stop.set()

        for sig in (signal.SIGTERM, signal.SIGINT):
            loop.add_signal_handler(sig, on_signal)
        await stop.wait()
        # the drain must COMPLETE while this loop is still alive: open SSE
        # handlers deliver their typed 'drain' finish through loop wakers,
        # and closing the loop first would strand them mid-stream
        await loop.run_in_executor(None, svc.join, 600.0)
        await fe.stop()

    asyncio.run(amain())
    svc.join(timeout=600)
    if multiproc:
        eng.stop_workers()
    if svc.error is not None:
        raise SystemExit(f"serve loop failed: {svc.error!r}")
    done = len(eng.finished)
    left = len(eng.pending) + sum(r is not None for r in eng.active)
    print(f"drained: {done} requests finished, {left} unfinished "
          + (f"snapshotted to {eng.snapshot_path}" if eng.snapshot_path
             else "(no --snapshot: progress dropped)"), flush=True)
    print("  stats:  ", {k: v for k, v in eng.stats.items()
                         if not k.startswith("replica_")})
    report_telemetry(eng, args)


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    args = build_args(argv)

    multiproc = args.num_processes > 1 or args.process_id is not None
    if multiproc:
        if not args.mesh:
            raise SystemExit("--num-processes requires --mesh DxM")
        if args.legacy_prefill:
            raise SystemExit("--legacy-prefill is single-device only")
    if args.num_processes > 1 and args.process_id is None:
        raise SystemExit(spawn_processes(args, argv))

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if multiproc:
        # child: join the jax.distributed job BEFORE any device query
        if not args.coordinator:
            raise SystemExit("a hand-started --process-id child needs an "
                             "explicit --coordinator HOST:PORT")
        from repro.launch.mesh import init_distributed
        init_distributed(args.coordinator, args.num_processes,
                         args.process_id)

    from repro.configs import ALL_ARCHS
    from repro.launch.mesh import make_serve_mesh, parse_mesh
    from repro.serve import Request, ServeConfig, build_engine

    if args.arch not in ALL_ARCHS:
        raise SystemExit(f"unknown --arch {args.arch!r}; "
                         f"choose from {sorted(ALL_ARCHS)}")
    if args.paged and args.legacy_prefill:
        raise SystemExit("--paged needs the bucketed prefill path")

    mesh = None
    if args.mesh:
        data, model = parse_mesh(args.mesh)
        if data % max(args.num_processes, 1):
            raise SystemExit(f"--mesh data axis ({data}) must divide over "
                             f"--num-processes ({args.num_processes})")
        mesh = make_serve_mesh(data, model)

    sc = ServeConfig(
        arch=args.arch, reduced=args.reduced, int8_kv=args.int8_kv,
        slots=args.slots, max_len=args.max_len,
        quantize_weights=args.int8, temperature=args.temperature,
        buckets=tuple(int(b) for b in args.buckets.split(",")),
        batch_prefill=not args.legacy_prefill,
        chunked_prefill=args.chunked_prefill,
        decode_steps=args.decode_steps,
        pdq_fallback=args.pdq_fallback, mesh=mesh,
        slots_per_replica=args.slots_per_replica or args.slots,
        multihost=multiproc, launch_timeout=args.launch_timeout,
        snapshot_path=args.snapshot, paged=args.paged,
        page_size=args.page_size, pool_pages=args.pool_pages,
        prefix_sharing=not args.no_prefix_sharing, spill=args.spill,
        telemetry=not args.no_telemetry, trace=args.trace_out is not None)
    try:
        eng = build_engine(sc)
    except ValueError as e:
        raise SystemExit(str(e))
    cfg = eng.cfg

    if mesh is not None:
        spr = args.slots_per_replica or args.slots
        mode = f"sharded {data}x{model} ({spr} slots/replica)"
        if multiproc:
            mode += f" x{args.num_processes}proc"
    else:
        mode = "legacy" if args.legacy_prefill else "bucketed"
    if args.paged:
        mode += f" paged/{args.page_size}"

    if multiproc and not eng.is_coordinator:
        print(f"[proc {args.process_id}] worker following the coordinator "
              f"command stream", flush=True)
        eng.serve_worker()
        print(f"[proc {args.process_id}] worker done", flush=True)
        return

    if args.http is not None:
        return serve_http(args, eng, multiproc)

    if args.resume:
        # requeue the previous run's unfinished work (progress cleared:
        # (uid, step)-keyed sampling regenerates the identical tokens)
        from repro.distributed.fault import load_snapshot
        from repro.serve import resume_requests
        done, reqs = resume_requests(load_snapshot(args.resume))
        print(f"resuming {len(reqs)} unfinished requests "
              f"({len(done)} already finished) from {args.resume}",
              flush=True)
    else:
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab,
                                            int(rng.integers(1, args.prompt_len + 1))),
                        max_new=args.max_new) for i in range(args.requests)]
    # preemption: SIGTERM/SIGINT request a drain - the scheduler finishes
    # the round, snapshots (with --snapshot) and run() returns; the
    # workers are then released through the normal CMD_STOP
    signal.signal(signal.SIGTERM, lambda *_: eng.request_drain())
    signal.signal(signal.SIGINT, lambda *_: eng.request_drain())
    t0 = time.perf_counter()
    eng.run(reqs)
    if multiproc:
        eng.stop_workers()
    dt = time.perf_counter() - t0
    if eng.drained:
        left = sum(not r.done for r in reqs)
        print(f"drained on preemption: {left} unfinished requests "
              + (f"snapshotted to {eng.snapshot_path}" if eng.snapshot_path
                 else "(no --snapshot: progress dropped)"), flush=True)
    total_new = sum(len(r.generated) for r in reqs)
    print(f"served {len(reqs)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s) int8={args.int8} int8_kv={args.int8_kv} "
          f"prefill={mode}")
    print("  buckets:", eng.buckets)
    print("  stats:  ", {k: v for k, v in eng.stats.items()
                         if not k.startswith("replica_")})
    report_telemetry(eng, args)
    for r, (adm, occ) in enumerate(zip(eng.stats["replica_admits"],
                                       eng.stats["replica_occupancy"])):
        print(f"  replica {r}: admits={adm} occupied={occ}/"
              f"{eng.slots_per_replica}")
    if multiproc:
        for proc, hs in sorted(eng.host_stats().items()):
            print(f"  process {proc}: replicas={hs['replicas']} "
                  f"admits={hs['admits']} occupied={hs['occupied']}/"
                  f"{hs['slots']}")
    for r in reqs[:3]:
        print(f"  req {r.uid}: {r.generated}")
    # request isolation keeps the engine serving past a failed launch; a
    # canned trace still has to report it: a compile error or non-finite
    # logits is a broken run, not a served one
    broken = eng.failures.count("exec") + eng.failures.count("nonfinite")
    if broken:
        first = next(e for e in eng.failures.events
                     if e["kind"] in ("exec", "nonfinite"))
        raise SystemExit(f"serve: {broken} requests failed in a launch; "
                         f"first: {first['detail'][:2000]}")


if __name__ == "__main__":
    main()
