"""Run the stablelm-1.6b serving path once on a TPU at its published widths.

    python3 chip_smoke.py                # phases A and B on one chip
    python3 chip_smoke.py --mesh 2x2     # the four-chip tensor-parallel path

Weights are random, made from ``--seed``; width and depth are the
published ones (24 layers, d_model 2048, 32 x 64 heads, d_ff 5632, vocab
100352, bf16).  Everything runs in this one process: a chip belongs to one
process at a time.

Phase A - bf16 weights, fp KV, slot-row pool, behind the HTTP front door
  (``ServeService`` + ``HttpFrontend`` on a free port).  Three prompts
  (16 to 512 tokens) are each sent once streamed and once not; the two
  token lists of a prompt must be identical.
Phase B - PDQ-int8 weights, int8 KV, paged pool, 4 decode steps per
  dispatch: every Pallas kernel of the serving path, compiled.  Tokens must
  equal the same engine at 1 decode step per dispatch, and its prefill
  logits must agree with the engine rebuilt on the jnp reference ops
  (``ops.set_impl("ref")``) to the stated tolerance.
``--mesh 2x2`` runs phase B only, on a ``ShardedServeEngine`` over four
  chips (data 2 x model 2), against the single-device engine on device 0:
  tokens must be identical, and the mesh must hold its state on all four
  chips.

Every phase checks: the device is a TPU, every request finishes without an
error, no PDQ projection fell back to fp, and the decode block compiled
once.  Earlier lines report the device kind, compile seconds, tokens
served and ``peak_bytes_in_use`` per phase; the last line is one JSON
object naming the device.  Any failure exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import asyncio
import concurrent.futures
import dataclasses
import gc
import http.client
import json
import os
import sys
import threading
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "stablelm-1.6b"
MAX_NEW = 16
SLOTS = 8
MAX_LEN = 1024
BUCKETS = (128, 512)
PROMPT_LENS_A = (16, 137, 512)
PROMPT_LENS_B = (16, 64, 137, 300, 431, 512)
# kernel vs jnp-reference prefill logits, relative L2 error.  The fused
# kernels round in a different order than the unfused reference (the
# SwiGLU stage keeps f32 where the reference rounds gate/up to bf16; the
# epilogue sums run chunked), PDQ quantizes every activation row to 1/127
# of its range, and 24 bf16 layers carry those ulp-level differences to
# the logits.  A wrong kernel is off by O(1).
LOGIT_RTOL = 0.1

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _on_duration(event: str, duration: float, **_) -> None:
    if event in COMPILE_EVENTS:
        _compile_s[0] += duration


def _mem(device, key: str):
    """A device memory statistic, or None where the backend keeps none."""
    return (device.memory_stats() or {}).get(key)


def report(phase: str, t0: float, c0: float, tokens: int) -> None:
    import jax
    peak = _mem(jax.devices()[0], "peak_bytes_in_use")
    print(f"phase {phase}: ok  wall_s={time.perf_counter() - t0:.1f} "
          f"compile_s={_compile_s[0] - c0:.1f} tokens_served={tokens} "
          f"peak_bytes_in_use={peak}", flush=True)


def check_engine(eng, phase: str) -> None:
    st = eng.stats
    bad = [e for e in eng.failures.events
           if e["kind"] in ("exec", "nonfinite", "plan")]
    check(st["failed"] == 0 and not bad, f"{phase}: failed requests: {bad[:2]}")
    check(st["pdq_fallbacks"] == 0,
          f"{phase}: {st['pdq_fallbacks']} PDQ projections fell back to fp")
    check(st["decode_compiles"] == 1,
          f"{phase}: decode compiled {st['decode_compiles']} times")


def prompts(vocab: int, lens, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in lens]


# ------------------------------------------------------------------ phase A

def _complete(port: int, prompt, stream: bool):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": [int(t) for t in prompt],
                                 "max_tokens": MAX_NEW, "stream": stream}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f"HTTP {resp.status}: {resp.read()[:500]!r}")
        if not stream:
            obj = json.loads(resp.read())
            return obj["tokens"], obj["finish_reason"], obj["error"]
        toks, fin = [], {}
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            if data == "[DONE]":
                break
            ev = json.loads(data)
            if "token" in ev:
                toks.append(ev["token"])
            else:
                fin = ev
        return toks, fin.get("finish_reason"), fin.get("error")
    finally:
        conn.close()


def phase_a(cfg, params, seed: int):
    from repro.serve import HttpFrontend, ServeConfig, ServeService, build_engine

    t0, c0 = time.perf_counter(), _compile_s[0]
    eng = build_engine(ServeConfig(arch=ARCH, reduced=False, slots=SLOTS,
                                   max_len=MAX_LEN, buckets=BUCKETS),
                       cfg=cfg, params=params)
    svc = ServeService(eng, max_pending=32).start()
    loop = asyncio.new_event_loop()
    lt = threading.Thread(target=loop.run_forever, daemon=True)
    lt.start()
    fe = HttpFrontend(svc, port=0)
    asyncio.run_coroutine_threadsafe(fe.start(), loop).result(60)
    try:
        ps = prompts(cfg.vocab, PROMPT_LENS_A, seed)
        jobs = [(i, stream) for i in range(len(ps)) for stream in (True, False)]
        with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
            futs = {job: pool.submit(_complete, fe.port, ps[job[0]], job[1])
                    for job in jobs}
            got = {job: f.result(timeout=1100) for job, f in futs.items()}
        for (i, stream), (toks, reason, err) in got.items():
            check(err is None and reason not in ("failed", "drain"),
                  f"A: prompt {i} stream={stream}: {reason} {err}")
            check(len(toks) == MAX_NEW,
                  f"A: prompt {i} stream={stream}: {len(toks)} tokens")
        for i in range(len(ps)):
            check(got[(i, True)][0] == got[(i, False)][0],
                  f"A: prompt {i} streamed {got[(i, True)][0]} != "
                  f"non-streamed {got[(i, False)][0]}")
    finally:
        svc.request_drain()
        svc.join(600)
        asyncio.run_coroutine_threadsafe(fe.stop(), loop).result(60)
        loop.call_soon_threadsafe(loop.stop)
        lt.join(10)
    check(svc.error is None, f"A: service loop died: {svc.error!r}")
    check_engine(eng, "A")
    report("A bf16/fp-kv/slot-rows/http", t0, c0,
           sum(len(t) for t, _, _ in got.values()))


# ------------------------------------------------------------------ phase B

def phase_b_config(**kw):
    from repro.serve import ServeConfig
    return ServeConfig(arch=ARCH, reduced=False, int8_kv=True,
                       quantize_weights=True, paged=True, page_size=64,
                       slots=SLOTS, max_len=MAX_LEN, buckets=BUCKETS, **kw)


def run_requests(eng, cfg, seed: int, phase: str):
    from repro.serve import Request
    reqs = [Request(uid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(prompts(cfg.vocab, PROMPT_LENS_B, seed))]
    eng.run(reqs)
    for r in reqs:
        check(r.done and r.error is None and len(r.generated) == MAX_NEW,
              f"{phase}: request {r.uid}: {r.error} "
              f"({len(r.generated)} tokens)")
    check_engine(eng, phase)
    return [list(r.generated) for r in reqs]


def prefill_batch(cfg, seed: int):
    """One full prefill batch in the 512 bucket: (tokens, seq_lens)."""
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    toks = rng.integers(1, cfg.vocab, (SLOTS, BUCKETS[-1])).astype(np.int32)
    lens = np.linspace(16, BUCKETS[-1], SLOTS).astype(np.int32)
    return toks, lens


def engine_prefill_logits(eng, toks, lens):
    """The engine's own compiled prefill program's last-token logits."""
    import jax.numpy as jnp
    import numpy as np
    (logits, _), _ = eng._prefill_many(eng.params, {"tokens": jnp.asarray(toks)},
                                       eng._prefill_pool, jnp.asarray(lens))
    return np.asarray(logits, np.float32)


def phase_b(cfg, params, seed: int):
    import numpy as np

    from repro.kernels import ops
    from repro.serve import build_engine

    t0, c0 = time.perf_counter(), _compile_s[0]
    cfg_b = dataclasses.replace(cfg, quant_kv="dynamic")
    eng = build_engine(phase_b_config(decode_steps=4), cfg=cfg_b, params=params)
    toks4 = run_requests(eng, cfg, seed, "B decode_steps=4")
    served = sum(map(len, toks4))
    del eng
    gc.collect()

    eng = build_engine(phase_b_config(decode_steps=1), cfg=cfg_b, params=params)
    toks1 = run_requests(eng, cfg, seed, "B decode_steps=1")
    served += sum(map(len, toks1))
    check(toks4 == toks1, f"B: decode_steps=4 tokens {toks4} != "
                          f"decode_steps=1 tokens {toks1}")
    pt, pl = prefill_batch(cfg, seed)
    lg_kernel = engine_prefill_logits(eng, pt, pl)
    del eng
    gc.collect()

    ops.set_impl("ref")
    try:
        eng = build_engine(phase_b_config(decode_steps=1), cfg=cfg_b,
                           params=params)
        lg_ref = engine_prefill_logits(eng, pt, pl)
    finally:
        ops.set_impl("auto")
    del eng
    gc.collect()
    check(np.isfinite(lg_kernel).all() and np.isfinite(lg_ref).all(),
          "B: non-finite prefill logits")
    rel = float(np.linalg.norm(lg_kernel - lg_ref) / np.linalg.norm(lg_ref))
    agree = float((lg_kernel.argmax(-1) == lg_ref.argmax(-1)).mean())
    print(f"phase B: kernel vs reference prefill logits: rel_l2={rel:.3e} "
          f"(limit {LOGIT_RTOL}) max_abs="
          f"{float(np.abs(lg_kernel - lg_ref).max()):.3e} "
          f"argmax_agree={agree:.2f}", flush=True)
    check(rel <= LOGIT_RTOL, f"B: prefill logits rel_l2 {rel} > {LOGIT_RTOL}")
    report("B pdq-int8/int8-kv/paged/decode_steps=4", t0, c0, served)


# ------------------------------------------------------------- --mesh 2x2

def phase_mesh(cfg, params, seed: int, spec: str):
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import serve_pool_specs
    from repro.launch.mesh import make_serve_mesh, parse_mesh
    from repro.serve import build_engine

    t0, c0 = time.perf_counter(), _compile_s[0]
    data, model = parse_mesh(spec)
    n, spr = data * model, SLOTS // data
    check(len(jax.devices()) >= n, f"--mesh {spec} needs {n} chips, found "
                                   f"{len(jax.devices())}")
    cfg_b = dataclasses.replace(cfg, quant_kv="dynamic")
    pt, pl = prefill_batch(cfg, seed)

    single = build_engine(phase_b_config(decode_steps=4), cfg=cfg_b,
                          params=params)
    want = run_requests(single, cfg, seed, "single device")
    lg_single = engine_prefill_logits(single, pt, pl)
    del single
    gc.collect()

    mesh = make_serve_mesh(data, model)
    eng = build_engine(phase_b_config(decode_steps=4, mesh=mesh,
                                      slots_per_replica=spr),
                       cfg=cfg_b, params=params)
    devs = {d for leaf in jax.tree.leaves((eng.params, eng.caches))
            for d in leaf.sharding.device_set}
    check(len(set(mesh.devices.flat)) == n and len(devs) == n,
          f"mesh state spans {len(devs)} devices, expected {n}")
    got = run_requests(eng, cfg, seed, f"mesh {spec}")

    cs = serve_pool_specs(eng._prefill_pool)
    prefill = jax.jit(eng._sharded(eng.bundle.prefill_many,
                                   in_specs=(P(), P("data"), cs, P("data")),
                                   out_specs=(P("data"), cs)))
    logits, _ = prefill(eng.params, {"tokens": pt}, eng._prefill_pool, pl)
    diff = float(np.abs(np.asarray(logits, np.float32) - lg_single).max())
    used = [_mem(d, "bytes_in_use") for d in mesh.devices.flat]
    same = sum(g == w for g, w in zip(got, want))
    print(f"mesh {spec}: {same}/{len(want)} token streams identical to the "
          f"single-device engine; max |prefill logit diff| = {diff:.3e}; "
          f"bytes_in_use per chip = {used}", flush=True)
    check(got == want, f"mesh {spec} tokens {got} != single-device {want}")
    check(all(u is not None and u > 1 << 30 for u in used),
          f"mesh {spec}: a chip holds under 1 GiB: {used}")
    report(f"mesh {spec} pdq-int8/int8-kv/paged/decode_steps=4", t0, c0,
           sum(map(len, got)) + sum(map(len, want)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run only phase B on a DxM ('data', 'model') mesh "
                         "of chips, against the single-device engine")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: JAX found no TPU (platform "
              f"{dev.platform!r}); this check runs on the chip only",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model

    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device_kind: {dev.device_kind}  devices: {len(devices)}  "
          f"jax {jax.__version__}  compile cache: {cache}", flush=True)
    try:
        check(ops._use_kernel() and not ops._interpret(),
              "the Pallas kernels would not run compiled on this backend")
        cfg = get_config(ARCH)
        t0 = time.perf_counter()
        params = jax.block_until_ready(
            jax.jit(build_model(cfg).init)(jax.random.PRNGKey(args.seed)))
        n_params = sum(x.size for x in jax.tree.leaves(params))
        print(f"{ARCH}: {cfg.n_layers} layers d_model={cfg.d_model} "
              f"heads={cfg.n_heads}x{cfg.hd} d_ff={cfg.d_ff} "
              f"vocab={cfg.vocab} {cfg.dtype}: {n_params} params "
              f"initialised in {time.perf_counter() - t0:.1f}s", flush=True)
        if args.mesh:
            phase_mesh(cfg, params, args.seed, args.mesh)
        else:
            phase_a(cfg, params, args.seed)
            # phase A's engine, service and front door reference each
            # other: free their device pools before phase B allocates
            gc.collect()
            phase_b(cfg, params, args.seed)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAIL", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
