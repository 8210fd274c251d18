"""MultiHostServeEngine: real multi-process ``jax.distributed`` serving.

Pins the PR-5 contract: 2 OS processes x 4 virtual CPU devices each,
joined into one ('data', 'model') = 4x2 logical mesh by
``jax.distributed.initialize`` (gloo CPU collectives), serve
token-for-token identically to the single-process ``ShardedServeEngine``
on the SAME logical mesh - fp and PDQ-int8 - with the coordinator on
process 0 owning admission and the workers following the broadcast
command stream.

Every subprocess gets a HARD timeout: a hung coordinator/worker pair
(desynced collective, dead peer) fails the test in minutes, not the CI
job's multi-hour default.
"""
import json
import os
import socket
import subprocess
import sys
import tempfile
import textwrap

import pytest

from repro.distributed.sharding import process_replicas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIMEOUT = 900           # hard per-subprocess cap (seconds)

# the acceptance trace: mixed lengths spanning all three buckets
_CASES = """
    import json
    import sys

    MIXED = [3, 5, 8, 9, 12, 16, 17, 23, 30, 4, 11, 27]

    def requests(cfg, lens, max_new, seed=0):
        rng = np.random.default_rng(seed)
        return [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                        max_new=max_new) for i, L in enumerate(lens)]

    # (name, lens, max_new, engine kwargs) - identical for ref and
    # multi-host runs; every case runs on the 4x2 logical mesh with 2
    # slots per data replica.
    CASES = [
        ("fp", MIXED, 6, dict(max_len=64, buckets=(8, 16, 32))),
        ("int8", MIXED, 6, dict(max_len=64, buckets=(8, 16, 32),
                                quantize_weights=True)),
        ("chunked", [4, 20, 40, 11], 4, dict(max_len=64, buckets=(8, 16),
                                             chunked_prefill=True)),
        # paged KV pool over the wire: land maps + page tables ride the
        # command payloads (single-device parity is pinned in
        # test_serve_paged.py; here paged-multihost == paged-sharded).
        # The tight pool (5 usable pages/replica, 2-page prompts growing
        # to 3) forces preempt-and-requeue through the broadcast stream.
        ("paged", MIXED, 6, dict(max_len=64, buckets=(8, 16, 32),
                                 temperature=0.9, paged=True,
                                 page_size=16)),
        ("paged_tight", [17] * 8, 30, dict(max_len=64,
                                           buckets=(8, 16, 32),
                                           temperature=0.9, paged=True,
                                           page_size=16, pool_pages=6)),
        # N-step fused decode over the wire: CMD_DECODE ships the block
        # size (workers verify lockstep), decode runs 4 steps per
        # dispatch inside the shard_map-ed scan, and ONE (slots, N)
        # token block comes back per round.  The reference run strips
        # decode_steps, so these pin multihost N=4 == sharded N=1
        # token-for-token - including preempt-and-requeue under the
        # tight pool.
        ("nstep", MIXED, 9, dict(max_len=64, buckets=(8, 16, 32),
                                 temperature=0.9, decode_steps=4)),
        ("nstep_tight", [17] * 8, 30, dict(max_len=64, buckets=(8, 16, 32),
                                           temperature=0.9, paged=True,
                                           page_size=16, pool_pages=6,
                                           decode_steps=4)),
    ]
"""

_REF = _CASES + """
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch.mesh import make_serve_mesh
    from repro.serve import Request, ShardedServeEngine

    cfg = reduced_config("stablelm-1.6b")
    from repro.models import build_model
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    mesh = make_serve_mesh(4, 2)
    out = {}
    for name, lens, max_new, kw in CASES:
        # the reference always decodes single-step: a decode_steps case
        # therefore pins multihost N-step == sharded N=1 across engines
        eng = ShardedServeEngine(cfg, params, mesh=mesh, slots_per_replica=2,
                                 **{k: v for k, v in kw.items()
                                    if k != "decode_steps"})
        reqs = requests(cfg, lens, max_new)
        eng.run(reqs)
        assert all(r.done for r in reqs)
        out[name] = [list(map(int, r.generated)) for r in reqs]
    with open(sys.argv[1], "w") as f:
        json.dump(out, f)
    print("REF OK")
"""

_MULTI = _CASES + """
    proc, port, out_path = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=2,
                               process_id=proc)
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch.mesh import make_serve_mesh
    from repro.serve import MultiHostServeEngine, Request

    assert jax.process_count() == 2 and len(jax.devices()) == 8
    cfg = reduced_config("stablelm-1.6b")
    from repro.models import build_model
    params = build_model(cfg).init(jax.random.PRNGKey(0))   # same seed: host-replicated
    mesh = make_serve_mesh(4, 2)
    out = {"host_stats": {}}
    for name, lens, max_new, kw in CASES:
        eng = MultiHostServeEngine(cfg, params, mesh=mesh,
                                   slots_per_replica=2, **kw)
        if proc == 0:
            reqs = requests(cfg, lens, max_new)
            eng.run(reqs)
            eng.stop_workers()
            assert all(r.done for r in reqs)
            out[name] = [list(map(int, r.generated)) for r in reqs]
            out["host_stats"][name] = {str(k): v
                                       for k, v in eng.host_stats().items()}
            out.setdefault("stats", {})[name] = {
                k: v for k, v in eng.stats.items()
                if k.endswith("_compiles") or k.startswith("replica_")
                or k in ("preemptions", "pages_total")}
        else:
            eng.serve_worker()
    if proc == 0:
        with open(out_path, "w") as f:
            json.dump(out, f)
    print("PROC", proc, "OK")
"""


def _env(devices: int) -> dict:
    # several jax processes on one host: a CPU-backend-only topology
    return dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run(code: str, argv: list[str], devices: int) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code), *argv],
                          capture_output=True, text=True, env=_env(devices),
                          cwd=REPO, timeout=TIMEOUT)


_BIND_RACE = ("EADDRINUSE", "Address already in use",
              "address already in use")


def _spawn_fleet(code: str, argv: list[str], *, n_procs: int = 2,
                 devices: int = 4, attempts: int = 3, timeout: int = TIMEOUT,
                 hang_ok: tuple[int, ...] = ()):
    """Spawn an n-process ``jax.distributed`` fleet on a fresh ephemeral
    port; each child gets [process_id, port, *argv].  Returns
    (procs, [(stdout, stderr), ...]).

    The coordination-service port is probed with ``_free_port()`` and can
    be grabbed by another process between the probe and jax binding it
    (parallel CI shards on one host), so an EADDRINUSE death of the fleet
    is retried on a NEW port instead of failing the test.

    ``hang_ok`` names process indices that are EXPECTED to hang (injected
    fault): they are killed once every other process has exited, instead
    of burning the full timeout waiting for them."""
    for attempt in range(attempts):
        port = _free_port()
        procs = [subprocess.Popen(
            [sys.executable, "-c", textwrap.dedent(code),
             str(i), str(port), *argv],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=_env(devices), cwd=REPO) for i in range(n_procs)]
        outs: list = [None] * n_procs
        try:
            for i, p in enumerate(procs):
                if i not in hang_ok:
                    outs[i] = p.communicate(timeout=timeout)
            for i in hang_ok:
                procs[i].kill()
                outs[i] = procs[i].communicate(timeout=60)
        finally:
            for p in procs:
                p.kill()
        raced = any(p.returncode not in (0, None)
                    and any(m in se for m in _BIND_RACE)
                    for p, (_, se) in zip(procs, outs))
        if raced and attempt < attempts - 1:
            continue
        return procs, outs
    raise AssertionError("unreachable")


def test_multihost_matches_single_process_sharded_engine():
    """Acceptance pin: 2 jax.distributed processes (4 virtual devices
    each) serve the mixed 12-request trace token-for-token identically to
    the single-process ShardedServeEngine on the same 4x2 logical mesh,
    fp AND int8 (plus a chunked-prefill case), and the coordinator's
    per-host accounting shows both processes' replicas admitting."""
    with tempfile.TemporaryDirectory() as td:
        ref_path = os.path.join(td, "ref.json")
        ref = _run(_REF, [ref_path], devices=8)
        assert ref.returncode == 0, ref.stderr[-3000:]

        mh_path = os.path.join(td, "mh.json")
        procs, outs = _spawn_fleet(_MULTI, [mh_path], n_procs=2, devices=4)
        for p, (so, se) in zip(procs, outs):
            assert p.returncode == 0, (so[-1500:], se[-3000:])

        with open(ref_path) as f:
            want = json.load(f)
        with open(mh_path) as f:
            got = json.load(f)

    for name in ("fp", "int8", "chunked", "paged", "paged_tight",
                 "nstep", "nstep_tight"):
        assert got[name] == want[name], (
            name, [i for i, (a, b) in enumerate(zip(got[name], want[name]))
                   if a != b])
    # coordinator accounting: admission spread across BOTH hosts' replicas,
    # every pool drained, and the compile counts stay bucket-bounded
    hs = got["host_stats"]["fp"]
    assert set(hs) == {"0", "1"}
    assert all(h["replicas"] == 2 and h["slots"] == 4 for h in hs.values())
    assert all(h["admits"] >= 1 and h["occupied"] == 0 for h in hs.values())
    assert sum(h["admits"] for h in hs.values()) == 12
    st = got["stats"]["fp"]
    assert st["decode_compiles"] == 1
    assert st["prefill_compiles"] <= 3
    assert min(st["replica_admits"]) >= 1
    # the tight paged pool actually preempted (and still matched the
    # single-process engine token for token above)
    assert got["stats"]["paged_tight"]["preemptions"] > 0
    # N-step blocks: one fused program, and the preempt-and-requeue path
    # stays token-exact at N=4 too (compared against the N=1 ref above)
    assert got["stats"]["nstep"]["decode_compiles"] == 1
    assert got["stats"]["nstep_tight"]["preemptions"] > 0


def test_multihost_engine_degenerate_single_process():
    """The same engine class on ONE process (no jax.distributed) is the
    sharded engine plus in-program sampling: token parity on a 2x2 mesh,
    coordinator role trivially held, worker entrypoints refused."""
    code = """
        import jax
        import numpy as np
        from repro.configs import reduced_config
        from repro.launch.mesh import make_serve_mesh
        from repro.models import build_model
        from repro.serve import MultiHostServeEngine, Request, ShardedServeEngine

        cfg = reduced_config("stablelm-1.6b")
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        lens = [3, 7, 11, 16, 5, 9]

        def run(cls):
            eng = cls(cfg, params, mesh=make_serve_mesh(2, 2),
                      slots_per_replica=2, max_len=48, buckets=(8, 16))
            rng = np.random.default_rng(0)
            reqs = [Request(uid=i,
                            prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                            max_new=4) for i, L in enumerate(lens)]
            eng.run(reqs)
            return eng, [tuple(r.generated) for r in reqs]

        ref, want = run(ShardedServeEngine)
        eng, got = run(MultiHostServeEngine)
        assert got == want, (got, want)
        assert eng.is_coordinator and eng.n_processes == 1
        assert eng.host_replicas == {0: [0, 1]}
        try:
            eng.serve_worker()
            raise SystemExit("serve_worker must refuse on the coordinator")
        except AssertionError:
            pass
        eng.stop_workers()            # no-op with no workers
        print("OK")
    """
    out = _run(code, [], devices=8)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_process_replicas_single_process_layout():
    """All data rows of a process-local mesh belong to process 0."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()).reshape(n, 1), ("data", "model"))
    assert process_replicas(mesh) == {jax.process_index(): list(range(n))}


@pytest.mark.parametrize("platforms", ["cpu", "tpu", ""],
                         ids=["cpu", "tpu", "unset"])
def test_local_process_topology_is_cpu_only(monkeypatch, platforms):
    """Several JAX processes on one host, and virtual devices for --mesh,
    exist on the CPU backend only: off it the spawners refuse before any
    child starts and XLA_FLAGS is left alone (on a TPU host one process
    holds the chips)."""
    import repro.launch.serve as launcher
    from repro.launch import mesh as lmesh

    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", "")
    lmesh.bootstrap_mesh_env(["--mesh", "2x2"])
    forced = "--xla_force_host_platform_device_count=4" in os.environ["XLA_FLAGS"]
    assert forced == (platforms == "cpu")
    if platforms == "cpu":
        lmesh.refuse_local_processes("--multiproc")
        return
    started = []
    monkeypatch.setattr(launcher.subprocess, "Popen",
                        lambda *a, **k: started.append(a))
    args = launcher.build_args(["--num-processes", "2"])
    with pytest.raises(SystemExit, match="JAX_PLATFORMS=cpu"):
        launcher.spawn_processes(args, [])
    assert started == []


def test_compile_cache_dir_is_env_or_checkout(monkeypatch, tmp_path):
    """The persistent compile cache lives in $JAX_COMPILATION_CACHE_DIR
    when it is set (and nothing is set in code), else at the fixed
    <checkout>/.jax_cache."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ------------------------------------------------- PR-7: ingress front door
# Three phases over one 2-process fleet, each vs a single-process
# ShardedServeEngine reference on the SAME 4x2 logical mesh:
#   a) vision extras ride the command stream (shape-tagged float32
#      bitcast over the int32 exchange) token-exactly,
#   b) worker-side submit_remote() traffic reaches the coordinator via
#      queue counts on the header exchange + CMD_INGRESS pulls, and the
#      worker mirrors the finished tokens without any backhaul,
#   c) the streaming service over the multi-host coordinator: cancel and
#      deadline evict ONLY their own request, peers bit-exact.

_V7_COMMON = """
    import json
    import sys

    def requests(cfg, lens, max_new, seed=0, uids=None):
        rng = np.random.default_rng(seed)
        return [Request(uid=(i if uids is None else uids[i]),
                        prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                        max_new=max_new) for i, L in enumerate(lens)]

    def ingress_prompt(cfg, i):
        rng = np.random.default_rng(100 + i)
        return rng.integers(0, cfg.vocab, 4 + 3 * i).astype(np.int32)

    VIS_LENS, VIS_NEW = [3, 5, 9, 12], 4
    ING_LENS, ING_NEW = [4, 7, 10], 5
    SVC_LENS, SVC_NEW = [3, 9, 12, 5], 12
    KW = dict(max_len=64, buckets=(8, 16, 32))
"""

_V7_REF = _V7_COMMON + """
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model
    from repro.serve import Request, ShardedServeEngine

    mesh = make_serve_mesh(4, 2)
    out = {}

    cfg_v = reduced_config("phi-3-vision-4.2b")
    params_v = build_model(cfg_v).init(jax.random.PRNGKey(0))
    extras = {"patches": (0.01 * np.random.default_rng(7).standard_normal(
        (1, cfg_v.frontend_tokens, cfg_v.d_model))).astype(np.float32)}
    eng = ShardedServeEngine(cfg_v, params_v, mesh=mesh,
                             slots_per_replica=2, **KW)
    reqs = requests(cfg_v, VIS_LENS, VIS_NEW)
    eng.run(reqs, extras=extras)
    out["extras"] = {str(r.uid): list(map(int, r.generated)) for r in reqs}

    cfg = reduced_config("stablelm-1.6b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ShardedServeEngine(cfg, params, mesh=mesh,
                             slots_per_replica=2, **KW)
    reqs = [Request(uid=(1 << 20) | (i + 1), prompt=ingress_prompt(cfg, i),
                    max_new=ING_NEW) for i in range(len(ING_LENS))]
    eng.run(reqs)
    out["ingress"] = {str(r.uid): list(map(int, r.generated)) for r in reqs}

    eng = ShardedServeEngine(cfg, params, mesh=mesh,
                             slots_per_replica=2, **KW)
    reqs = requests(cfg, SVC_LENS, SVC_NEW)
    eng.run(reqs)
    out["svc"] = {str(r.uid): list(map(int, r.generated)) for r in reqs}

    with open(sys.argv[1], "w") as f:
        json.dump(out, f)
    print("REF OK")
"""

_V7_MULTI = _V7_COMMON + """
    proc, port, out_path = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    import time
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=2,
                               process_id=proc)
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model
    from repro.serve import (MultiHostServeEngine, ProtocolError, Request,
                             ServeService)

    mesh = make_serve_mesh(4, 2)
    out = {}

    # ---- phase a: vision extras over the command stream
    cfg_v = reduced_config("phi-3-vision-4.2b")
    params_v = build_model(cfg_v).init(jax.random.PRNGKey(0))
    eng = MultiHostServeEngine(cfg_v, params_v, mesh=mesh,
                               slots_per_replica=2, **KW)
    if proc == 0:
        extras = {"patches": (0.01 * np.random.default_rng(7)
                              .standard_normal((1, cfg_v.frontend_tokens,
                                                cfg_v.d_model))
                              ).astype(np.float32)}
        reqs = requests(cfg_v, VIS_LENS, VIS_NEW)
        eng.run(reqs, extras=extras)
        eng.stop_workers()
        out["extras"] = {str(r.uid): list(map(int, r.generated))
                         for r in reqs}
        try:                       # unknown key: typed, BEFORE any command
            eng._validate_extras(3, {"bogus": np.zeros((1, 2), np.float32)})
            out["bad_extra_typed"] = False
        except ProtocolError:
            out["bad_extra_typed"] = True
    else:
        eng.serve_worker()

    # ---- phase b: worker-side ingress
    cfg = reduced_config("stablelm-1.6b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = MultiHostServeEngine(cfg, params, mesh=mesh,
                               slots_per_replica=2, **KW)
    if proc == 0:
        got = []
        while len(got) < len(ING_LENS):
            got.extend(eng.poll_ingress())
        eng.run(got)
        eng.stop_workers()
        out["ingress"] = {str(r.uid): list(map(int, r.generated))
                          for r in got}
        out["remote_ingress_stat"] = eng.stats["remote_ingress"]
    else:
        uids = [eng.submit_remote(ingress_prompt(cfg, i), max_new=ING_NEW)
                for i in range(len(ING_LENS))]
        eng.serve_worker()
        out["worker_uids"] = uids
        out["worker_mirror"] = {str(u): list(map(int, eng.remote_tokens(u)))
                                for u in uids}
        out["worker_done"] = all(eng.remote_done(u) for u in uids)

    # ---- phase c: streaming service over the multi-host coordinator
    eng = MultiHostServeEngine(cfg, params, mesh=mesh,
                               slots_per_replica=2, **KW)
    if proc == 0:
        eng._clock = lambda: float(eng._round)     # deadlines in rounds
        svc = ServeService(eng, max_pending=8).start()
        prompts = [r.prompt for r in requests(cfg, SVC_LENS, SVC_NEW)]
        streams = [svc.submit(p, max_new=SVC_NEW,
                              deadline_s=(4.0 if i == 2 else None))
                   for i, p in enumerate(prompts)]
        got1 = []
        while len(got1) < 2:                       # cancel uid 1 mid-flight
            got1.extend(streams[1].drain()[0])
            time.sleep(0.005)
        svc.cancel(1, reason="client gone")
        res = {s.uid: s.result(timeout=600) for s in streams}
        svc.stop()
        eng.stop_workers()
        out["svc"] = {str(u): [list(map(int, t)), fin, err]
                      for u, (t, fin, err) in res.items()}
        out["svc_early1"] = list(map(int, got1))
        out["svc_stats"] = {"cancelled": eng.stats["cancelled"],
                            "deadline_expired": eng.stats["deadline_expired"],
                            "free": eng._free_total(), "slots": eng.slots}
    else:
        eng.serve_worker()

    suffix = "" if proc == 0 else ".worker"
    with open(out_path + suffix, "w") as f:
        json.dump(out, f)
    print("PROC", proc, "OK")
"""


def test_multihost_ingress_extras_and_service_eviction():
    with tempfile.TemporaryDirectory() as td:
        ref_path = os.path.join(td, "ref.json")
        ref = _run(_V7_REF, [ref_path], devices=8)
        assert ref.returncode == 0, ref.stderr[-3000:]
        mh_path = os.path.join(td, "mh.json")
        procs, outs = _spawn_fleet(_V7_MULTI, [mh_path], n_procs=2,
                                   devices=4)
        for p, (so, se) in zip(procs, outs):
            assert p.returncode == 0, (so[-2000:], se[-3000:])
        with open(ref_path) as f:
            want = json.load(f)
        with open(mh_path) as f:
            got = json.load(f)
        with open(mh_path + ".worker") as f:
            wrk = json.load(f)

    # a) extras: token-exact across the fleet, bad key typed-refused
    assert got["extras"] == want["extras"]
    assert got["bad_extra_typed"] is True

    # b) ingress: worker submits scheduled by the coordinator match the
    # reference AND the worker's local mirror - uids fleet-namespaced
    assert wrk["worker_uids"] == [(1 << 20) | (i + 1) for i in range(3)]
    assert got["ingress"] == want["ingress"]
    assert wrk["worker_mirror"] == want["ingress"]
    assert wrk["worker_done"] is True
    assert got["remote_ingress_stat"] == 3

    # c) service: cancel (uid 1) + deadline (uid 2) evict alone; peers
    # (0, 3) bit-exact vs the single-process reference run
    svc = got["svc"]
    for uid in ("0", "3"):
        toks, fin, err = svc[uid]
        assert fin == "complete" and toks == want["svc"][uid], uid
    toks1, fin1, err1 = svc["1"]
    all1 = got["svc_early1"] + toks1
    assert fin1 == "cancel" and err1 == "client gone"
    assert all1 == want["svc"]["1"][:len(all1)] and len(all1) < 12
    toks2, fin2, err2 = svc["2"]
    assert fin2 == "deadline" and len(toks2) < 12
    assert toks2 == want["svc"]["2"][:len(toks2)]
    st = got["svc_stats"]
    assert st["cancelled"] == 1 and st["deadline_expired"] == 1
    assert st["free"] == st["slots"]


# ----------------------------------------------- PR-9: fleet telemetry
# The launcher's --trace-out on a 2-process fleet: the coordinator writes
# ONE merged Chrome-trace JSON with a process row per jax process (worker
# launch timings ride the command-header timing slots), and the registry
# carries per-process fleet launch histograms.

_TRACE_FLEET = """
    import json
    import sys

    proc, port = int(sys.argv[1]), sys.argv[2]
    trace_path, metrics_path = sys.argv[3], sys.argv[4]

    import repro.launch.serve as launcher

    # dump the coordinator's registry at exit time, alongside the normal
    # report (the engine is launcher-internal; the wrap is the test's tap)
    _report = launcher.report_telemetry
    def report(eng, args):
        _report(eng, args)
        with open(metrics_path, "w") as f:
            f.write(eng.tel.metrics.render())
    launcher.report_telemetry = report

    launcher.main(["--reduced", "--mesh", "4x2", "--num-processes", "2",
                   "--process-id", str(proc),
                   "--coordinator", f"127.0.0.1:{port}",
                   "--requests", "6", "--max-new", "4", "--prompt-len", "12",
                   "--buckets", "8,16", "--max-len", "64",
                   "--trace-out", trace_path])
    print("PROC", proc, "OK")
"""


def test_multihost_trace_out_merges_both_processes():
    """--trace-out on a 2-process fleet: one Perfetto-loadable trace with
    spans attributed to BOTH pids (worker launches reconstructed from the
    header timing slots), fleet launch histograms labeled by process, and
    the drain printout reporting latency percentiles."""
    with tempfile.TemporaryDirectory() as td:
        trace_path = os.path.join(td, "trace.json")
        metrics_path = os.path.join(td, "metrics.prom")
        procs, outs = _spawn_fleet(_TRACE_FLEET,
                                   [trace_path, metrics_path],
                                   n_procs=2, devices=4)
        for p, (so, se) in zip(procs, outs):
            assert p.returncode == 0, (so[-2000:], se[-3000:])
        with open(trace_path) as f:
            trace = json.load(f)
        with open(metrics_path) as f:
            metrics = f.read()

    # the drain printout: histogram summaries + the trace-write notice
    so0 = outs[0][0]
    assert "ttft: n=6 p50=" in so0 and "p99=" in so0
    assert "per-token: n=" in so0
    assert "queue wait: n=6" in so0
    assert f"spans -> {trace_path}" in so0

    # Chrome-trace schema: X spans from both pids, M rows naming both
    # process tracks, every span numerically timestamped
    evs = trace["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["pid"] for e in spans} == {0, 1}
    for e in spans:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    named = {e["pid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "coordinator" in named[0] and named[1] == "jax process 1"
    names0 = {e["name"] for e in spans if e["pid"] == 0}
    assert {"plan:prefill", "launch:prefill", "plan:decode",
            "launch:decode"} <= names0
    # worker spans: reconstructed launches only, kind-attributed, tagged
    # with the source process
    worker = [e for e in spans if e["pid"] == 1]
    assert worker and all(e["name"].startswith("launch:") for e in worker)
    assert all(e["args"]["process"] == 1 for e in worker)
    assert trace["otherData"]["dropped_spans"] == 0

    # fleet aggregation: the registry carries per-process launch
    # histograms fed from the header timing slots
    assert 'serve_launch_seconds_bucket{kind="decode"' in metrics
    assert ('serve_launch_seconds_count{kind="decode",process="1"}'
            in metrics)
    assert "serve_ttft_seconds_count 6" in metrics
