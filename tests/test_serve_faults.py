"""Fault-tolerant serving: request isolation, watchdogs, drain-and-resume.

The deterministic fault-injection harness (``distributed/fault.FaultPlan``)
drives every scenario from counters - scheduler round, protocol command
seq - never wall-clock, so each replay is exact:

  * a poisoned request (NaN logits, malformed prompt, raising launch)
    fails ALONE; its batch peers' token streams stay bit-exact vs a clean
    run (sampling keys derive from (uid, step), not batch composition);
  * a preempted run snapshots, and a fresh engine resumes it
    token-for-token equal to an uninterrupted run - same for a 2-process
    fleet whose worker is killed mid-decode;
  * a hung worker trips the coordinator's deadline watchdog: typed
    ABORT_DEADLINE exit (87) with the drain snapshot already on disk;
  * a corrupted command header is a typed ``ProtocolError``, not a hang;
  * an injected straggler delay is flagged in ``engine.stats`` within the
    EMA window;
  * the guarded PDQ path routes a poisoned projection to the fp-dequant
    fallback per launch, keeping requests finite.

Subprocess fleets ride the helpers in test_serve_multihost.py (ephemeral
port with EADDRINUSE retry, per-topology compilation-cache subdirs, hard
per-child timeouts).
"""
import collections
import dataclasses
import json
import os
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypo_compat import given, settings, strategies as st

from test_serve_multihost import _run, _spawn_fleet

from repro.configs import reduced_config
from repro.distributed.fault import (EXIT_DEADLINE, EXIT_KILLED,
                                     DeadlineWatchdog, FaultInjector,
                                     FaultPlan, StragglerWatchdog,
                                     load_snapshot, save_snapshot)
from repro.kernels import ops
from repro.models import build_model
from repro.models.linops import quantize_weight
from repro.serve import (CoordinatorAbort, MultiHostServeEngine,
                         ProtocolError, Request, ServeEngine, Telemetry,
                         resume_requests)
from repro.serve.multihost import ABORT_DEADLINE, CMD_ABORT


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced_config("stablelm-1.6b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _reqs(cfg, lens, max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                    max_new=max_new) for i, L in enumerate(lens)]


def _toks(reqs):
    return {r.uid: tuple(r.generated) for r in reqs}


# ---------------------------------------------------------------------------
# Request isolation (single-process engine)
# ---------------------------------------------------------------------------


def test_nan_poisoned_prefill_fails_alone(small_model):
    """A request whose prefill logits carry NaN is failed + evicted; its
    batch peers (same prefill launch) are token-for-token unaffected."""
    cfg, m, params = small_model
    kw = dict(slots=4, max_len=64, temperature=0.7, rng=jax.random.PRNGKey(7))
    ref = _reqs(cfg, [4, 6, 5, 7])
    ServeEngine(cfg, params, **kw).run(ref)

    eng = ServeEngine(cfg, params, **kw,
                      fault=FaultPlan(nan_uid=1, nan_kind="prefill").injector())
    got = _reqs(cfg, [4, 6, 5, 7])
    eng.run(got)

    assert got[1].done and got[1].error == "non-finite logits at prefill"
    assert got[1].generated == []
    assert eng.stats["failed"] == 1
    assert eng.failures.count("nonfinite") == 1
    for uid in (0, 2, 3):                      # peers of the poisoned launch
        assert got[uid].error is None
        assert _toks(got)[uid] == _toks(ref)[uid]
    # the engine keeps serving after the eviction: freed slot is reusable
    late = _reqs(cfg, [5], max_new=3, seed=9)[0]
    late.uid = 99
    eng.run([late])
    assert late.done and late.error is None and len(late.generated) == 3


def test_nan_poisoned_decode_evicts_mid_stream(small_model):
    """NaN appearing at decode evicts that slot only; peers sharing the
    decode batch keep their exact streams."""
    cfg, m, params = small_model
    kw = dict(slots=4, max_len=64, temperature=0.7, rng=jax.random.PRNGKey(7))
    ref = _reqs(cfg, [4, 6, 5], max_new=8)
    ServeEngine(cfg, params, **kw).run(ref)

    eng = ServeEngine(cfg, params, **kw,
                      fault=FaultPlan(nan_uid=2, nan_kind="decode").injector())
    got = _reqs(cfg, [4, 6, 5], max_new=8)
    eng.run(got)

    assert got[2].done and got[2].error == "non-finite logits at decode"
    assert len(got[2].generated) == 1          # prefill token landed, then cut
    assert _toks(got)[2] == _toks(ref)[2][:1]  # ... and it matches the ref
    assert eng.failures.count("nonfinite") == 1
    for uid in (0, 1):
        assert got[uid].error is None
        assert _toks(got)[uid] == _toks(ref)[uid]


def test_malformed_prompt_fails_alone(small_model):
    """A structurally bad prompt fails at dequeue (kind='plan'); it never
    reaches a device launch and its co-submitted peers are unaffected."""
    cfg, m, params = small_model
    kw = dict(slots=4, max_len=64, temperature=0.7, rng=jax.random.PRNGKey(7))
    ref = _reqs(cfg, [4, 6, 5])
    ServeEngine(cfg, params, **kw).run(ref)

    eng = ServeEngine(cfg, params, **kw)
    got = _reqs(cfg, [4, 6, 5])
    bad = Request(uid=9, prompt=np.linspace(0.0, 1.0, 5), max_new=4)  # floats
    eng.run(got[:1] + [bad] + got[1:])

    assert bad.done and "malformed prompt" in bad.error
    assert bad.generated == []
    assert eng.failures.count("plan") == 1 and eng.stats["failed"] == 1
    assert _toks(got) == _toks(ref)


def test_raising_launch_fails_only_its_requests(small_model):
    """An exception inside one device launch fails that launch's requests
    and releases their slots; the engine keeps serving and later launches
    (including the SAME uids' peers) are exact."""
    cfg, m, params = small_model
    ref = _reqs(cfg, [4, 5, 6, 7], max_new=4)
    ServeEngine(cfg, params, slots=2, max_len=64).run(ref)

    plan = FaultPlan(raise_kind="prefill", raise_round=0)   # one-shot
    eng = ServeEngine(cfg, params, slots=2, max_len=64, fault=plan.injector())
    got = _reqs(cfg, [4, 5, 6, 7], max_new=4)
    eng.run(got)

    failed = [r for r in got if r.error]
    ok = [r for r in got if not r.error]
    assert len(failed) == 2                    # first admission group (2 slots)
    assert all("prefill launch failed" in r.error for r in failed)
    assert all("injected prefill launch fault" in r.error for r in failed)
    assert eng.stats["failed"] == 2 and eng.failures.count("exec") == 2
    assert len(ok) == 2
    for r in ok:
        assert _toks(got)[r.uid] == _toks(ref)[r.uid]


@pytest.mark.parametrize("raise_kind", [None, "prefill"],
                         ids=["clean", "failed_launch"])
def test_canned_trace_exit_code_reports_failed_launches(small_model,
                                                        monkeypatch,
                                                        raise_kind):
    """Request isolation keeps ``launch/serve.py`` serving past a failed
    launch, but its canned-trace run then exits non-zero naming the
    failure (a Mosaic compile error is such a launch failure); a clean run
    returns normally."""
    import repro.launch.serve as launcher
    import repro.serve as serve_pkg
    from repro.launch import compile_cache

    cfg, m, params = small_model
    build = serve_pkg.build_engine

    def build_with_fault(sc, **kw):
        eng = build(sc, cfg=cfg, params=params)
        if raise_kind:
            eng.fault = FaultPlan(raise_kind=raise_kind).injector()
            eng.fault.bind(eng)
        return eng

    monkeypatch.setattr(serve_pkg, "build_engine", build_with_fault)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(launcher.signal, "signal", lambda *a: None)
    argv = ["--reduced", "--requests", "3", "--slots", "2", "--max-new", "2",
            "--prompt-len", "6", "--buckets", "8", "--max-len", "32"]
    if raise_kind is None:
        launcher.main(argv)
        return
    with pytest.raises(SystemExit) as exc:
        launcher.main(argv)
    assert "requests failed in a launch" in str(exc.value.code)
    assert "injected prefill launch fault" in str(exc.value.code)


# ---------------------------------------------------------------------------
# Guarded PDQ -> fp-dequant fallback
# ---------------------------------------------------------------------------


def test_pdq_guard_passes_finite_results_through():
    """With the guard armed but the fast path healthy, pdq_dense output is
    bit-identical to the unguarded kernel."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256), jnp.float32)
    rec = quantize_weight(
        jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32))
    fast = np.asarray(ops.pdq_dense(x, rec))
    with ops.pdq_guard():
        guarded = np.asarray(ops.pdq_dense(x, rec))
    np.testing.assert_array_equal(guarded, fast)


def test_pdq_fault_routes_to_fp_dequant_fallback():
    """A poisoned fast path makes the guard select the fp-dequant branch:
    the result equals the plain ``x @ (q * scale)`` reference exactly."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256), jnp.float32)
    rec = quantize_weight(
        jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32))
    with ops.pdq_guard(), ops.pdq_fault():
        forced = np.asarray(ops.pdq_dense(x, rec))
    want = np.asarray(
        ops._fp_dequant_matmul(x, rec["q"], rec["scale"], jnp.float32))
    np.testing.assert_array_equal(forced, want)
    assert np.isfinite(forced).all()


def test_engine_pdq_fallback_survives_poisoned_kernels(small_model):
    """End-to-end: with every guarded projection's fast path poisoned, a
    pdq_fallback int8 engine still completes every request with finite
    logits (zero nonfinite evictions)."""
    cfg, m, params = small_model
    eng = ServeEngine(cfg, params, slots=2, max_len=64,
                      quantize_weights=True, pdq_fallback=True)
    reqs = _reqs(cfg, [4, 6], max_new=4)
    with ops.pdq_fault():             # trace-time: jits trace on first run
        eng.run(reqs)
    assert all(r.done and r.error is None for r in reqs)
    assert all(len(r.generated) == 4 for r in reqs)
    assert eng.stats["failed"] == 0


# ---------------------------------------------------------------------------
# Deadline watchdog + snapshot primitives
# ---------------------------------------------------------------------------


def test_deadline_watchdog_fires_cancels_and_disarms():
    fired = []
    with DeadlineWatchdog(0.05, reason="unit",
                          on_timeout=lambda r, s: fired.append((r, s))) as wd:
        time.sleep(0.4)
    assert wd.fired and fired == [("unit", 0.05)]

    with DeadlineWatchdog(5.0, on_timeout=lambda r, s: fired.append("no")) as wd:
        pass                                   # exits before expiry: cancelled
    time.sleep(0.1)
    assert not wd.fired and fired == [("unit", 0.05)]

    with DeadlineWatchdog(None, on_timeout=lambda r, s: fired.append("no")) as wd:
        assert wd._timer is None               # disarmed entirely
    assert not wd.fired


def test_snapshot_roundtrip_and_resume_clears_progress(tmp_path):
    snap = {
        "version": 1, "round": 5,
        "inflight": [{"uid": 2, "prompt": np.arange(4, dtype=np.int32),
                      "max_new": 8, "generated": [7, 9], "error": None}],
        "pending": [{"uid": 3, "prompt": np.arange(6, dtype=np.int32),
                     "max_new": 8, "generated": [], "error": None}],
        "finished": [{"uid": 1, "prompt": np.arange(3, dtype=np.int32),
                      "max_new": 2, "generated": [4, 4], "error": None}],
        "stats": {"completed": 1}, "failures": [],
    }
    path = os.path.join(tmp_path, "snap.npy")
    save_snapshot(path, snap)
    got = load_snapshot(path)
    assert got["version"] == 1 and got["round"] == 5
    np.testing.assert_array_equal(got["inflight"][0]["prompt"], np.arange(4))

    finished, todo = resume_requests(got)
    assert [r.uid for r in finished] == [1] and finished[0].done
    assert [r.uid for r in todo] == [2, 3]     # inflight first, then pending
    assert all(r.generated == [] and not r.done for r in todo)


def test_fault_plan_ships_over_json():
    """FaultPlan is the subprocess fixture format: asdict -> json -> init
    reproduces the plan (delay_rounds keys re-intified by the unpacker)."""
    plan = FaultPlan(nan_uid=3, kill_process=1, kill_at_seq=6,
                     delay_rounds={4: 5.0}, corrupt_header_at_seq=2)
    d = json.loads(json.dumps(dataclasses.asdict(plan)))
    d["delay_rounds"] = {int(k): v for k, v in d["delay_rounds"].items()}
    plan2 = FaultPlan(**d)
    assert plan2 == plan
    inj = plan2.injector()
    assert inj.exec_delay("decode", 4) == 5.0
    assert inj.exec_delay("decode", 3) == 0.0


# ---------------------------------------------------------------------------
# Heartbeat / typed protocol faults (no jax.distributed needed)
# ---------------------------------------------------------------------------


def _bare_mh(n_processes=2, process_id=0):
    eng = object.__new__(MultiHostServeEngine)
    eng.n_processes = n_processes
    eng.process_id = process_id
    eng.is_coordinator = process_id == 0
    # acks + per-process ingress counts + per-process launch-timing slots
    eng._hdr = 4 + 3 * n_processes
    eng._seq = 1
    eng._done_seq = 0
    eng._last_exec_us = 0
    eng._prev_kind = None
    eng.tel = Telemetry(enabled=False)
    eng._stopped = False
    eng._ingress_lock = threading.Lock()
    eng._out_q = collections.deque()
    eng._ingress_counts = [0] * n_processes
    eng._remote = {}
    eng._remote_seq = 1
    eng.fault = FaultInjector()
    return eng


def test_heartbeat_ack_mismatch_is_typed_desync():
    """The coordinator verifies every worker acked seq-1 on the command
    header exchange; a stale ack raises ProtocolError, a fresh one
    advances the stream."""
    eng = _bare_mh()

    def exchange(arrays, all_ranks=False):
        hdr = np.array(arrays[0], np.int32)
        hdr[4 + 1] = eng._seq - 1              # worker 1: correct heartbeat
        return [hdr]

    eng._broadcast = exchange
    eng._cmd(5)                                # seq 1 -> ok
    eng._cmd(5)                                # seq 2 -> ok
    assert eng._seq == 3

    def stale(arrays, all_ranks=False):
        hdr = np.array(arrays[0], np.int32)
        hdr[4 + 1] = 0                         # worker 1 stuck at seq 0
        return [hdr]

    eng._broadcast = stale
    with pytest.raises(ProtocolError, match="desynchronized"):
        eng._cmd(5)


def test_worker_refuses_to_drive_and_decodes_typed_abort():
    worker = _bare_mh(process_id=1)
    with pytest.raises(RuntimeError, match="worker"):
        worker._cmd(5)

    def abort(arrays, all_ranks=False):
        hdr = np.zeros_like(np.asarray(arrays[0], np.int32))
        hdr[0], hdr[1], hdr[2] = CMD_ABORT, ABORT_DEADLINE, 7
        return [hdr]

    worker._broadcast = abort
    with pytest.raises(CoordinatorAbort, match="deadline exceeded") as ei:
        worker._recv_cmd()
    assert ei.value.reason == ABORT_DEADLINE


# ---------------------------------------------------------------------------
# Straggler watchdog
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(warmup=st.integers(min_value=5, max_value=30),
       magnitude=st.floats(min_value=4.0, max_value=50.0),
       base=st.floats(min_value=1e-3, max_value=0.1))
def test_straggler_watchdog_flags_spikes_not_steady_state(warmup, magnitude,
                                                         base):
    """Any spike past factor x EMA after any warmup is flagged on THAT
    observation; a steady stream never flags."""
    wd = StragglerWatchdog()
    for _ in range(warmup):
        assert not wd.observe(base)
    assert wd.observe(base * magnitude)        # magnitude > factor (3.0)
    assert wd.flagged == 1

    steady = StragglerWatchdog()
    for _ in range(warmup + 1):
        steady.observe(base)
    assert steady.flagged == 0


def test_straggler_flag_surfaces_in_engine_stats(small_model):
    """An injected virtual decode delay (never actually slept) is flagged
    by the serving loop within the run and lands in stats + failure log."""
    cfg, m, params = small_model
    plan = FaultPlan(delay_rounds={6: 300.0})
    eng = ServeEngine(cfg, params, slots=2, max_len=64, fault=plan.injector())
    req = _reqs(cfg, [6], max_new=12)[0]
    eng.run([req])
    assert req.done and req.error is None
    assert eng.stats["straggler_flags"] >= 1
    assert eng.failures.count("straggler") >= 1
    detail = [e for e in eng.failures.events if e["kind"] == "straggler"]
    assert "EMA" in detail[0]["detail"]


def test_prefill_straggler_has_own_ema_and_event_kind(small_model):
    """Prefill launches feed their OWN watchdog: an injected virtual delay
    scoped to ``delay_kind='prefill'`` flags the prefill EMA (distinct
    'straggler_prefill' event kind) and never touches the decode EMA's
    flag count - the two streams have very different baselines, so one
    shared EMA would either mask prefill stragglers or false-flag every
    prefill after a decode-heavy stretch."""
    cfg, m, params = small_model
    # rounds 1-4 serve request 0's undelayed prefill + decode (warming the
    # prefill EMA); every LATER prefill is virtually 300s slow
    plan = FaultPlan(delay_rounds={r: 300.0 for r in range(5, 60)},
                     delay_kind="prefill")
    eng = ServeEngine(cfg, params, slots=1, max_len=64,
                      fault=plan.injector())
    assert eng.prefill_straggler is not eng.straggler   # independent EMAs
    reqs = _reqs(cfg, [5, 6, 4], max_new=4)
    eng.run(reqs)
    assert all(r.done and r.error is None for r in reqs)
    assert eng.stats["prefill_straggler_flags"] >= 1
    assert eng.failures.count("straggler_prefill") >= 1
    ev = [e for e in eng.failures.events if e["kind"] == "straggler_prefill"]
    assert "prefill launch" in ev[0]["detail"] and "EMA" in ev[0]["detail"]
    # the decode watchdog saw only real (undelayed) decode timings
    decode_flagged = [e for e in eng.failures.events
                      if e["kind"] == "straggler"]
    assert not any("300" in e["detail"].split("s >")[0]
                   for e in decode_flagged)


def test_chunked_launches_feed_the_prefill_straggler(small_model):
    """Chunked prefill launches ride the same prefill watchdog (they are
    the prefill path, just split), with the 'chunked' kind named in the
    flag detail."""
    cfg, m, params = small_model
    plan = FaultPlan(delay_rounds={r: 300.0 for r in range(3, 60)},
                     delay_kind="chunked")
    eng = ServeEngine(cfg, params, slots=2, max_len=64, buckets=(8, 16),
                      chunked_prefill=True, fault=plan.injector())
    reqs = _reqs(cfg, [20, 24, 18], max_new=4)
    eng.run(reqs)
    assert all(r.done and r.error is None for r in reqs)
    assert eng.stats["chunked_requests"] == 3
    assert eng.stats["prefill_straggler_flags"] >= 1
    ev = [e for e in eng.failures.events if e["kind"] == "straggler_prefill"]
    assert ev and "chunked launch" in ev[0]["detail"]


# ---------------------------------------------------------------------------
# Drain -> snapshot -> resume (single process)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_preempt_snapshot_resume_token_parity(small_model, tmp_path,
                                              temperature):
    """Preempt mid-serve, snapshot, resume on a FRESH engine: finished +
    regenerated streams are token-for-token the uninterrupted run, greedy
    and sampled (keys derive from (uid, step), not engine history)."""
    cfg, m, params = small_model
    kw = dict(slots=2, max_len=64, temperature=temperature,
              rng=jax.random.PRNGKey(3))
    lens = [4, 6, 9, 5, 7]
    ref = _reqs(cfg, lens, max_new=8)
    ServeEngine(cfg, params, **kw).run(ref)

    plan = FaultPlan(preempt_at_round=3)
    eng = ServeEngine(cfg, params, **kw, fault=plan.injector())
    eng.snapshot_path = os.path.join(tmp_path, f"snap{temperature}.npy")
    eng.run(_reqs(cfg, lens, max_new=8))
    assert eng.drained and os.path.exists(eng.snapshot_path)

    finished, todo = resume_requests(load_snapshot(eng.snapshot_path))
    assert todo                                # the preemption left real work
    eng2 = ServeEngine(cfg, params, **kw)      # fresh engine, no shared state
    eng2.run(todo)

    out = finished + todo
    assert {r.uid for r in out} == set(range(len(lens)))
    assert all(r.done and r.error is None for r in out)
    assert _toks(out) == _toks(ref)


# ---------------------------------------------------------------------------
# Multi-process fleets under injected faults (subprocess suite)
# ---------------------------------------------------------------------------
#
# 2 OS processes x 1 virtual CPU device each over a ('data','model') = 2x1
# mesh, temperature sampling, 20s launch deadlines.  The reference is the
# single-process ShardedServeEngine on the same logical mesh (the pinned
# multihost==sharded parity contract).

_FLEET = """
    import json
    import os
    import sys

    proc, port = int(sys.argv[1]), sys.argv[2]
    mode, out_path, snap_path = sys.argv[3], sys.argv[4], sys.argv[5]

    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=2,
                               process_id=proc)
    import numpy as np
    from repro.configs import reduced_config
    from repro.distributed.fault import FaultPlan, load_snapshot
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model
    from repro.serve import MultiHostServeEngine, Request, resume_requests

    assert jax.process_count() == 2
    cfg = reduced_config("stablelm-1.6b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    LENS = [3, 5, 8, 6, 4]

    def fresh_requests():
        rng = np.random.default_rng(0)
        return [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                        max_new=8) for i, L in enumerate(LENS)]

    plan = {"kill": FaultPlan(kill_process=1, kill_at_seq=6),
            "hang": FaultPlan(hang_process=1, hang_at_seq=5,
                              hang_seconds=600.0),
            "corrupt": FaultPlan(corrupt_header_at_seq=4),
            "resume": None}[mode]
    eng = MultiHostServeEngine(
        cfg, params, mesh=make_serve_mesh(2, 1), slots_per_replica=2,
        max_len=48, buckets=(8, 16), temperature=0.5,
        fault=None if plan is None else plan.injector(),
        launch_timeout=20.0,
        snapshot_path=snap_path if proc == 0 and snap_path != "-" else None)
    if proc == 0:
        if mode == "resume":
            finished, todo = resume_requests(load_snapshot(snap_path))
            eng.run(todo)
            eng.stop_workers()
            done = finished + todo
        else:
            done = fresh_requests()
            eng.run(done)
            eng.stop_workers()
        with open(out_path, "w") as f:
            json.dump({str(r.uid): [list(map(int, r.generated)), r.error]
                       for r in done}, f)
    else:
        eng.serve_worker()
    print("PROC", proc, "OK")
"""

_FLEET_REF = """
    import json
    import sys

    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model
    from repro.serve import Request, ShardedServeEngine

    cfg = reduced_config("stablelm-1.6b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                    max_new=8) for i, L in enumerate([3, 5, 8, 6, 4])]
    eng = ShardedServeEngine(cfg, params, mesh=make_serve_mesh(2, 1),
                             slots_per_replica=2, max_len=48,
                             buckets=(8, 16), temperature=0.5)
    eng.run(reqs)
    assert all(r.done and r.error is None for r in reqs)
    with open(sys.argv[1], "w") as f:
        json.dump({str(r.uid): [list(map(int, r.generated)), r.error]
                   for r in reqs}, f)
    print("REF OK")
"""


def test_killed_worker_drains_and_fresh_fleet_resumes_token_exact():
    """Kill worker 1 mid-decode (injected os._exit at command seq 6): the
    coordinator dies typed-nonzero but persists the drain snapshot; a
    FRESH 2-process fleet resumes it and finished+resumed streams equal
    the uninterrupted single-process reference token-for-token."""
    with tempfile.TemporaryDirectory() as td:
        ref_path = os.path.join(td, "ref.json")
        ref = _run(_FLEET_REF, [ref_path], devices=2)
        assert ref.returncode == 0, ref.stderr[-3000:]

        snap = os.path.join(td, "snap.npy")
        procs, outs = _spawn_fleet(
            _FLEET, ["kill", os.path.join(td, "k.json"), snap],
            n_procs=2, devices=1)
        coord, worker = procs
        assert worker.returncode == EXIT_KILLED, outs[1][1][-2000:]
        assert "FAULT-INJECTION: killing process 1" in outs[1][1]
        # the coordinator loses the fleet either as a raised gloo error
        # (run()'s except path) or as a deadline abort - both nonzero,
        # both leave the snapshot behind
        assert coord.returncode not in (0, None), outs[0][1][-2000:]
        assert os.path.exists(snap), outs[0][1][-2000:]

        out_path = os.path.join(td, "resumed.json")
        procs, outs = _spawn_fleet(_FLEET, ["resume", out_path, snap],
                                   n_procs=2, devices=1)
        for p, (so, se) in zip(procs, outs):
            assert p.returncode == 0, (so[-1500:], se[-3000:])
        with open(out_path) as f:
            got = json.load(f)
        with open(ref_path) as f:
            want = json.load(f)
        assert got == want, {u: (got.get(u), want.get(u)) for u in want
                             if got.get(u) != want.get(u)}


def test_hung_worker_trips_deadline_watchdog():
    """Worker 1 sleeps inside the seq-5 header rendezvous: the
    coordinator's 20s deadline watchdog fires - typed ABORT_DEADLINE line,
    exit code 87, snapshot dumped from the side thread."""
    with tempfile.TemporaryDirectory() as td:
        snap = os.path.join(td, "snap.npy")
        procs, outs = _spawn_fleet(
            _FLEET, ["hang", os.path.join(td, "h.json"), snap],
            n_procs=2, devices=1, timeout=300, hang_ok=(1,))
        coord = procs[0]
        assert coord.returncode == EXIT_DEADLINE, outs[0][1][-3000:]
        assert "FATAL ABORT_DEADLINE" in outs[0][1]
        assert "FAULT-INJECTION: hanging process 1" in outs[1][1]
        assert os.path.exists(snap)


def test_corrupt_header_is_typed_protocol_error():
    """A corrupted command header (opcode 99 at seq 4) kills the worker
    with the typed ProtocolError message instead of a silent hang."""
    with tempfile.TemporaryDirectory() as td:
        procs, outs = _spawn_fleet(
            _FLEET, ["corrupt", os.path.join(td, "c.json"), "-"],
            n_procs=2, devices=1, timeout=300)
        coord, worker = procs
        assert worker.returncode not in (0, None), outs[1][1][-2000:]
        assert "unknown multi-host serve opcode 99" in outs[1][1]
        assert coord.returncode not in (0, None), outs[0][1][-2000:]


def test_extras_protocol_validation_is_typed():
    """Unsupported extras are typed ProtocolErrors raised at the entry
    point, BEFORE any command is issued (raising mid-admission would
    desync the fleet or leak a planned slot)."""
    eng = _bare_mh()
    eng.chunked_prefill = False
    eng.buckets = (8, 16)
    ok = {"patches": np.zeros((1, 4, 8), np.float32)}
    eng._validate_extras(5, ok)               # known key, float, 1..4 dims
    with pytest.raises(ProtocolError, match="not part of the multi-host"):
        eng._validate_extras(5, {"bogus": np.zeros((1, 2), np.float32)})
    with pytest.raises(ProtocolError, match="not a float type"):
        eng._validate_extras(5, {"frames": np.zeros((1, 2), np.int32)})
    with pytest.raises(ProtocolError, match="shape-tag"):
        eng._validate_extras(
            5, {"frames": np.zeros((1, 2, 3, 4, 5), np.float32)})
    eng.chunked_prefill = True                # oversized + extras: refused
    with pytest.raises(ProtocolError, match="chunked-prefill"):
        eng._validate_extras(40, ok)
    eng._validate_extras(5, ok)               # in-bucket prompt still fine


def test_worker_ingress_counts_ride_the_header_exchange():
    """submit_remote() queues locally under a fleet-unique namespaced uid;
    the queue LENGTH piggybacks on the very next header exchange (slot
    4+N+pid), and the coordinator harvests it from any command."""
    worker = _bare_mh(process_id=1)
    u1 = worker.submit_remote(np.array([3, 1], np.int32), max_new=4)
    u2 = worker.submit_remote(np.array([2], np.int32), max_new=2,
                              deadline_ms=50)
    assert (u1, u2) == ((1 << 20) | 1, (1 << 20) | 2)
    shipped = {}

    def exchange(arrays, all_ranks=False, src=0):
        hdr = np.array(arrays[0], np.int32)
        shipped["hdr"] = hdr.copy()
        hdr[0] = 8                            # coordinator sent CMD_POLL
        return [hdr]

    worker._broadcast = exchange
    op, arg, seq, n_ex = worker._recv_cmd()
    assert op == 8 and n_ex == 0
    assert shipped["hdr"][4 + 2 + 1] == 2     # 2 queued submits announced

    coord = _bare_mh()

    def cexchange(arrays, all_ranks=False, src=0):
        hdr = np.array(arrays[0], np.int32)
        hdr[4 + 1] = coord._seq - 1           # worker heartbeat in order
        hdr[4 + 2 + 1] = 2                    # ... announcing 2 queued
        return [hdr]

    coord._broadcast = cexchange
    coord._cmd(8)
    assert coord._ingress_counts == [0, 2]
