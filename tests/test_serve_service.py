"""Streaming serving front door: continuous admission, overload
backpressure, per-request deadlines & cancellation (PR-7 contract).

Pins:
  * continuously-admitted streams are token-for-token identical to batch
    ``engine.run()`` (per-(uid, step) sampling keys make the two paths the
    same computation),
  * the admission queue is bounded: past the watermark submits shed with a
    typed ``OverloadedError``/HTTP 429 + Retry-After, counted in stats,
    and an overload soak is DETERMINISTIC round-for-round,
  * cancel (client, disconnect, slow consumer) and deadline expiry evict
    ONLY their own request - batch peers stay bit-exact and the slot is
    reclaimed within a round,
  * the raw-asyncio HTTP layer maps every failure mode to a typed status
    (400/404/429/503) and SSE streams carry the engine's exact tokens,
  * SIGTERM during live HTTP traffic drains, snapshots, and ``--resume``
    regenerates the interrupted request token-exactly.
"""
import asyncio
import contextlib
import gc
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.distributed.fault import FaultInjector, FaultPlan
from repro.models import build_model
from repro.serve import (EngineDraining, HttpFrontend, OverloadedError,
                         Request, ServeConfig, ServeService, build_engine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced_config("stablelm-1.6b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _engine(cfg, params, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("buckets", (8, 16, 32))
    # the supported construction surface (PR 8): every engine through
    # ServeConfig + build_engine
    return build_engine(ServeConfig(**kw), cfg=cfg, params=params)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, L).astype(np.int32) for L in lens]


def _batch_ref(cfg, params, reqs, **kw):
    """Run copies of ``reqs`` through a fresh engine's batch path; return
    {uid: tokens}.  Sampling keys are (uid, step)-derived, so this is THE
    reference the streamed tokens must equal bit-for-bit."""
    eng = _engine(cfg, params, **kw)
    copies = [Request(uid=r.uid, prompt=np.asarray(r.prompt),
                      max_new=r.max_new) for r in reqs]
    eng.run(copies)
    assert all(r.done and r.error is None for r in copies)
    return {r.uid: tuple(r.generated) for r in copies}


def _wait(pred, timeout=300.0, every=0.01):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise TimeoutError("condition not reached")
        time.sleep(every)


class _SlowDecode(FaultInjector):
    """Really sleep before each decode launch (FaultPlan.delay_rounds is
    VIRTUAL - watchdog-only) so a cancel racing a fast tiny-model decode
    reliably lands while the request is still in flight."""

    def __init__(self, seconds: float = 0.03):
        self.seconds = seconds

    def on_exec(self, kind: str, rnd: int) -> None:
        if kind == "decode":
            time.sleep(self.seconds)


# ---------------------------------------------------------------------------
# continuous admission: streamed == batch
# ---------------------------------------------------------------------------


def test_streamed_tokens_match_batch_run(small_model):
    """Requests submitted continuously (staggered, mid-flight) through the
    service produce the same tokens as one batch run() - and the streams
    deliver them incrementally, first token before the request finishes."""
    cfg, m, params = small_model
    lens = [3, 9, 12, 5, 17, 7]
    prompts = _prompts(cfg, lens)
    reqs = [Request(uid=i, prompt=p, max_new=6) for i, p in
            enumerate(prompts)]
    want = _batch_ref(cfg, params, reqs)

    eng = _engine(cfg, params)
    svc = ServeService(eng, max_pending=16).start()
    streams = []
    for i, p in enumerate(prompts):
        streams.append(svc.submit(p, max_new=6))
        if i == 2:      # stagger: later submits land mid-decode
            _wait(lambda: eng.stats["decode_steps"] > 0)
    got = {s.uid: s.result(timeout=300) for s in streams}
    assert {u: tuple(t) for u, (t, _, _) in got.items()} == want
    assert all(fin == "complete" and err is None
               for _, fin, err in got.values())
    st = svc.stats()
    assert st["completed"] == len(lens) and st["shed"] == 0
    assert st["pending"] == 0 and st["free_slots"] == st["slots"]
    svc.stop()
    assert not svc._streams           # stream table drained, nothing leaked


def test_heap_frozen_after_traces_and_released_at_stop(small_model):
    """A round that traced programs collects and freezes the heap, so a
    full collection while serving walks only what serving allocated; a
    round that traced nothing settles nothing, and stopping the service
    hands the heap back to the collector."""
    cfg, m, params = small_model
    eng = _engine(cfg, params)
    svc = ServeService(eng, max_pending=16)
    settled = []
    settle = svc._settle_heap
    svc._settle_heap = lambda: (settled.append(gc.get_freeze_count()),
                                settle())
    svc.start()
    prompt = _prompts(cfg, [5])[0]
    svc.submit(prompt, max_new=6).result(timeout=300)
    assert eng.stats["prefill_compiles"] and eng.stats["decode_compiles"]
    assert settled and gc.get_freeze_count() > 0
    n = len(settled)
    traced = (eng.stats["prefill_compiles"], eng.stats["decode_compiles"])
    svc.submit(prompt, max_new=6).result(timeout=300)
    assert (eng.stats["prefill_compiles"],
            eng.stats["decode_compiles"]) == traced
    assert len(settled) == n
    svc.stop()
    assert gc.get_freeze_count() == 0


# ---------------------------------------------------------------------------
# overload: bounded queue, deterministic shed, accepted work exact
# ---------------------------------------------------------------------------


def _soak(cfg, params, rounds=60, per_round=6):
    """Deterministic 3x-capacity open-loop soak via burst injection:
    ``per_round`` submits hit a 2-slot engine with a 4-deep admission
    queue at the top of every scheduler round."""
    burst = {r: [[3 + (r + i) % 6, 4] for i in range(per_round)]
             for r in range(rounds)}
    plan = FaultPlan(burst_rounds=dict(burst))
    eng = _engine(cfg, params, slots=2, buckets=(8,),
                  fault=plan.injector())
    svc = ServeService(eng, max_pending=4).start()
    # every offered request terminal (monotonic counters: no transient
    # window mid queue-to-slot handoff, unlike polling pending/active)
    _wait(lambda: eng.stats["shed"] + eng.stats["completed"]
          == rounds * per_round, timeout=600)
    svc.stop()
    accepted = list(eng.finished)
    stats = dict(eng.stats)
    return eng, accepted, stats


def test_overload_soak_sheds_deterministically_no_leak(small_model):
    cfg, m, params = small_model
    eng, accepted, stats = _soak(cfg, params)
    # sustained 3x overload: the bounded queue shed most of the offered
    # load, every shed is counted, and what WAS accepted all completed
    assert eng._round >= 50
    assert stats["shed"] > 0
    assert stats["completed"] == len(accepted) > 0
    assert stats["shed"] + stats["completed"] == 60 * 6
    assert all(r.done and r.finish_reason == "complete" for r in accepted)
    # no slot/queue leak after the storm
    assert eng._free_total() == eng.slots
    assert not eng.pending and all(r is None for r in eng.active)
    # accepted streams are token-for-token the batch-run tokens
    want = _batch_ref(cfg, params, accepted, slots=2, buckets=(8,))
    assert {r.uid: tuple(r.generated) for r in accepted} == want
    # the soak is deterministic: same plan, same rounds -> same shed
    # pattern and same accepted set, replayed end to end
    eng2, accepted2, stats2 = _soak(cfg, params)
    assert stats2["shed"] == stats["shed"]
    assert stats2["completed"] == stats["completed"]
    assert ([(r.uid, tuple(r.generated)) for r in accepted2]
            == [(r.uid, tuple(r.generated)) for r in accepted])


def test_overloaded_error_is_typed_and_counted(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params, slots=2, buckets=(8,))
    svc = ServeService(eng, max_pending=2, retry_after=1.5)
    # not started: submits queue in ingress, so the watermark is exact
    p = _prompts(cfg, [4])[0]
    svc.submit(p, max_new=4)
    svc.submit(p, max_new=4)
    with pytest.raises(OverloadedError) as ei:
        svc.submit(p, max_new=4)
    assert ei.value.retry_after == 1.5
    assert eng.stats["shed"] == 1
    svc.start()
    svc.stop()


# ---------------------------------------------------------------------------
# cancellation: only the cancelled request is evicted
# ---------------------------------------------------------------------------


def test_cancel_midflight_evicts_only_own_request(small_model):
    cfg, m, params = small_model
    prompts = _prompts(cfg, [5, 9, 12, 7])
    reqs = [Request(uid=i, prompt=p, max_new=12) for i, p in
            enumerate(prompts)]
    want = _batch_ref(cfg, params, reqs)

    eng = _engine(cfg, params, fault=_SlowDecode())
    svc = ServeService(eng, max_pending=16).start()
    streams = [svc.submit(p, max_new=12) for p in prompts]
    victim = streams[1]
    got_early: list[int] = []

    def two_tokens_flowed():
        got_early.extend(victim.drain()[0])
        return len(got_early) >= 2

    _wait(two_tokens_flowed)
    svc.cancel(victim.uid, reason="user hit stop")
    results = {s.uid: s.result(timeout=300) for s in streams}
    svc.stop()

    toks, fin, err = results[victim.uid]
    assert fin == "cancel" and err == "user hit stop"
    early_and_late = tuple(got_early) + tuple(toks)
    assert early_and_late == want[victim.uid][:len(early_and_late)]
    assert len(early_and_late) < 12           # actually cut short
    # peers: bit-exact, untouched by the eviction
    for uid in (0, 2, 3):
        toks, fin, _ = results[uid]
        assert fin == "complete" and tuple(toks) == want[uid]
    assert eng.stats["cancelled"] == 1
    assert eng._free_total() == eng.slots     # slot reclaimed


def test_cancel_mid_chunked_prefill_reclaims_slot_same_round(small_model):
    """A cancel landing while the chunked-prefill launch sequence is IN
    FLIGHT (planned, not yet applied) is honoured at apply time: the slot
    is reclaimed within that same round, no token is emitted, and the
    co-batched peer's stream is bit-exact."""
    cfg, m, params = small_model
    prompts = _prompts(cfg, [20, 26, 4])
    reqs = [Request(uid=i, prompt=p, max_new=5) for i, p in
            enumerate(prompts)]
    want = _batch_ref(cfg, params, reqs, buckets=(8, 16))

    eng = _engine(cfg, params, buckets=(8, 16), chunked_prefill=True)
    orig = eng._exec_chunked

    def exec_then_cancel(plan, extras):
        res = orig(plan, extras)
        assert eng.cancel(0, reason="client gone mid-prefill")
        return res

    eng._exec_chunked = exec_then_cancel
    run = [Request(uid=i, prompt=p, max_new=5) for i, p in
           enumerate(prompts)]
    rounds_before = eng._round
    eng.run(run)
    assert run[0].done and run[0].finish_reason == "cancel"
    assert run[0].generated == []             # evicted before first token
    assert run[1].done and tuple(run[1].generated) == want[1]
    assert run[2].done and tuple(run[2].generated) == want[2]
    assert eng.stats["cancelled"] == 1
    assert eng.stats["replica_occupancy"] == [0]
    assert eng._free_total() == eng.slots
    assert eng._round > rounds_before         # and the run kept going


def test_cancel_of_finished_or_unknown_uid_is_noop(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params)
    req = Request(uid=7, prompt=_prompts(cfg, [5])[0], max_new=3)
    eng.run([req])
    assert req.done and req.finish_reason == "complete"
    before = dict(eng.stats)
    assert eng.cancel(7) is False             # finished: no-op
    assert eng.cancel(999) is False           # never existed: no-op
    assert eng.stats == before
    assert req.finish_reason == "complete"    # untouched


def test_submit_after_drain_rejected_with_typed_error(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params)
    svc = ServeService(eng, max_pending=8).start()
    s = svc.submit(_prompts(cfg, [5])[0], max_new=3)
    svc.request_drain()
    with pytest.raises(EngineDraining):
        svc.submit(_prompts(cfg, [4])[0], max_new=3)
    with pytest.raises(EngineDraining):
        eng.submit(Request(uid=99, prompt=np.array([1, 2], np.int32),
                           max_new=2))
    with pytest.raises(EngineDraining):
        eng.run([Request(uid=98, prompt=np.array([1], np.int32), max_new=2)])
    svc.join(60)
    toks, fin, _ = s.result(timeout=10)
    assert fin in ("drain", "complete")       # drained or just finished
    assert svc.error is None


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------


def test_deadline_evicts_only_own_request_round_clock(small_model):
    """Deadlines on a deterministic round-counter clock: the expiring
    request is evicted alone (typed 'deadline' finish, counted), peers
    run to completion bit-exactly, the slot comes back."""
    cfg, m, params = small_model
    prompts = _prompts(cfg, [5, 9, 7])
    ref = _batch_ref(cfg, params, [Request(uid=i, prompt=p, max_new=10)
                                   for i, p in enumerate(prompts)])
    eng = _engine(cfg, params)
    eng._clock = lambda: float(eng._round)    # rounds, not wall time
    reqs = [Request(uid=0, prompt=prompts[0], max_new=10),
            Request(uid=1, prompt=prompts[1], max_new=10, deadline=3.0),
            Request(uid=2, prompt=prompts[2], max_new=10)]
    eng.run(reqs)
    assert reqs[1].done and reqs[1].finish_reason == "deadline"
    assert 0 < len(reqs[1].generated) < 10
    assert tuple(reqs[1].generated) == ref[1][:len(reqs[1].generated)]
    assert tuple(reqs[0].generated) == ref[0]
    assert tuple(reqs[2].generated) == ref[2]
    assert eng.stats["deadline_expired"] == 1
    assert eng.stats["cancelled"] == 0        # separate counters
    assert eng._free_total() == eng.slots


def test_deadline_through_service_submit(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params)
    eng._clock = lambda: float(eng._round)
    svc = ServeService(eng, max_pending=8).start()
    s_ok = svc.submit(_prompts(cfg, [5])[0], max_new=8)
    s_dl = svc.submit(_prompts(cfg, [9], seed=1)[0], max_new=64,
                      deadline_s=4.0)
    toks_dl, fin_dl, err_dl = s_dl.result(timeout=300)
    toks_ok, fin_ok, _ = s_ok.result(timeout=300)
    svc.stop()
    assert fin_dl == "deadline" and "deadline" in err_dl
    assert len(toks_dl) < 64
    assert fin_ok == "complete" and len(toks_ok) == 8
    assert eng.stats["deadline_expired"] == 1


# ---------------------------------------------------------------------------
# injected ingress faults: disconnect + slow consumer
# ---------------------------------------------------------------------------


def test_injected_disconnect_and_slow_consumer(small_model):
    cfg, m, params = small_model
    plan = FaultPlan(disconnect_uid=0, disconnect_after=2,
                     stall_uid=1, stall_cap=2)
    eng = _engine(cfg, params, fault=plan.injector())
    svc = ServeService(eng, max_pending=8).start()
    s_disc = svc.submit(_prompts(cfg, [5])[0], max_new=16)
    s_stall = svc.submit(_prompts(cfg, [7], seed=1)[0], max_new=16)
    assert (s_disc.uid, s_stall.uid) == (0, 1)
    # disconnect: consumer drains normally but the injected client drop
    # cancels after 2 delivered tokens
    toks, fin, _ = s_disc.result(timeout=300)
    assert fin == "disconnect" and len(toks) <= 3
    # slow consumer: NOBODY drains this stream; the bounded buffer (cap 2
    # via stream_cap) overflows and the service cancels the request
    _wait(lambda: s_stall.finished, timeout=300)
    toks, fin = s_stall.drain()
    assert fin[0] == "slow_consumer" and "overflowed" in fin[1]
    assert len(toks) <= 2                     # nothing past the cap
    svc.stop()
    assert eng.stats["cancelled"] == 2
    assert eng._free_total() == eng.slots


# ---------------------------------------------------------------------------
# HTTP layer
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _http(svc):
    fe = HttpFrontend(svc)
    ready = threading.Event()
    box = {}

    def run():
        async def amain():
            await fe.start()
            box["loop"] = asyncio.get_running_loop()
            box["stop"] = asyncio.Event()
            ready.set()
            await box["stop"].wait()
            await fe.stop()

        asyncio.run(amain())

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert ready.wait(30)
    try:
        yield fe
    finally:
        box["loop"].call_soon_threadsafe(box["stop"].set)
        t.join(10)


def _req(port, method, path, body=None, timeout=300):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request(method, path, None if body is None else json.dumps(body),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        return r.status, r.read(), dict(r.getheaders())
    finally:
        c.close()


def _sse(port, body, timeout=300):
    """POST a stream=true completion; return (tokens, finish_event)."""
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        c.request("POST", "/v1/completions", json.dumps(body),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        assert r.status == 200, r.read()
        toks, fin, saw_done = [], None, False
        for raw in r.fp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                saw_done = True
                break
            d = json.loads(line[6:])
            if "token" in d:
                assert d["index"] == len(toks)
                toks.append(d["token"])
            else:
                fin = d
        assert saw_done
        return toks, fin
    finally:
        c.close()


def test_http_endpoints_roundtrip(small_model):
    cfg, m, params = small_model
    prompts = _prompts(cfg, [5, 9])
    want = _batch_ref(cfg, params,
                      [Request(uid=i, prompt=p, max_new=5)
                       for i, p in enumerate(prompts)])
    eng = _engine(cfg, params)
    svc = ServeService(eng, max_pending=8).start()
    with _http(svc) as fe:
        st, body, _ = _req(fe.port, "GET", "/healthz")
        assert st == 200 and json.loads(body)["status"] == "serving"
        st, body, _ = _req(fe.port, "GET", "/v1/stats")
        stats = json.loads(body)
        assert {"shed", "completed", "watermark", "round"} <= set(stats)
        # non-streaming completion: exact batch tokens
        st, body, _ = _req(fe.port, "POST", "/v1/completions",
                           {"prompt": prompts[0].tolist(), "max_tokens": 5})
        out = json.loads(body)
        assert st == 200 and tuple(out["tokens"]) == want[0]
        assert out["finish_reason"] == "complete"
        # SSE: same tokens, one event each, typed finish, [DONE]
        toks, fin = _sse(fe.port, {"prompt": prompts[1].tolist(),
                                   "max_tokens": 5, "stream": True})
        assert tuple(toks) == want[1]
        assert fin["finish_reason"] == "complete" and fin["error"] is None
        # typed client errors
        st, body, _ = _req(fe.port, "POST", "/v1/completions",
                           {"max_tokens": 5})
        assert st == 400                      # no prompt
        st, body, _ = _req(fe.port, "POST", "/v1/completions",
                           {"prompt": list(range(500)), "max_tokens": 2})
        assert st == 400                      # oversized for every bucket
        st, _, _ = _req(fe.port, "GET", "/nope")
        assert st == 404
        # draining -> 503 with the drain state visible on healthz
        svc.request_drain()
        st, body, _ = _req(fe.port, "POST", "/v1/completions",
                           {"prompt": [1, 2], "max_tokens": 2})
        assert st == 503
        st, body, _ = _req(fe.port, "GET", "/healthz")
        assert json.loads(body)["status"] == "draining"
    svc.join(60)
    assert svc.error is None


def test_http_overload_returns_429_with_retry_after(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params, slots=2, buckets=(8,))
    svc = ServeService(eng, max_pending=2, retry_after=0.7)
    p = _prompts(cfg, [4])[0]
    svc.submit(p, max_new=4)                  # service not started: the
    svc.submit(p, max_new=4)                  # queue sits at the watermark
    with _http(svc) as fe:
        st, body, hdrs = _req(fe.port, "POST", "/v1/completions",
                              {"prompt": p.tolist(), "max_tokens": 4})
        assert st == 429
        assert hdrs.get("Retry-After") == "0.7"
        assert "shed" in json.loads(body)["error"]
        assert eng.stats["shed"] == 1
        svc.start()
        _wait(lambda: json.loads(_req(fe.port, "GET", "/v1/stats")[1])
              ["completed"] == 2, timeout=300)
    svc.stop()


def test_http_client_disconnect_cancels_request(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params, fault=_SlowDecode())
    svc = ServeService(eng, max_pending=8).start()
    with _http(svc) as fe:
        # raw socket: http.client hides its fd once the server announces
        # Connection: close, and this test needs an abrupt client close
        body = json.dumps({"prompt": _prompts(cfg, [5])[0].tolist(),
                           "max_tokens": 512, "stream": True})
        s = socket.create_connection(("127.0.0.1", fe.port), timeout=300)
        s.sendall((f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                   f"Content-Type: application/json\r\n"
                   f"Content-Length: {len(body)}\r\n\r\n{body}").encode())
        buf = b""
        while b'"token"' not in buf:           # at least one token flowed
            chunk = s.recv(4096)
            assert chunk, f"stream closed early: {buf!r}"
            buf += chunk
        s.close()                             # client hangs up mid-stream
        # the connection watcher turns EOF into cancel(uid): the slot
        # comes back and the cancel is counted as a disconnect
        _wait(lambda: eng.stats["cancelled"] == 1
              and eng._free_total() == eng.slots, timeout=300)
    req = eng.finished[-1]
    assert req.finish_reason == "disconnect"
    assert len(req.generated) < 512
    svc.stop()


# ---------------------------------------------------------------------------
# SIGTERM during live HTTP traffic -> drain -> snapshot -> --resume
# ---------------------------------------------------------------------------


def _launch_env():
    env = dict(os.environ, PYTHONPATH="src")
    base = env.get("JAX_COMPILATION_CACHE_DIR")
    if base:
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(base, "service")
    return env


def test_sigterm_under_live_http_traffic_snapshots_then_resumes(small_model):
    """launch/serve --http: SIGTERM while an SSE stream is mid-request
    drains at a round boundary (client sees a typed 'drain' finish),
    snapshots, exits 0; a --resume run regenerates the interrupted
    request token-for-token (prefix already streamed + resumed tokens ==
    the uninterrupted reference)."""
    cfg, m, params = small_model
    prompt = _prompts(cfg, [6])[0]
    ref_req = Request(uid=0, prompt=prompt, max_new=96)
    want = _batch_ref(cfg, params, [ref_req], max_len=128)[0]

    common = ["-m", "repro.launch.serve", "--arch", "stablelm-1.6b",
              "--reduced", "--slots", "4", "--max-len", "128",
              "--buckets", "8,16,32"]
    with tempfile.TemporaryDirectory() as td:
        snap = os.path.join(td, "drain.npy")
        proc = subprocess.Popen(
            [sys.executable, *common, "--http", "0", "--snapshot", snap],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_launch_env(), cwd=REPO)
        try:
            port = None
            for line in proc.stdout:
                mo = re.search(r"serving HTTP on 127\.0\.0\.1:(\d+)", line)
                if mo:
                    port = int(mo.group(1))
                    break
            assert port, "server never reported its port"
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
            c.request("POST", "/v1/completions",
                      json.dumps({"prompt": prompt.tolist(),
                                  "max_tokens": 96, "stream": True}),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            assert r.status == 200
            first = r.fp.readline()           # live traffic: token flowing
            assert b"token" in first
            proc.send_signal(signal.SIGTERM)  # preempt mid-stream
            streamed, fin = [json.loads(first[6:])["token"]], None
            for raw in r.fp:
                line = raw.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                d = json.loads(line[6:])
                if "token" in d:
                    streamed.append(d["token"])
                else:
                    fin = d
            c.close()
            out, _ = proc.communicate(timeout=600)
        finally:
            proc.kill()
        assert proc.returncode == 0, out[-3000:]
        assert tuple(streamed) == want[:len(streamed)]

        if fin is not None and fin["finish_reason"] == "drain":
            # the interesting path: preempted mid-request -> the snapshot
            # must exist and --resume must regenerate uid 0 token-exactly
            assert len(streamed) < 96
            assert os.path.exists(snap), out[-3000:]
            res = subprocess.run(
                [sys.executable, *common, "--resume", snap],
                capture_output=True, text=True, env=_launch_env(),
                cwd=REPO, timeout=600)
            assert res.returncode == 0, res.stderr[-3000:]
            assert "resuming 1 unfinished" in res.stdout
            mo = re.search(r"req 0: \[([\d, ]*)\]", res.stdout)
            assert mo, res.stdout[-2000:]
            resumed = tuple(int(x) for x in mo.group(1).split(",") if
                            x.strip())
            assert resumed == want
        else:
            # the request beat the signal: it must then be COMPLETE with
            # the full reference stream (still pins token-exact serving
            # under a drain racing live traffic)
            assert fin is not None and fin["finish_reason"] == "complete"
            assert tuple(streamed) == want
