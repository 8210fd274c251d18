"""Serving telemetry plane (serve/telemetry.py) + its engine hook points.

Pins the observability contract:
  * the metrics registry renders VALID Prometheus text exposition 0.0.4:
    HELP/TYPE lines, cumulative ``_bucket{le=...}`` series ending in +Inf,
    ``_sum``/``_count``, label escaping of backslash/quote/newline;
  * histograms never lose observations through any observe/merge
    interleaving (hypothesis property: sum(counts) == count == total
    observations, sum preserved exactly);
  * the tracer exports Chrome-trace-event JSON Perfetto accepts: every
    span is a "X" complete event with numeric ts/dur and int pid/tid, and
    process/thread metadata rows name every (pid, tid) in the trace;
  * a served engine populates the standard series (TTFT, per-token, queue
    wait, launch wall time, loop phase seconds, pdq health) and ``GET
    /metrics`` + ``GET /v1/events`` serve them over the front door;
  * the serve loop's phases (serve_loop_seconds_total) sum to the loop
    thread's wall time, ``fetch`` is booked once per decode dispatch, and
    the front door observes one delivery per SSE write that carried
    tokens;
  * ``Telemetry.span`` writes a ``jax.profiler`` annotation of its name
    with the ring off, and the ring's export carries its clock epoch;
  * /v1/stats and /metrics survive a concurrent scrape storm racing the
    serving loop (the PR-9 snapshot-under-lock fix - list-valued counters
    used to be serialized while the loop thread resized them);
  * the device-side pdq collector counts clip saturation and guard
    fallbacks without adding pallas_calls (census pinned elsewhere), and
    the S-blocks the int8-KV attend reads and its grid spans.
"""
import dataclasses
import glob
import http.client
import json
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

from test_serve_service import _http, _prompts, _req, _sse, _wait

from repro.configs import reduced_config
from repro.kernels import ops
from repro.kernels.kv_cache import attend_tiling
from repro.models import build_model
from repro.models.linops import quantize_weight
from repro.serve import Request, ServeConfig, ServeService, build_engine
from repro.serve.service import TokenStream
from repro.serve.telemetry import (LATENCY_BUCKETS, LOOP_PHASES, Histogram,
                                   MetricsRegistry, Telemetry, Tracer)


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
    return build_engine(ServeConfig(**kw), cfg=cfg, params=params)


# ---------------------------------------------------------------------------
# Prometheus exposition correctness
# ---------------------------------------------------------------------------


def test_prometheus_exposition_names_types_and_series():
    m = MetricsRegistry()
    m.counter("reqs_total", "requests seen").inc(3)
    m.gauge("pool_free", "free pages").set(41)
    h = m.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(7.0)
    text = m.render()
    lines = text.splitlines()
    assert text.endswith("\n")
    assert "# HELP reqs_total requests seen" in lines
    assert "# TYPE reqs_total counter" in lines
    assert "reqs_total 3" in lines
    assert "# TYPE pool_free gauge" in lines
    assert "pool_free 41" in lines
    assert "# TYPE lat_seconds histogram" in lines
    # cumulative buckets, +Inf == _count, integral values print as ints
    assert 'lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{le="1"} 2' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 3' in lines
    assert "lat_seconds_count 3" in lines
    assert any(l.startswith("lat_seconds_sum 7.55") for l in lines)
    # families are sorted and every non-comment line belongs to a family
    fams = [l.split()[2] for l in lines if l.startswith("# TYPE")]
    assert fams == sorted(fams)


def test_prometheus_label_escaping_and_label_sets():
    m = MetricsRegistry()
    m.counter("c_total", "c", kind='we"ird\\path\nx').inc()
    m.counter("c_total", "c", kind="plain").inc(2)
    text = m.render()
    # one TYPE line, two children, escaped backslash/quote/newline
    assert text.count("# TYPE c_total counter") == 1
    assert 'c_total{kind="we\\"ird\\\\path\\nx"} 1' in text
    assert 'c_total{kind="plain"} 2' in text
    # same (name, labels) returns the same child
    assert m.counter("c_total", kind="plain").value == 2.0


def test_registry_is_shared_by_handle_and_lookup():
    tel = Telemetry(enabled=True)
    tel.ttft.observe(0.2)
    again = tel.metrics.histogram("serve_ttft_seconds")
    assert again is tel.ttft and again.count == 1
    text = tel.metrics.render()
    for name in ("serve_ttft_seconds", "serve_per_token_seconds",
                 "serve_queue_wait_seconds",
                 "serve_frontdoor_deliver_seconds", "serve_shed_total",
                 "pdq_fallbacks", "pdq_clip_hits", "pdq_clip_total"):
        assert f"# TYPE {name}" in text, name
    # the two series that duplicated exported numbers are gone
    assert "serve_round_occupancy" not in text
    assert "pdq_clip_rate" not in text


def test_disabled_telemetry_renders_empty_and_spans_are_noops():
    tel = Telemetry(enabled=False, trace=True)
    assert tel.metrics.render() == "\n"
    with tel.span("launch:decode"):
        pass
    assert tel.tracer.events() == []
    assert tel.summary() == {}
    tel.observe_pdq(1, 2, 3)          # must not raise, must not record
    assert tel.metrics.render() == "\n"


# ---------------------------------------------------------------------------
# histogram properties
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(groups=st.lists(st.lists(st.floats(min_value=0.0, max_value=100.0,
                                          allow_nan=False), max_size=30),
                       min_size=1, max_size=6),
       data=st.data())
def test_histogram_observe_merge_never_loses_counts(groups, data):
    """Observations spread over several histograms and merged in any order
    conserve count, per-bucket counts and sum exactly."""
    parts = [Histogram(buckets=(0.5, 1.0, 5.0, 50.0)) for _ in groups]
    for h, vals in zip(parts, groups):
        for v in vals:
            h.observe(v)
    total = Histogram(buckets=(0.5, 1.0, 5.0, 50.0))
    order = data.draw(st.permutations(range(len(parts))))
    for i in order:
        total.merge(parts[i])
    all_vals = [v for vals in groups for v in vals]
    assert total.count == len(all_vals)
    assert sum(total.counts) == total.count
    assert total.sum == pytest.approx(sum(all_vals))
    # bucket membership matches a direct histogram of the same values
    direct = Histogram(buckets=(0.5, 1.0, 5.0, 50.0))
    for v in all_vals:
        direct.observe(v)
    assert total.counts == direct.counts


def test_histogram_percentiles_bracket_the_data():
    h = Histogram(buckets=LATENCY_BUCKETS)
    assert h.percentile(0.5) == 0.0           # empty: defined, zero
    for v in [0.002] * 90 + [0.2] * 10:
        h.observe(v)
    assert 0.001 <= h.percentile(0.50) <= 0.0025
    assert 0.1 <= h.percentile(0.99) <= 0.25
    h2 = Histogram(buckets=(1.0,))
    h2.observe(100.0)                         # overflow bucket
    assert h2.percentile(0.99) == 1.0         # reports the edge


def test_histogram_merge_rejects_mismatched_buckets():
    with pytest.raises(AssertionError):
        Histogram(buckets=(1.0,)).merge(Histogram(buckets=(2.0,)))


# ---------------------------------------------------------------------------
# tracer: Chrome trace-event JSON schema
# ---------------------------------------------------------------------------


def test_tracer_exports_valid_chrome_trace():
    clock = iter(np.arange(0.0, 10.0, 0.001))
    tr = Tracer(enabled=True, pid=0, clock=lambda: next(clock))
    tr.add("launch:decode", cat="phase", ts=tr.now_us(), dur=1000.0, tid=2,
           args={"rows": 4})
    tr.add("launch:prefill", ts=100.0, dur=250.0, pid=1, tid=2,
           args={"process": 1})
    tr.name_process(1, "jax process 1")
    tr.name_thread(1, 2, "launch")
    obj = json.loads(json.dumps(tr.export()))    # JSON-serializable
    evs = obj["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert len(spans) == 2
    for e in spans:
        assert isinstance(e["name"], str) and isinstance(e["cat"], str)
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    assert {e["pid"] for e in spans} == {0, 1}
    # metadata names every pid and every (pid, tid)
    proc_rows = {e["pid"] for e in meta if e["name"] == "process_name"}
    thread_rows = {(e["pid"], e["tid"]) for e in meta
                   if e["name"] == "thread_name"}
    assert {0, 1} <= proc_rows
    assert {(0, 2), (1, 2)} <= thread_rows
    named = {e["pid"]: e["args"]["name"] for e in meta
             if e["name"] == "process_name"}
    assert named[1] == "jax process 1"
    # args values are JSON primitives
    assert spans[1]["args"]["process"] == 1


def test_tracer_ring_is_bounded_and_counts_drops():
    clock = iter(np.arange(0.0, 10.0, 0.001))
    tr = Tracer(enabled=True, capacity=4, clock=lambda: next(clock))
    for i in range(10):
        tr.add(f"s{i}", ts=float(i), dur=1.0)
    assert len(tr.events()) == 4
    assert tr.dropped == 6
    assert tr.export()["otherData"]["dropped_spans"] == 6
    assert [e["name"] for e in tr.events()] == ["s6", "s7", "s8", "s9"]


# ---------------------------------------------------------------------------
# device-side pdq health collector (kernels/ops.pdq_telemetry)
# ---------------------------------------------------------------------------


def test_pdq_collector_counts_clip_and_fallbacks():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256), jnp.float32)
    rec = quantize_weight(
        jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32))

    def prog(x):
        with ops.pdq_guard(), ops.pdq_telemetry() as col:
            y = ops.pdq_dense(x, rec)
            return y, col.summary()

    y, tel = jax.jit(prog)(x)
    tel = dict(zip(ops.TEL_FIELDS, np.asarray(tel)))
    fb, hits, total = tel["fallbacks"], tel["clip_hits"], tel["clip_total"]
    assert tel["expert_rows"] == 0.0                  # no MoE layer here
    assert total == x.shape[0] * rec["q"].shape[1]    # every output checked
    assert 0 <= hits <= total
    assert fb == 0.0                                  # healthy fast path

    def poisoned(x):
        with ops.pdq_guard(), ops.pdq_fault(), ops.pdq_telemetry() as col:
            y = ops.pdq_dense(x, rec)
            return y, col.summary()

    y2, tel2 = jax.jit(poisoned)(x)
    assert np.asarray(tel2)[0] == 1.0                 # the guard fired once
    assert np.isfinite(np.asarray(y2)).all()


def test_pdq_collector_disabled_is_constant_zeros():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 256), jnp.float32)
    rec = quantize_weight(
        jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32))

    def prog(x):
        with ops.pdq_telemetry(enable=False) as col:
            return ops.pdq_dense(x, rec), col.summary()

    _, tel = jax.jit(prog)(x)
    assert np.asarray(tel).tolist() == [0.0] * len(ops.TEL_FIELDS)
    assert np.asarray(tel).shape == (ops.PDQ_TEL_WIDTH,)


def test_engine_counts_the_attend_blocks_it_reads(small_model):
    """``attend_blocks`` in ``/v1/stats`` is the sum over decode steps,
    layers and rows of ceil(len / bs), the S-blocks the int8-KV attend
    reads; ``attend_blocks_grid`` is rows x S / bs x layers x steps.
    Counted on the jnp path too, with the kernel's block."""
    cfg, _, params = small_model
    cfg = dataclasses.replace(cfg, quant_kv="dynamic")
    eng = _engine(cfg, params, slots=1, max_len=512, buckets=(256,))
    prompt = np.arange(250, dtype=np.int32) % cfg.vocab
    eng.run([Request(uid=0, prompt=prompt, max_new=12)])
    stats = eng.stats_snapshot()
    S = 512
    bs = attend_tiling(S, cfg.n_kv_heads, cfg.head_dim)
    assert S // bs == 2
    steps = stats["decode_steps"]
    assert steps == 11                      # the prefill gave the first token
    lens = len(prompt) + 1 + np.arange(steps)   # 251 .. 261: crosses 256
    assert stats["attend_blocks"] == cfg.n_layers * int(np.sum(-(-lens // bs)))
    assert stats["attend_blocks_grid"] == cfg.n_layers * steps * S // bs


# ---------------------------------------------------------------------------
# engine integration: standard series populated, trace spans emitted
# ---------------------------------------------------------------------------


def test_served_engine_populates_standard_series(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params, trace=True)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                    max_new=4) for i, L in enumerate([3, 9, 12])]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    tel = eng.tel
    assert tel.ttft.count == 3
    assert tel.per_token.count == sum(len(r.generated) - 1 for r in reqs)
    assert tel.queue_wait.count == 3
    phases = {dict(labels)["phase"] for labels in
              tel.metrics.get("serve_loop_seconds_total")}
    assert {"plan", "dispatch", "fetch", "apply"} <= phases
    kinds = {k for labels, _ in
             tel.metrics.get("serve_launch_seconds").items()
             for lk, k in labels if lk == "kind"}
    assert {"prefill", "decode"} <= kinds
    summ = tel.summary()
    for key in ("ttft", "per_token", "queue_wait"):
        s = summ[key]
        assert s["count"] > 0 and 0 <= s["p50"] <= s["p90"] <= s["p99"]
    names = {e["name"] for e in tel.tracer.events()}
    assert {"plan:prefill", "launch:prefill", "apply:prefill",
            "plan:decode", "launch:decode", "apply:decode",
            "dispatch:prefill", "fetch:prefill",
            "dispatch:decode", "fetch:decode"} <= names
    assert any(n.startswith("req 0") for n in names)
    # request spans ride the request thread row with uid attribution
    req_spans = [e for e in tel.tracer.events() if e["tid"] == 0]
    assert all("uid" in (e.get("args") or {}) for e in req_spans)


def test_telemetry_disabled_engine_serves_identically(small_model):
    cfg, m, params = small_model
    rng = np.random.default_rng(0)
    lens = [3, 9, 12, 5]
    mk = lambda: [Request(uid=i, prompt=np.asarray(p), max_new=4)
                  for i, p in enumerate(_prompts(cfg, lens))]
    on = _engine(cfg, params, telemetry=True)
    off = _engine(cfg, params, telemetry=False)
    r_on, r_off = mk(), mk()
    on.run(r_on)
    off.run(r_off)
    assert ([tuple(r.generated) for r in r_on]
            == [tuple(r.generated) for r in r_off])
    assert off.tel.metrics.render() == "\n"


# ---------------------------------------------------------------------------
# front door: /metrics + /v1/events + the scrape storm
# ---------------------------------------------------------------------------


def test_metrics_and_events_endpoints(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params)
    svc = ServeService(eng, max_pending=8).start()
    with _http(svc) as fe:
        streams = [svc.submit(p, max_new=4)
                   for p in _prompts(cfg, [5, 9, 30])]
        for s in streams:
            s.result(timeout=300)
        st, body, hdrs = _req(fe.port, "GET", "/metrics")
        assert st == 200
        assert hdrs.get("Content-Type", "").startswith("text/plain")
        text = body.decode()
        for name in ("serve_ttft_seconds_bucket", "serve_ttft_seconds_count",
                     "serve_per_token_seconds_sum",
                     "serve_queue_wait_seconds_count",
                     "serve_launch_seconds_bucket",
                     "serve_loop_seconds_total", "pdq_fallbacks",
                     "pdq_clip_hits"):
            assert name in text, name
        assert 'serve_loop_seconds_total{kind="decode",phase="fetch"}' \
            in text
        assert 'serve_launch_seconds_bucket{kind="prefill"' in text
        assert "serve_ttft_seconds_count 3" in text
        st, body, hdrs = _req(fe.port, "GET", "/v1/events")
        assert st == 200
        events = [json.loads(l) for l in body.decode().splitlines()]
        assert all({"t", "step", "kind", "detail"} <= set(e)
                   for e in events)
    svc.stop()


def test_stats_and_metrics_survive_concurrent_scrape_storm(small_model):
    """Regression for the /v1/stats race: scrape threads hammer /v1/stats,
    /metrics and /v1/events while the loop thread serves a 3x-overload
    burst (list-valued stats resized per admission); every response must
    parse and no scrape may crash the serializer."""
    cfg, m, params = small_model
    eng = _engine(cfg, params, slots=2, buckets=(8,))
    svc = ServeService(eng, max_pending=4).start()
    errs: list = []
    stop = threading.Event()

    def scrape(path, check):
        while not stop.is_set():
            try:
                c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
                c.request("GET", path)
                r = c.getresponse()
                check(r.status, r.read())
                c.close()
            except Exception as e:          # noqa: BLE001 - collect, assert
                errs.append((path, repr(e)))
                return

    with _http(svc) as fe:
        port = fe.port
        threads = [
            threading.Thread(target=scrape, args=(
                "/v1/stats",
                lambda s, b: (json.loads(b), )[0] if s == 200
                else errs.append(("status", s)))),
            threading.Thread(target=scrape, args=(
                "/metrics",
                lambda s, b: b.decode() if s == 200
                else errs.append(("status", s)))),
            threading.Thread(target=scrape, args=(
                "/v1/events",
                lambda s, b: [json.loads(l) for l in b.splitlines()]
                if s == 200 else errs.append(("status", s)))),
        ]
        for t in threads:
            t.start()
        streams = []
        for i in range(24):
            try:
                streams.append(svc.submit(
                    _prompts(cfg, [4 + i % 5], seed=i)[0], max_new=4))
            except Exception:
                pass                        # shed: part of the storm
        for s in streams:
            s.result(timeout=300)
        stop.set()
        for t in threads:
            t.join(60)
    svc.stop()
    assert not errs, errs[:5]
    snap = eng.stats_snapshot()
    assert snap["completed"] == len(streams)
    assert isinstance(snap["replica_admits"], list)


# ---------------------------------------------------------------------------
# the serve loop's phases, the front-door stamp, profiler annotations
# ---------------------------------------------------------------------------


def _phase_seconds(tel) -> dict:
    return {(dict(k)["phase"], dict(k)["kind"]): c.value for k, c in
            tel.metrics.get("serve_loop_seconds_total").items()}


def test_loop_phases_sum_to_the_loop_wall_time(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params, paged=True, page_size=16, decode_steps=2)
    svc = ServeService(eng, max_pending=8)
    t0 = time.perf_counter()
    svc.start()
    for s in [svc.submit(p, max_new=6) for p in _prompts(cfg, [5, 9, 20])]:
        s.result(timeout=300)
    time.sleep(0.3)                          # an idle stretch
    svc.submit(_prompts(cfg, [7], seed=1)[0], max_new=3).result(timeout=300)
    svc.stop()
    wall = time.perf_counter() - t0
    sec = _phase_seconds(eng.tel)
    assert {p for p, _ in sec} == set(LOOP_PHASES)
    assert sec[("idle", "loop")] >= 0.25
    assert abs(sum(sec.values()) - wall) <= 0.02 * wall, (sum(sec.values()),
                                                          wall)


def test_fetch_is_booked_once_per_decode_dispatch(small_model):
    cfg, m, params = small_model
    eng = _engine(cfg, params, trace=True, decode_steps=2)
    reqs = [Request(uid=i, prompt=p, max_new=5)
            for i, p in enumerate(_prompts(cfg, [4, 11]))]
    eng.run(reqs)
    spans = [e for e in eng.tel.tracer.events()
             if e["name"] == "fetch:decode"]
    assert len(spans) == eng.stats["decode_steps"] > 0
    # fetch nests in its launch, after its dispatch
    launches = [e for e in eng.tel.tracer.events()
                if e["name"] == "launch:decode"]
    dispatches = [e for e in eng.tel.tracer.events()
                  if e["name"] == "dispatch:decode"]
    for ln, d, f in zip(launches, dispatches, spans):
        assert ln["ts"] <= d["ts"] <= d["ts"] + d["dur"] <= f["ts"] + 1e-3
        assert f["ts"] + f["dur"] <= ln["ts"] + ln["dur"] + 1e-3
    sec = _phase_seconds(eng.tel)
    assert sec[("fetch", "decode")] == pytest.approx(
        sum(e["dur"] for e in spans) * 1e-6, rel=1e-3, abs=1e-5)


def test_frontdoor_observes_one_delivery_per_sse_write(small_model,
                                                       monkeypatch):
    cfg, m, params = small_model
    eng = _engine(cfg, params, decode_steps=2)
    writes = []                              # drains that carried tokens
    drain = TokenStream.drain

    def counting(self):
        toks, fin = drain(self)
        if toks:
            writes.append(self.drained_since)
        return toks, fin

    monkeypatch.setattr(TokenStream, "drain", counting)
    svc = ServeService(eng, max_pending=8).start()
    with _http(svc) as fe:
        got = [_sse(fe.port, {"prompt": p.tolist(), "max_tokens": 6,
                              "stream": True})[0]
               for p in _prompts(cfg, [5, 12])]
    svc.stop()
    assert [len(t) for t in got] == [6, 6]
    h = eng.tel.deliver
    assert 2 <= h.count == len(writes) <= 12
    assert all(t is not None for t in writes)
    assert 0.0 < h.sum < 60.0


def test_span_writes_a_profiler_annotation_with_the_ring_off(tmp_path):
    tel = Telemetry(enabled=True, trace=False)
    assert not tel.tracer.enabled
    x = jnp.ones((64, 64))
    with jax.profiler.trace(str(tmp_path)):
        with tel.span("plan:decode", phase="plan", kind="decode"):
            time.sleep(0.002)
        with tel.span("fetch:decode"):
            (x @ x).block_until_ready()
    assert tel.tracer.events() == []
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host = {ev.name: ev.duration_ns for plane in pd.planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events}
    assert host["plan:decode"] >= 2e6 and "fetch:decode" in host
    assert _phase_seconds(tel)[("plan", "decode")] >= 0.002


def test_trace_export_carries_the_clock_epoch():
    clock = iter(np.arange(5.0, 10.0, 0.001))
    before = time.time_ns()
    tel = Telemetry(trace=True, clock=lambda: next(clock))
    after = time.time_ns()
    with tel.span("apply:decode"):
        pass
    other = json.loads(json.dumps(tel.tracer.export()))["otherData"]
    assert other["epoch_clock_s"] == 5.0
    assert before <= other["epoch_unix_ns"] <= after
    # a span's wall time is epoch_unix_ns + ts * 1e3: here 1 ms after it
    ev, = tel.tracer.events()
    assert ev["ts"] == pytest.approx(1000.0) and ev["dur"] == \
        pytest.approx(1000.0)
