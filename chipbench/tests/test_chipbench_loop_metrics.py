"""The readers of the serving loop's own spans and counters, on hand-made
runs (no JAX): the front door's delivery, the decode fetch and the host
time per dispatch; a program without the counters reads nothing."""
from __future__ import annotations

import pytest

from chipbench import bench

FETCH = 'serve_loop_seconds_total{kind="decode",phase="fetch"}'


def phases(**sec):
    """``/metrics`` samples of serve_loop_seconds_total, keyed phase_kind."""
    out = {}
    for key, v in sec.items():
        phase, kind = key.split("_")
        out[f'serve_loop_seconds_total{{kind="{kind}",phase="{phase}"}}'] = v
    return out


def run_of(m0, m1, steps0=10, steps1=60):
    return {"snap0": {"stats": {"decode_steps": steps0}, "metrics": m0},
            "snap1": {"stats": {"decode_steps": steps1}, "metrics": m1}}


def read(name, run):
    return bench.load_metric(name).read(run)


def test_decode_fetch_and_host_time_per_dispatch():
    m0 = phases(ingress_loop=0.1, plan_decode=0.2, dispatch_decode=0.3,
                fetch_decode=4.0, apply_decode=0.2, plan_prefill=0.05,
                dispatch_prefill=0.01, fetch_prefill=0.5, apply_prefill=0.01,
                idle_loop=2.0, other_loop=0.1)
    m1 = phases(ingress_loop=0.15, plan_decode=0.45, dispatch_decode=0.55,
                fetch_decode=24.0, apply_decode=0.7, plan_prefill=0.1,
                dispatch_prefill=0.03, fetch_prefill=1.5, apply_prefill=0.03,
                idle_loop=2.5, other_loop=0.3)
    r = run_of(m0, m1)
    # 50 dispatches: fetch 20 s; host 0.05+0.25+0.25+0.5+0.05+0.02+0.02+0.2
    assert read("decode_fetch_ms", r) == pytest.approx(400.0)
    assert read("decode_host_ms", r) == pytest.approx(1e3 * 1.34 / 50)


def test_a_phase_first_seen_inside_the_window_counts_from_zero():
    m1 = phases(fetch_decode=2.0, plan_decode=0.5, idle_loop=9.0)
    r = run_of({}, m1, steps0=0, steps1=10)
    assert read("decode_fetch_ms", r) == pytest.approx(200.0)
    assert read("decode_host_ms", r) == pytest.approx(50.0)


def test_frontdoor_delivery_per_write():
    n = "serve_frontdoor_deliver_seconds"
    r = run_of({n + "_sum": 0.010, n + "_count": 100},
               {n + "_sum": 0.085, n + "_count": 400})
    assert read("frontdoor_deliver_ms", r) == pytest.approx(0.25)
    still = run_of({n + "_sum": 0.010, n + "_count": 100},
                   {n + "_sum": 0.010, n + "_count": 100})
    assert read("frontdoor_deliver_ms", still) is None


@pytest.mark.parametrize("name", ["frontdoor_deliver_ms", "decode_fetch_ms",
                                  "decode_host_ms"])
def test_a_program_without_the_counters_reads_nothing(name):
    launch = {'serve_launch_seconds_sum{kind="decode"}': 3.0,
              'serve_launch_seconds_count{kind="decode"}': 10}
    assert read(name, run_of(launch, launch)) is None


def test_no_dispatch_in_the_window_reads_nothing():
    m = phases(fetch_decode=1.0, plan_decode=0.1)
    r = run_of(m, m, steps0=5, steps1=5)
    assert read("decode_fetch_ms", r) is None
    assert read("decode_host_ms", r) is None
