"""Each metric reader gives the hand-worked value on a recorded run (no JAX)."""
from __future__ import annotations

import pytest

from chipbench import bench

CONFIG = {"hidden_size": 2048, "num_attention_heads": 32,
          "num_key_value_heads": 32, "intermediate_size": 5632,
          "num_hidden_layers": 24, "vocab_size": 100352, "peak": "int8_ops",
          "serve": {"slots": 8, "decode_steps": 4}}
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def rec(due, times, prompt_len=10, status=200, warmup=False, finish="complete"):
    return {"idx": 0, "prompt_len": prompt_len, "max_tokens": len(times),
            "due": due, "sent": due, "status": status, "times": times,
            "n_tokens": len(times), "finish": finish, "error": None,
            "warmup": warmup}


def run_of(records, stats0=None, stats1=None, m0=None, m1=None, trace=None):
    return {"config": CONFIG, "seconds": 10.0, "t_start": 80.0, "t0": 100.0,
            "t1": 110.0, "t_stop": 125.0, "records": records,
            "snap0": {"stats": stats0 or {}, "metrics": m0 or {}},
            "snap1": {"stats": stats1 or {}, "metrics": m1 or {}},
            "trace": trace, "peaks": PEAKS}


def read(name, run):
    return bench.load_metric(name).read(run)


def test_tpot_p50_reads_tokens_inside_the_window():
    rs = []
    for i in range(10):                 # 20 tokens each, gap (i + 1) ms
        g = 0.001 * (i + 1)
        rs.append(rec(95.0, [100.5 + g * k for k in range(20)]))
    rs.append(rec(95.0, [100.5, 100.6]))      # too few tokens: not counted
    # one request straddles the window's start: only its tokens inside count
    rs.append(rec(95.0, [99.0 + 0.5 * k for k in range(12)]))   # gap 0.5 s
    vals = sorted([1e-3 * (i + 1) for i in range(10)] + [0.5])
    assert read("tpot_p50_ms", run_of(rs)) == pytest.approx(1e3 * vals[5])


def test_output_tok_s_and_setup_s():
    rs = [rec(90.0, [99.0, 100.0, 105.0, 110.0, 111.0]),
          rec(101.0, [101.5 + 0.1 * k for k in range(30)])]
    assert read("output_tok_s", run_of(rs)) == pytest.approx(33 / 10)
    assert read("setup_s", run_of(rs)) == pytest.approx(20.0)


def test_scheduler_counters():
    r = run_of([], stats0={"prefill_tokens": 100, "prefill_padded_tokens": 1000,
                           "decode_steps": 10, "decode_tokens": 200},
               stats1={"prefill_tokens": 356, "prefill_padded_tokens": 3048,
                       "decode_steps": 60, "decode_tokens": 1640})
    assert read("prefill_pad_share", r) == pytest.approx(100 * (1 - 256 / 2048))
    # 50 dispatches x 8 slots x 4 steps = 1600 rows, 1440 tokens
    assert read("decode_row_occupancy", r) == pytest.approx(90.0)
    idle = run_of([], stats0={"prefill_tokens": 5, "prefill_padded_tokens": 9},
                  stats1={"prefill_tokens": 5, "prefill_padded_tokens": 9})
    assert read("prefill_pad_share", idle) is None


def test_launch_times_from_histogram_deltas():
    m0 = {'serve_launch_seconds_sum{kind="prefill"}': 1.0,
          'serve_launch_seconds_count{kind="prefill"}': 10,
          'serve_launch_seconds_sum{kind="decode"}': 5.0,
          'serve_launch_seconds_count{kind="decode"}': 100}
    m1 = {'serve_launch_seconds_sum{kind="prefill"}': 1.6,
          'serve_launch_seconds_count{kind="prefill"}': 14,
          'serve_launch_seconds_sum{kind="decode"}': 16.0,
          'serve_launch_seconds_count{kind="decode"}': 150}
    r = run_of([], m0=m0, m1=m1)
    assert read("prefill_launch_ms", r) == pytest.approx(150.0)
    assert read("decode_dispatch_ms", r) == pytest.approx(220.0)
    assert read("prefill_launch_ms", run_of([], m0=m0, m1=m0)) is None


def test_idle_share_and_trace_metrics_need_a_trace():
    tr = {"busy_s": 3.6, "window_s": 4.0, "custom_calls": [],
          "breakdown": {"device_ops": [], "idle_gaps": []}}
    assert read("idle_share", run_of([], trace=tr)) == pytest.approx(10.0)
    assert read("idle_share", run_of([])) is None
    assert read("swiglu_matmul_roofline", run_of([])) is None
    assert read("swiglu_matmul_roofline", run_of([], trace=tr)) is None


def test_swiglu_roofline_from_call_signatures():
    sig = ("(f32[32,11264], s8[32,5632], f32[32,1], f32[32,1], f32[32,1]) "
           "custom-call(s8[32,2048] %a, s8[2048,11264] %b, f32[32,1] %c)")
    other = "bf16[128,2048] custom-call(s8[128,2048] %a, s8[2048,2048] %b)"
    tr = {"busy_s": 1.0, "window_s": 1.0, "breakdown": {},
          "custom_calls": [[sig, 100, 0.02], [other, 100, 0.5]]}
    # least time per call: 24,869,504 bytes / 819e9 (memory bound)
    want = 100 * 100 * (24869504 / 819e9) / 0.02
    assert read("swiglu_matmul_roofline", run_of([], trace=tr)) == \
        pytest.approx(want)


def test_step_mfu_counts_prompts_and_tokens_in_the_window():
    # prompt of 10 whose first token came in the window, then 2 tokens
    # (contexts 11, 12); a second request's token outside the window
    rs = [rec(99.0, [100.2, 100.3, 100.4]), rec(80.0, [90.0, 111.0])]
    L, n_layer, hd, head = 24, 51380224, 2048, 205520896
    prefill = 2 * L * n_layer * 10 + 4 * L * hd * 55 + 2 * head
    decode = sum(2 * L * n_layer + 4 * L * hd * c + 2 * head for c in (11, 12))
    want = 100 * (prefill + decode) / (10.0 * 393e12)
    assert read("step_mfu", run_of(rs)) == pytest.approx(want)
