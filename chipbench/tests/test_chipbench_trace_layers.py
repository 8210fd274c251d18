"""The program's own names in a trace (trace_layers): on hand-made events,
on the PR-12 trace recorded before the program wrote any, and on a trace
recorded on a TPU v5e with the annotations and named scopes (no JAX)."""
from __future__ import annotations

import gzip
import hashlib
import json
from pathlib import Path

import pytest

from chipbench import bench, trace_layers, trace_reduce

DATA = Path(__file__).with_name("data")
OLD = DATA / "trace_v5e_pdq_decode.json.gz"
NAMED = DATA / "trace_v5e_pdq_decode_named.json.gz"


def _load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_host_spans_idle_in_host_and_scopes_by_hand():
    ops = [["%gather = f32[1] fusion()", 0, 100],
           ["%a = f32[1] custom-call()", 100, 50],
           ["%while.1 = () while()", 300, 100],
           ["%b = f32[1] copy()", 310, 30],
           ["%c = f32[1] fusion()", 600, 50]]
    host = [["fetch:decode", 0, 300], ["apply:decode", 160, 40],
            ["ingress", 200, 20], ["plan:decode", 230, 60],
            ["dispatch:decode", 290, 30], ["$core.py:1 step", 150, 500],
            ["idle", 400, 150], ["plan:decode", 560, 20]]
    ev = {"devices": {"/device:TPU:0": ops},
          "scopes": {"/device:TPU:0": ["paged_gather", "w8a8_matmul", "",
                                       "paged_writeback", ""]},
          "host": host}
    red = trace_layers.reduce_layers(ev)
    assert red["host_spans"] == {
        "fetch:decode": [1, pytest.approx(300e-9)],
        "apply:decode": [1, pytest.approx(40e-9)],
        "ingress": [1, pytest.approx(20e-9)],
        "plan:decode": [2, pytest.approx(80e-9)],
        "dispatch:decode": [1, pytest.approx(30e-9)],
        "idle": [1, pytest.approx(150e-9)]}
    # device idle: 150-300 and 400-600; host work: 160-220, 230-320 and
    # 560-580 (fetch and idle are waits, not work): 60 + 70 + 20 ns
    assert red["idle_in_host_s"] == pytest.approx(150e-9)
    assert red["scope_s"] == {"": pytest.approx(120e-9),
                              "paged_gather": pytest.approx(100e-9),
                              "paged_writeback": pytest.approx(30e-9),
                              "w8a8_matmul": pytest.approx(50e-9)}
    # the extra list leaves trace_reduce's reading as it was
    plain = {"devices": ev["devices"], "host": host}
    assert (trace_reduce.reduce_events(ev, 1e-6)
            == trace_reduce.reduce_events(plain, 1e-6))


def test_program_name_prefers_the_scope_over_the_kernel():
    # op-name metadata as a v5e trace records it (a fusion joins its ops')
    assert trace_layers.program_name(
        "jit(wrapped)/paged_writeback/cache_scatter/pallas_call:") \
        == "paged_writeback"
    assert trace_layers.program_name(
        "jit(wrapped)/while/body/closed_call/vmap(decode_attend_i8kv_fused)"
        "/pallas_call:") == "decode_attend_i8kv_fused"
    assert trace_layers.program_name(
        "jit(wrapped)/while:;jit(wrapped)/paged_gather/reshape:") \
        == "paged_gather"
    # a name inside another identifier is not the name
    assert trace_layers.program_name("jit(f)/my_quantize_fn/dequantized") \
        == ""


def _pb(field: int, value) -> bytes:
    """One protobuf field: a varint for an int, length-delimited else."""
    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_op_names_read_from_the_xspace_protobuf():
    def meta(mid, text, stats):
        return _pb(1, mid) + _pb(2, text) + b"".join(
            _pb(5, _pb(1, sid) + _pb(5, val)) for sid, val in stats)

    def entry(key, value):                      # a protobuf map entry
        return _pb(1, key) + _pb(2, value)

    tpu = (_pb(1, 7) + _pb(2, "/device:TPU:0")
           + _pb(3, _pb(2, "XLA Ops") + _pb(4, _pb(1, 1)))   # skipped
           + _pb(4, entry(1, meta(1, "%cache_scatter.9 = custom-call()", [
               (11, "custom-call"),
               (12, "jit(wrapped)/paged_writeback/cache_scatter/pallas_call:")])))
           + _pb(4, entry(2, meta(2, "%copy.1 = copy()", [(11, "copy")])))
           + _pb(5, entry(11, _pb(1, 11) + _pb(2, "hlo_category")))
           + _pb(5, entry(12, _pb(1, 12) + _pb(2, "tf_op"))))
    host = _pb(2, "/host:CPU") + _pb(4, entry(1, meta(1, "plan:decode", [
        (1, "jit(wrapped)/paged_gather")])))
    space = _pb(1, host) + _pb(1, tpu)
    assert trace_layers.op_names(space) == {"/device:TPU:0": {
        "%cache_scatter.9 = custom-call()":
            "jit(wrapped)/paged_writeback/cache_scatter/pallas_call:"}}


def test_a_trace_without_program_names_reads_none():
    old = _load(OLD)
    red = trace_layers.reduce_layers(old)
    assert red == {"host_spans": {}, "idle_in_host_s": None, "scope_s": {}}
    assert trace_layers.reduce_layers({"devices": {}, "host": []}) is None


def test_the_old_trace_reduces_as_it_did():
    """Every key ``trace_reduce`` gives on the PR-12 recording, pinned."""
    old = _load(OLD)
    red = trace_reduce.reduce_events(old, old["window_s"])
    digest = hashlib.sha256(json.dumps(red, sort_keys=True).encode())
    assert digest.hexdigest() == (
        "117f635ea322faa7f85f392f5f0f99290ff14870ab52081873d5849f4929dfe7")


def test_the_recorded_trace_carries_the_program_names():
    ev = _load(NAMED)
    red = trace_layers.reduce_layers(ev)
    spans = red["host_spans"]
    # one dispatch boundary: the fetch that ends it, the host work between
    # the two programs, and the next launch
    for name in ("fetch:decode", "apply:decode", "ingress", "plan:decode",
                 "dispatch:decode", "page_stats", "launch:decode"):
        assert name in spans, name
    assert spans["apply:decode"][0] == spans["dispatch:decode"][0] == 1
    # the device idles while the host applies, plans and dispatches
    old = trace_reduce.reduce_events(ev, ev["window_s"])
    idle = old["window_s"] - old["busy_s"]
    assert 0.0 < red["idle_in_host_s"] < idle
    assert red["idle_in_host_s"] == pytest.approx(0.010611327, rel=1e-6)
    # the pool's scopes and the kernels' names, each found
    sc = red["scope_s"]
    assert set(sc) == {"", "paged_gather", "paged_writeback",
                       "decode_attend_i8kv_fused", "w8a8_matmul",
                       "w8a8_swiglu_matmul", "pdq_prologue"}
    assert sum(sc.values()) == pytest.approx(old["busy_s"], rel=1e-6)
    assert sc["paged_gather"] == pytest.approx(0.053704604, rel=1e-6)
    assert sc["paged_writeback"] == pytest.approx(0.011802898, rel=1e-6)
    # trace_reduce reads the new recording as it reads the old one
    assert set(old) == {"busy_s", "window_s", "n_ops", "custom_calls",
                        "breakdown"}
    assert old["n_ops"] == 947


def test_kv_move_and_idle_in_host_shares_on_the_recorded_trace():
    ev = _load(NAMED)
    red = trace_reduce.reduce_all(ev, ev["window_s"])
    # what trace_reduce gives, with trace_layers' readings beside it
    assert red["busy_s"] == trace_reduce.reduce_events(
        ev, ev["window_s"])["busy_s"]
    assert red["scope_s"] == trace_layers.reduce_layers(ev)["scope_s"]
    run = {"trace": red}
    kv = bench.load_metric("kv_move_share").read(run)
    assert kv == pytest.approx(100 * (0.053704604 + 0.011802898)
                               / red["busy_s"], rel=1e-6)
    idle = bench.load_metric("idle_in_host_share").read(run)
    assert idle == pytest.approx(100 * 0.010611327 / ev["window_s"], rel=1e-6)
    assert 0 < idle < bench.load_metric("idle_share").read(run)


def test_a_trace_without_program_names_reads_no_shares():
    old = _load(OLD)
    run = {"trace": trace_reduce.reduce_all(old, old["window_s"])}
    assert bench.load_metric("kv_move_share").read(run) is None
    assert bench.load_metric("idle_in_host_share").read(run) is None
    for name in ("kv_move_share", "idle_in_host_share"):
        assert bench.load_metric(name).read({"trace": None}) is None
