"""The trace reduction, on a 250 ms trace recorded on a TPU v5e (PDQ-int8
paged decode at 8 slots) and on hand-made ones (no JAX)."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import bench, trace_reduce

DATA = Path(__file__).with_name("data") / "trace_v5e_pdq_decode.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def test_busy_time_is_the_union_of_op_intervals(recorded):
    red = trace_reduce.reduce_events(recorded, recorded["window_s"])
    ops = recorded["devices"]["/device:TPU:0"]
    # the union on a 10 ns grid, computed apart from the reduction
    end = int(max(s + d for _, s, d in ops) // 10) + 2
    grid = np.zeros(end, np.int32)
    for _, s, d in ops:
        grid[int(s // 10)] += 1
        grid[int((s + d) // 10)] -= 1
    busy = (np.cumsum(grid) > 0).sum() * 10e-9
    assert red["busy_s"] == pytest.approx(busy, rel=1e-3)
    assert red["busy_s"] == pytest.approx(0.243869239, rel=1e-9)
    run = {"trace": red}
    idle = bench.load_metric("idle_share").read(run)
    assert idle == pytest.approx(100 * (1 - 0.243869239 / 0.25), rel=1e-6)


def test_swiglu_kernel_time_from_its_calls(recorded):
    red = trace_reduce.reduce_events(recorded, recorded["window_s"])
    rf = bench.load_roofline("swiglu_matmul")
    calls = [(n, s) for sig, n, s in red["custom_calls"] if rf.match(sig)]
    ops = recorded["devices"]["/device:TPU:0"]
    direct = [d * 1e-9 for t, _, d in ops
              if rf.match(t.partition(" = ")[2]) is not None]
    assert calls == [(114, pytest.approx(sum(direct)))]
    assert sum(direct) == pytest.approx(0.020865292, rel=1e-6)
    pk = bench.load_peaks()["TPU v5 lite"]
    share = bench.load_metric("swiglu_matmul_roofline").read(
        {"trace": red, "peaks": pk})
    assert share == pytest.approx(
        100 * 114 * (24869504 / 819e9) / sum(direct), rel=1e-6)


def test_breakdown_names_ops_and_idle_gaps(recorded):
    red = trace_reduce.reduce_events(recorded, recorded["window_s"])
    bd = red["breakdown"]
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert bd["device_ops"][0][0] == "%vmap__.13 custom-call"
    assert bd["idle_gaps"][0] == ["$engine.py:402 _exec_decode",
                                  pytest.approx(0.00593216)]
    secs = [s for _, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_self_time_subtracts_nested_ops():
    ops = [["%while.1 = () while()", 0, 100], ["%a = f32[1] fusion()", 10, 20],
           ["%b = f32[1] copy()", 40, 30], ["%c = f32[1] fusion()", 50, 5],
           ["%d = f32[1] fusion()", 120, 10]]
    assert trace_reduce.self_times(ops) == [50, 20, 25, 5, 10]
    red = trace_reduce.reduce_events(
        {"devices": {"/device:TPU:0": ops},
         "host": [["plan", 95, 40], ["step", 0, 10000]]}, 1e-6)
    assert red["busy_s"] == pytest.approx(110e-9)
    assert red["breakdown"]["idle_gaps"] == [["plan", pytest.approx(20e-9)]]
    assert red["breakdown"]["device_ops"][0] == ["%while.1 while",
                                                 pytest.approx(50e-9)]


def test_no_device_ops_reduces_to_nothing():
    assert trace_reduce.reduce_events({"devices": {}, "host": []}, 1.0) is None


def test_compact_drops_layouts_and_attributes():
    hlo = ("%closed_call.1 = (f32[32,11264]{1,0:T(8,128)S(1)}) custom-call("
           "s8[32,2048]{1,0:T(8,128)(4,1)S(1)} %x), custom_call_target="
           "\"tpu_custom_call\", frontend_attributes={kernel_metadata={}}")
    assert trace_reduce.compact(hlo) == (
        "%closed_call.1 = (f32[32,11264]) custom-call(s8[32,2048] %x)")
