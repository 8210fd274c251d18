"""The held experts' per-layer metrics read what they should from a reduced
run, and nothing from a program or configuration without routed experts."""
from __future__ import annotations

import json

import pytest

from chipbench import bench

CONFIG = json.loads((bench.ROOT / "chipbench" / "configs" /
                     "deepseek-v2-lite.pdq-int8.json").read_text())
DENSE = json.loads((bench.ROOT / "chipbench" / "configs" /
                    "stablelm-1.6b.pdq-int8.json").read_text())


def _sig(M, K, N, E, out="bf16"):
    return (f"{out}[{M},{N}] custom-call(s8[{M},{K}] %a.1, s8[{K},{N}] %b.2, "
            f"f32[{M},1] %c.3, s32[{M},1] %d.4, f32[1,{N}] %e.5, "
            f"s32[1,{N}] %f.6, f32[{M},{E}] %g.7, s32[{M},{E}] %h.8, "
            f"f32[{M},{E}] %i.9, f32[{M},{E}] %j.10)")


GATE_UP = _sig(128, 2048, 22528, 176)          # 8 experts x (gate, up) x 1408
DOWN = _sig(128, 11264, 2048, 1)               # 8 x 1408 stacked along K
WO = _sig(128, 2048, 2048, 1)                  # attention output, not experts


def _run(config, calls, stats0=None, stats1=None):
    return {"config": config, "root": bench.ROOT,
            "peaks": bench.load_peaks()["TPU v5 lite"],
            "trace": {"busy_s": 2.0, "custom_calls": calls},
            "snap0": {"stats": stats0 or {"decode_steps": 0}},
            "snap1": {"stats": stats1 or {"decode_steps": 0}}}


def test_expert_calls_are_told_by_the_configuration_widths():
    rf = bench.load_roofline("expert_matmul")
    assert rf.widths(CONFIG) == {(2048, 22528), (11264, 2048)}
    assert rf.match(GATE_UP, CONFIG) == {"M": 128, "K": 2048, "N": 22528,
                                         "E": 176, "out_bytes": 2}
    assert rf.match(DOWN, CONFIG)["E"] == 1
    assert rf.match(WO, CONFIG) is None
    assert rf.match(GATE_UP, DENSE) is None and rf.widths(DENSE) == set()
    # weight bytes dominate: 2048 x 22528 int8 plus activations and output
    assert rf.bytes(128, 2048, 22528, 2, 176) > 2048 * 22528


def test_expert_share_and_roofline_read_the_expert_calls():
    share = bench.load_metric("moe_expert_share")
    roof = bench.load_metric("expert_matmul_roofline")
    run = _run(CONFIG, [[GATE_UP, 26, 0.2], [DOWN, 26, 0.1], [WO, 27, 0.5]])
    assert share.read(run) == pytest.approx(15.0)
    r = roof.read(run)
    assert 0 < r <= 100
    # a bytes-bound decode call: least time is its bytes at HBM bandwidth
    rf = bench.load_roofline("expert_matmul")
    least = 26 * (rf.bytes(128, 2048, 22528, 2, 176)
                  + rf.bytes(128, 11264, 2048, 2, 1)) / 819e9
    assert r == pytest.approx(100.0 * least / 0.3)
    for cfg, calls in ((DENSE, [[GATE_UP, 1, 0.1]]), (CONFIG, [[WO, 1, 0.1]])):
        assert share.read(_run(cfg, calls)) is None
        assert roof.read(_run(cfg, calls)) is None


def test_expert_rows_per_step_reads_the_counter():
    rows = bench.load_metric("expert_rows_per_step")
    # 50 dispatches of 4 steps, 26 expert layers, 8 held experts, 6 rows each
    run = _run(CONFIG, [], {"decode_steps": 10, "expert_rows": 100},
               {"decode_steps": 60, "expert_rows": 100 + 50 * 4 * 26 * 8 * 6})
    assert rows.read(run) == 6.0
    # a program without the counter (the parent's) reads nothing
    assert rows.read(_run(CONFIG, [], {"decode_steps": 1},
                          {"decode_steps": 9})) is None
    assert rows.read(_run(DENSE, [], {"decode_steps": 1, "expert_rows": 0},
                          {"decode_steps": 9, "expert_rows": 0})) is None
