"""Operations and bytes at stablelm-1.6b widths, against hand values."""
from __future__ import annotations

import pytest

from chipbench import bench

STABLELM = {"hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 32, "intermediate_size": 5632,
            "num_hidden_layers": 24, "vocab_size": 100352}


def test_swiglu_flops_and_bytes_at_decode_width():
    rf = bench.load_roofline("swiglu_matmul")
    assert rf.flops(32, 2048, 11264) == 1_476_395_008
    # reads: x 65,536 + w 23,068,672 + scales, sums, intervals 112,896;
    # writes: y f32 1,441,792 + hsw_q 180,224 + row stats 384
    assert rf.bytes(32, 2048, 11264, out_bytes=4) == 24_869_504


@pytest.mark.parametrize("sig,want", [
    ("(f32[32,11264], s8[32,5632], f32[32,1], f32[32,1], f32[32,1]) "
     "custom-call(s8[32,2048] %x, s8[2048,11264] %w, f32[32,1] %s)",
     {"M": 32, "K": 2048, "N": 11264, "out_bytes": 4}),
    ("(bf16[2048,11264], s8[2048,5632], f32[2048,1], f32[2048,1], "
     "f32[2048,1]) custom-call(s8[2048,2048] %x, s8[2048,11264] %w)",
     {"M": 2048, "K": 2048, "N": 11264, "out_bytes": 2}),
    ("bf16[128,6144] custom-call(s8[128,2048] %x, s8[2048,6144] %w)", None),
    ("(s8[128,2048], f32[128,1], f32[128,1], f32[128,1]) "
     "custom-call(bf16[128,2048] %x)", None),
    ("(f32[32,11264], s8[32,5000], f32[32,1], f32[32,1], f32[32,1]) "
     "custom-call(s8[32,2048] %x, s8[2048,11264] %w)", None),
])
def test_swiglu_calls_are_told_by_signature(sig, want):
    assert bench.load_roofline("swiglu_matmul").match(sig) == want


def test_model_flops_at_stablelm_widths():
    mf = bench.load_model("dense")
    # layers: 24 x (4 x 2048^2 + 3 x 2048 x 5632) = 1,233,125,376 params;
    # head 2048 x 100,352 = 205,520,896
    assert mf.decode_flops(STABLELM, 100) == 2_896_953_344
    assert mf.prefill_flops(STABLELM, 10) == 25_084_362_752


def test_peaks_table_names_its_source():
    peaks = bench.load_peaks()
    assert peaks["TPU v5 lite"] == {"bf16_flops": 197e12, "int8_ops": 393e12,
                                    "hbm_bytes_per_s": 819e9,
                                    "hbm_bytes": 16e9}
