"""The DeepSeek-V2 family at a tiny size on the CPU: its weights are the
program's tree, the program served through ``build_engine`` (paged pool,
4-step decode) agrees with the family's float32 reference, with PDQ too,
and a whole harness run decides ``correct`` with it."""
from __future__ import annotations

import argparse
import dataclasses
import json

import numpy as np
import pytest

from chipbench import bench, reference, run, weights
from chipbench.tests import tiny

SEED = 2**31 + 7
# the published keys at small widths: 3 layers (one dense), 4 heads of MLA
# with a 32-wide latent and YaRN over 8 rope dims, 8 experts of which 2 are
# held (ids 2 and 3), top-2, 2 shared experts
TINY_DSV2 = {
    "name": "tiny-dsv2", "arch": "deepseek-v2-lite", "family": "deepseek_v2",
    "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "kv_lora_rank": 32,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 2, "n_routed_experts_published": 8,
    "held_expert_offset": 2, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax",
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 16,
    "vocab_size": 512, "torch_dtype": "float32",
    "reduced": ["n_routed_experts"],
    "serve": {"slots": 2, "max_len": 64, "buckets": [16, 32],
              "decode_steps": 4, "paged": True, "page_size": 16,
              "prefix_sharing": True, "quantize_weights": False,
              "kv": "none", "max_pending": 8},
    "peak": "bf16_flops",
    # float32 on both sides: the program and the reference agree to ~1e-5
    "check": {"gap_max": 1e-3, "control_bits": 4},
}
LENS = (5, 11, 16, 20)


@pytest.fixture(scope="module")
def family():
    return bench.load_model("deepseek_v2")


@pytest.fixture(scope="module")
def params(family):
    return family.make_params(TINY_DSV2, SEED)


def _serve(cfg, params, quantize):
    from repro.serve import Request, ServeConfig, build_engine
    s = TINY_DSV2["serve"]
    eng = build_engine(ServeConfig(
        slots=s["slots"], max_len=s["max_len"], buckets=tuple(s["buckets"]),
        decode_steps=s["decode_steps"], paged=True, page_size=s["page_size"],
        quantize_weights=quantize), cfg=cfg, params=params)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(1, 512, n).astype(np.int32),
                    max_new=24) for i, n in enumerate(LENS)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    return eng, [{"prompt": r.prompt.tolist(), "tokens": list(r.generated)}
                 for r in reqs]


def test_the_program_takes_the_family_layout(family, params):
    cfg = family.arch_config(TINY_DSV2)
    assert (cfg.moe.n_experts, cfg.moe.n_held, cfg.moe.held_offset) == (8, 2, 2)
    assert cfg.mla.q_lora is None and cfg.head == ("global_dense",)
    ffn = params["blocks"][0]["ffn"]
    assert ffn["router"].shape == (2, 64, 8)
    assert ffn["we_gate"].shape == (2, 2, 64, 32)
    weights.check_layout(params, cfg)
    with pytest.raises(RuntimeError, match="layout differs"):
        weights.check_layout(params, dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_held=4)))


@pytest.mark.parametrize("quantize", [False, True], ids=["float32", "pdq"])
def test_served_tokens_agree_with_the_reference(family, params, quantize):
    """Prefill, then 4-step decode through the paged pool, against the
    float32 reference, teacher-forced over every served token.  Float32:
    the served token is the reference's argmax to rounding (gap < 1e-3).
    PDQ: W8A8 on every projection and expert moves the logits by its
    rounding, so the served token may lose to the argmax by a little (the
    mean gap stays far under the int4 control's); the stacks run as
    int8 records."""
    cfg = family.arch_config(TINY_DSV2)
    eng, seqs = _serve(cfg, params, quantize)
    res = reference.compare(family, params, TINY_DSV2, seqs, control=True)
    assert res["n_tokens"] == 24 * len(LENS)
    assert eng.stats["expert_rows"] > 0
    if not quantize:
        assert res["gap_max"] < 1e-3
        assert res["argmax_agree"] == 1.0
        return
    ffn = eng.params["blocks"][0]["ffn"]
    assert ffn["we_gate_up"]["q"].dtype == np.int8
    assert ffn["we_down"]["q"].dtype == np.int8
    assert "we_gate" not in ffn and "we_up" not in ffn
    assert res["gap_mean"] < 0.25 * res["control_gap_mean"]


def test_a_harness_run_decides_correct_with_the_family(tmp_path, capsys):
    root = tiny.make_root(tmp_path)
    tiny.write_json(root / "chipbench" / "configs" / "tiny-dsv2.json",
                    TINY_DSV2)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-dsv2", "source": TINY_DSV2["source"],
                         "file": "chipbench/configs/tiny-dsv2.json",
                         "reduced": TINY_DSV2["reduced"], "why": "test"})
    b["workloads"].append({"name": "tiny-dsv2.decode", "config": "tiny-dsv2",
                           "traffic": "tiny_closed", "chips": 1,
                           "why": "test"})
    b["per_layer"] += [
        {"name": "expert_rows_per_step", "unit": "rows", "better": "higher",
         "source": "program_counter", "layer": "experts",
         "moves": "output_tok_s", "workloads": ["tiny-dsv2.decode"]},
        {"name": "step_mfu", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "model step",
         "moves": "output_tok_s", "workloads": ["tiny-dsv2.decode"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    def go(control):
        args = argparse.Namespace(
            workload="tiny-dsv2.decode", seed=SEED, seconds=2.0, trace=1,
            control=control, root=str(root),
            server_argv=tiny.server_argv("none"))
        rc = run.run(args)
        out = capsys.readouterr()
        assert rc == 0, out.err[-3000:]
        return json.loads(out.out.strip().splitlines()[-1])

    res = go(0)
    assert res["correct"] is True
    assert res["metrics"]["step_mfu"]["value"] > 0
    # 2 slots x top-2 of 8 experts, 2 held: half a routed row per held
    # expert, step and expert layer on average
    assert 0 < res["metrics"]["expert_rows_per_step"]["value"] <= 2
    assert go(1)["correct"] is False


def test_flops_count_the_share(family):
    """Per token: the attention and dense FFN whole, the router, the shared
    experts, and top-k x held / published of one expert."""
    d, H = 64, 4
    attn = d * H * 24 + d * 40 + 32 * H * 32 + H * 16 * d
    moe = d * 8 + 3 * d * 64 + (2 * 2 / 8) * 3 * d * 32
    params = (attn + 3 * d * 128) + 2 * (attn + moe)
    pair = 2 * 3 * H * (16 + 8 + 16)
    assert family.decode_flops(TINY_DSV2, 10) == pytest.approx(
        2 * params + 10 * pair + 2 * d * 512)
    assert family.prefill_flops(TINY_DSV2, 4) == pytest.approx(
        2 * params * 4 + pair * 10 + 2 * d * 512)


def test_held_expert_faults_move_the_logits_past_rounding(family, params):
    """The weights make the held experts weigh in the output: held experts
    left out, taken at the wrong ids or with renormalised gates move the
    reference logits several times as far as rounding every projection to
    8 bits does, so the comparison that decides ``correct`` sees them."""
    import jax
    import jax.numpy as jnp
    m = family.dims(TINY_DSV2)
    tokens = jnp.asarray(np.random.default_rng(3).integers(1, 512, (2, 24)))

    def logits(params=params, off=m["off"], norm_topk=m["norm_topk"],
               bits=0):
        f = family._logits(family._key(m), off, m["k"], norm_topk,
                           m["route_scale"], m["theta"], m["eps"], m["yarn"],
                           bits)
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(f)(params, tokens))

    want = logits()
    rounding = np.abs(logits(bits=8) - want).mean()
    left_out = jax.tree.map(lambda a: a, params)
    ffn = dict(left_out["blocks"][0]["ffn"])
    ffn["we_down"] = jnp.zeros_like(ffn["we_down"])
    left_out["blocks"] = ({**left_out["blocks"][0], "ffn": ffn},)
    for fault in (logits(params=left_out), logits(off=m["off"] + 2),
                  logits(norm_topk=True)):
        assert np.abs(fault - want).mean() > 4 * rounding
