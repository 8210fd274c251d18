"""Model families are found by file: the dense family makes the weights and
the reference logits it always made, and a second architecture runs end to
end with nothing but a family module and a configuration file of its own."""
from __future__ import annotations

import argparse
import hashlib
import json

import numpy as np
import pytest

from chipbench import bench, run
from chipbench.tests import tiny

SEED = 1234
TOKENS = (np.arange(24, dtype=np.int32).reshape(2, 12) * 37 + 5) % 512
# the tiny configuration's weights and reference logits, as the harness made
# them before the dense family moved into models/dense.py
WEIGHTS_SHA256 = "1fe15abd9d2a9aeb7ccce46aa174ec1266fe574194eaf289229e0dfc9f433f2e"
LOGITS = {
    0: {"sum": 29.160121986710237, "sum_sq": 317.30339819818164,
        "argmax": [[318, 502, 304, 267, 227, 267, 267, 227, 267, 140, 140, 287],
                   [505, 252, 181, 295, 495, 365, 140, 193, 62, 219, 477, 306]],
        "last": [0.08375775814056396, -0.06380938738584518,
                 0.08021952211856842, 0.31871291995048523]},
    8: {"sum": 28.084372636576973, "sum_sq": 317.4306120119517,
        "argmax": [[318, 502, 304, 267, 227, 267, 267, 227, 267, 140, 140, 287],
                   [505, 252, 181, 295, 495, 365, 140, 382, 62, 219, 477, 306]],
        "last": [0.0825209692120552, -0.06335192918777466,
                 0.07926272600889206, 0.3209654688835144]},
}


@pytest.fixture(scope="module")
def dense_params():
    return bench.load_model("dense").make_params(tiny.TINY_CONFIG, SEED)


def test_a_configuration_without_a_family_is_dense():
    assert bench.family(tiny.TINY_CONFIG) == "dense"
    assert bench.family(dict(tiny.TINY_CONFIG, family="moe")) == "moe"
    for c in bench.load_benchmark()["configs"]:
        with open(bench.ROOT / c["file"]) as f:
            assert bench.family(json.load(f)) == "dense"
    assert bench.load_model("dense") is bench.load_model("dense")
    with pytest.raises(FileNotFoundError):
        bench.load_model("no_such_family")


def test_dense_weights_are_pinned(dense_params):
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(dense_params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256


@pytest.mark.parametrize("bits", [0, 8])
def test_dense_reference_logits_are_pinned(dense_params, bits):
    import jax
    logits = bench.load_model("dense").reference_logits(tiny.TINY_CONFIG, bits)
    assert logits is bench.load_model("dense").reference_logits(
        tiny.TINY_CONFIG, bits)
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(jax.jit(logits)(dense_params, TOKENS))
    want = LOGITS[bits]
    assert lg.shape == (2, 12, 512)
    assert lg.argmax(-1).tolist() == want["argmax"]
    assert float(lg.astype(np.float64).sum()) == pytest.approx(want["sum"],
                                                               rel=1e-6)
    assert float((lg.astype(np.float64) ** 2).sum()) == pytest.approx(
        want["sum_sq"], rel=1e-6)
    assert lg[1, -1, :4].tolist() == pytest.approx(want["last"], rel=1e-5,
                                                   abs=1e-7)


def test_the_program_takes_the_dense_layout(dense_params):
    import dataclasses

    from chipbench import weights
    cfg = bench.load_model("dense").arch_config(tiny.TINY_CONFIG)
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab) == (2, 64, 128, 512)
    weights.check_layout(dense_params, cfg)
    with pytest.raises(RuntimeError, match="layout differs"):
        weights.check_layout(dense_params,
                             dataclasses.replace(cfg, d_ff=256))


# A second architecture, written here and nowhere else: the dense block with
# the embedding scaled by sqrt(d_model) and the logits soft-capped, as Gemma
# 2's block does.  Its weights, FLOPs and tree are the dense family's.
SCALED_FAMILY = '''
import dataclasses
import functools
from pathlib import Path

from chipbench import bench
from chipbench.reference import fq

dense = bench.load_model("dense", Path(__file__).resolve().parents[2])
CAP = 30.0

make_params = dense.make_params
prefill_flops = dense.prefill_flops
decode_flops = dense.decode_flops


def arch_config(config):
    return dataclasses.replace(dense.arch_config(config), embed_scale=True,
                               logit_softcap=CAP)


def reference_logits(config, bits):
    m = dense.dims(config)
    return _logits(m["L"], m["H"], m["Hkv"], m["Dh"], m["d"], m["theta"],
                   m["eps"], int(bits))


@functools.lru_cache(maxsize=None)
def _logits(L, H, Hkv, Dh, d, theta, eps, bits):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def lin(x, w):
        w = w.astype(jnp.float32)
        if bits:
            x, w = fq(x, bits, -1), fq(w, bits, 0)
        return jnp.einsum("bsk,kn->bsn", x, w, precision=hi)

    def rms(x, g):
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                * (1.0 + g.astype(jnp.float32)))

    def rope(x, pos):
        f = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
        ang = pos[:, None].astype(jnp.float32) * f
        sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def logits(params, tokens):
        B, S = tokens.shape
        pos = jnp.arange(S)
        emb = params["embed"]["embedding"].astype(jnp.float32)
        h = jnp.take(emb, tokens, axis=0) * d ** 0.5
        causal = pos[:, None] >= pos[None, :]

        def layer(h, p):
            a, f = p["attn"], p["ffn"]
            x = rms(h, p["attn_norm"])
            q = rope(lin(x, a["wq"]).reshape(B, S, H, Dh), pos)
            k = rope(lin(x, a["wk"]).reshape(B, S, Hkv, Dh), pos)
            v = lin(x, a["wv"]).reshape(B, S, Hkv, Dh)
            if bits:
                k, v = fq(k, bits, -1), fq(v, bits, -1)
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) * Dh ** -0.5
            s = jnp.where(causal, s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                           precision=hi)
            h = h + lin(o.reshape(B, S, H * Dh), a["wo"])
            x = rms(h, p["ffn_norm"])
            return h + lin(jax.nn.silu(lin(x, f["w_gate"])) * lin(x, f["w_up"]),
                           f["w_down"]), None

        h, _ = jax.lax.scan(layer, h, params["blocks"][0])
        h = rms(h, params["final_norm"])
        lg = jnp.einsum("bsd,vd->bsv", h, emb, precision=hi)
        return CAP * jnp.tanh(lg / CAP)

    return logits
'''


def test_a_new_family_needs_only_new_files(tmp_path, capsys):
    root = tiny.make_root(tmp_path)
    pkg = root / "chipbench"
    before = {p: p.read_bytes() for p in pkg.rglob("*") if p.is_file()}
    (pkg / "models" / "scaled.py").write_text(SCALED_FAMILY)
    # the scaled embedding separates the logits: rounding to 8 bits flips
    # no greedy token at this size, rounding to 4 does
    cfg = dict(tiny.TINY_CONFIG, name="tiny-scaled", family="scaled",
               check=dict(tiny.TINY_CONFIG["check"], control_bits=4))
    tiny.write_json(pkg / "configs" / "tiny-scaled.json", cfg)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-scaled", "source": cfg["source"],
                         "file": "chipbench/configs/tiny-scaled.json",
                         "reduced": cfg["reduced"], "why": "test"})
    b["workloads"].append({"name": "tiny-scaled.decode",
                           "config": "tiny-scaled", "traffic": "tiny_closed",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "step_mfu", "unit": "%",
                           "better": "higher", "source": "host_clock",
                           "layer": "model step", "moves": "output_tok_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert all(p.read_bytes() == data for p, data in before.items())

    def go(control):
        args = argparse.Namespace(
            workload="tiny-scaled.decode", seed=2**31 + 29, seconds=2.0,
            trace=1, control=control, root=str(root),
            server_argv=tiny.server_argv("none"))
        rc = run.run(args)
        out = capsys.readouterr()
        assert rc == 0, out.err[-3000:]
        return json.loads(out.out.strip().splitlines()[-1])

    res = go(0)
    assert res["correct"] is True
    assert res["checks"]["gap_max"]["value"] <= 1e-3
    assert res["metrics"]["step_mfu"]["value"] > 0
    # the control, the same family's forward rounded to 8 bits, fails
    assert go(1)["correct"] is False


def test_the_dense_reference_does_not_pass_the_new_family(tmp_path):
    """The family's own forward decides: the dense one, on the scaled
    family's greedy tokens, reads gaps far beyond the limit."""
    import jax
    import jax.numpy as jnp
    root = tiny.make_root(tmp_path)
    (root / "chipbench" / "models" / "scaled.py").write_text(SCALED_FAMILY)
    scaled = bench.load_model("scaled", root)
    dense = bench.load_model("dense", root)
    params = dense.make_params(tiny.TINY_CONFIG, SEED)
    with jax.default_matmul_precision("highest"):
        served = jnp.argmax(scaled.reference_logits(tiny.TINY_CONFIG, 0)(
            params, TOKENS), -1)
        lg = dense.reference_logits(tiny.TINY_CONFIG, 0)(params, TOKENS)
    gap = jnp.max(lg, -1) - jnp.take_along_axis(lg, served[..., None], -1)[..., 0]
    assert float(gap.max()) > 1e-3
