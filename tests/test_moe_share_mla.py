"""DeepSeek-V2-Lite's mechanisms in the program, at tiny sizes on the CPU:
an expert layer that holds a share of the experts, ungated top-k, YaRN
rotary, MLA without query compression, PDQ over the expert stacks - and
guards that the code they share with the dense model computes what it
computed before."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels import ops
from repro.models import build_model
from repro.models.layers import (apply_rope, rope_freqs, yarn_freqs,
                                 yarn_mscale, yarn_ramp_bounds,
                                 yarn_softmax_scale)
from repro.models.linops import is_quantized, quantize_param_tree
from repro.models.moe import (EXPERT_KERNEL, MoEConfig, moe_ffn_dense_masked,
                              moe_init, route)
from repro.models.transformer import _apply_ffn

# digests of the dense model's quantized tree and of plain rotary, as the
# program computed them before the expert stacks and YaRN came in
STABLELM_PDQ_TREE = {
    "float32": "300ecdc0212fe553ba32cabe4ad725602bb6c135b689bb5e9089dc90f1c9beb6",
    "bfloat16": "ae73a826648c8428e0097bc4f9f97aa22cea44a12ab3a870da803f1fc5bc7486"}
PLAIN_ROPE = {
    "float32": "a7a547cc2d3b44d8b07c8c9aa7fa8945a368a6eff86871741bff34af587d06c2",
    "bfloat16": "57e8213e4804b88edcc4ec1cf5865eb5393b36b855211f54e75226689028b095"}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dense_pdq_tree_is_unchanged(dtype):
    cfg = reduced_config("stablelm-1.6b", dtype=dtype)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    assert _digest(quantize_param_tree(params)) == STABLELM_PDQ_TREE[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_plain_rotary_is_unchanged(dtype):
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 37, 4, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(37)[None] * 29, (2, 37))
    y = apply_rope(x.astype(dtype), pos, 10000.0).astype(jnp.float32)
    assert _digest(y) == PLAIN_ROPE[dtype]


def test_yarn_constants_at_the_published_values():
    y = get_config("deepseek-v2-lite").yarn
    assert yarn_ramp_bounds(64, 10000.0, y) == (10, 23)
    assert yarn_mscale(40, 0.707) ** 2 == pytest.approx(1.589626, abs=1e-6)
    assert yarn_softmax_scale(192, y) == pytest.approx(0.114721, abs=1e-6)
    # cos/sin keep their scale when mscale == mscale_all_dim, and the
    # lowest pairs keep their plain frequency, the highest are divided by 40
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 1, 64))
    pos = jnp.arange(3)[None]
    assert jnp.allclose(jnp.linalg.norm(apply_rope(x, pos, 1e4, y), axis=-1),
                        jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    f, f0 = yarn_freqs(64, 1e4, y), rope_freqs(64, 1e4)
    assert jnp.allclose(f[:10], f0[:10]) and jnp.allclose(f[23:], f0[23:] / 40)


def test_mla_is_refused_an_int8_kv_cache():
    with pytest.raises(ValueError, match="quant_kv"):
        reduced_config("deepseek-v2-lite", quant_kv="dynamic")


def test_mla_without_query_compression_has_one_wq():
    cfg = reduced_config("deepseek-v2-lite")
    attn = jax.eval_shape(build_model(cfg).init,
                          jax.random.PRNGKey(0))["head"][0]["attn"]
    assert "wq" in attn and "wq_a" not in attn and "q_norm" not in attn
    q = jax.eval_shape(quantize_param_tree,
                       jax.eval_shape(build_model(cfg).init,
                                      jax.random.PRNGKey(0)))
    a = q["head"][0]["attn"]
    # wq and wkv_a share the normed input: one grouped record
    segs = a["wq"]["group"]["segs"]
    assert segs.names == ("wq", "wkv_a") and a["wkv_a"]["group"]["segs"] == segs


def _moe(n_held=None, offset=0, norm_topk=False, top_k=2):
    return MoEConfig(n_experts=8, top_k=top_k, d_ff_expert=16,
                     norm_topk=norm_topk, n_held=n_held, held_offset=offset)


def test_top_k_gates_unnormalised():
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 32))
    p = moe_init(jax.random.PRNGKey(0), 32, _moe(), jnp.float32)
    g, _, _ = route(x, p["router"], _moe(norm_topk=False))
    gn, _, _ = route(x, p["router"], _moe(norm_topk=True))
    probs = jax.nn.softmax(x @ p["router"], -1)
    assert jnp.allclose(g, jax.lax.top_k(probs, 2)[0], atol=1e-6)
    assert float(g.sum(-1).max()) < 0.99
    assert jnp.allclose(gn.sum(-1), 1.0, atol=1e-6)


def _share(p, offset, n):
    out = dict(p)
    for k in ("we_gate", "we_up", "we_down"):
        out[k] = p[k][offset:offset + n]
    return out


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_shares_add_up_to_the_uncut_layer(mode):
    """The routed parts of the four 2-expert shares, plus the shared
    experts once, are the whole layer of 8 experts."""
    cfg = reduced_config("deepseek-v2-lite")
    full = build_model(cfg).init(jax.random.PRNGKey(0))["blocks"][0]["ffn"]
    full = jax.tree.map(lambda a: a[0], full)          # the first MoE layer
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model))
    want, _ = _apply_ffn(full, cfg, "global", h, "decode")
    shared = _apply_ffn(dict(full, we_down=0 * full["we_down"]), cfg,
                        "global", h, "decode")[0]
    total = -3 * shared
    for off in (0, 2, 4, 6):
        scfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_held=2, held_offset=off))
        total = total + _apply_ffn(_share(full, off, 2), scfg, "global", h,
                                   mode)[0]
    assert jnp.allclose(total, want, atol=1e-5)


@pytest.mark.parametrize("quantize", [False, True], ids=["float32", "pdq"])
def test_no_held_assignment_is_dropped(quantize):
    """All 64 tokens route to held expert 2: every one gets its output (no
    capacity), the same as the expert run on each token alone."""
    cfg = _moe(n_held=2, offset=2, top_k=1)
    p = moe_init(jax.random.PRNGKey(0), 32, cfg, jnp.float32)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 2].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (64, 32)))
    g, ids, _ = route(x, p["router"], cfg)
    assert bool(jnp.all(ids == 2))
    want = g * (jax.nn.silu(x @ p["we_gate"][0]) * (x @ p["we_up"][0])
                ) @ p["we_down"][0]
    if quantize:
        p = quantize_param_tree(p)
        assert is_quantized(p["we_down"])
    with ops.pdq_telemetry(True) as col:
        y, _ = moe_ffn_dense_masked(p, x, cfg, count_rows=True)
        rows = col.summary()
    assert rows.shape == (ops.PDQ_TEL_WIDTH,)
    assert float(rows[ops.TEL_FIELDS.index("expert_rows")]) == 64
    tol = 0.05 * float(jnp.abs(want).max()) if quantize else 1e-5
    assert float(jnp.abs(y - want).max()) < tol
    assert bool(jnp.all(jnp.abs(y).max(axis=-1) > 0))


def test_expert_stacks_are_int8_records_run_by_the_expert_kernel():
    cfg = reduced_config("deepseek-v2-lite")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, n_held=2))
    m = build_model(cfg)
    q = quantize_param_tree(m.init(jax.random.PRNGKey(0)))
    f = q["blocks"][0]["ffn"]
    assert set(f) == {"router", "shared", "we_gate_up", "we_down"}
    assert f["we_gate_up"]["q"].dtype == jnp.int8
    assert f["we_gate_up"]["q"].shape == (2, 64, 4 * 128)  # 2 x (gate, up)
    assert f["we_down"]["q"].shape == (2, 2 * 32, 64)      # stacked along K
    assert f["router"].dtype == jnp.float32                 # not quantized
    caches = m.init_caches(2, 16)
    tok = jnp.zeros((2, 1), jnp.int32)
    ops.set_impl("kernel")
    try:
        jaxpr = jax.make_jaxpr(m.decode_step)(q, caches, tok, tok)
    finally:
        ops.set_impl("auto")
    names = [str(e.params.get("name")) for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    # per MoE layer: the grouped gate/up and the stacked down matmul
    assert names.count(EXPERT_KERNEL) == 2


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)
