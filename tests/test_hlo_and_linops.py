"""HLO scaled-cost analyzer + PDQ-int8 linop tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.launch.hlo_analysis import analyze
from repro.models.linops import (is_quantized, lin, lin_grouped,
                                 quantize_param_tree, quantize_weight)


def _count_pallas_calls(jaxpr) -> int:
    """Recursively count pallas_call eqns in a (Closed)Jaxpr."""
    if hasattr(jaxpr, "jaxpr"):              # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    n += _count_pallas_calls(sub)
    return n


def test_analyzer_scales_scan_bodies():
    """A scan of 10 matmuls must report ~10x one matmul's flops."""
    w = jnp.ones((64, 64))

    def one(x):
        return x @ w

    def scanned(x):
        def body(c, _):
            return c @ w, ()
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    x = jnp.ones((32, 64))
    f1 = analyze(jax.jit(one).lower(x).compile().as_text()).dot_flops
    f10 = analyze(jax.jit(scanned).lower(x).compile().as_text()).dot_flops
    assert f1 > 0
    ratio = f10 / f1
    assert 8.0 <= ratio <= 12.0, ratio


def test_analyzer_flops_value():
    x = jnp.ones((128, 256))
    w = jnp.ones((256, 512))
    f = analyze(jax.jit(lambda a, b: a @ b).lower(x, w).compile().as_text())
    want = 2 * 128 * 256 * 512
    assert abs(f.dot_flops - want) / want < 0.05


def test_quantize_weight_record_and_lin():
    key = jax.random.PRNGKey(0)
    w = 0.1 * jax.random.normal(key, (128, 64))
    rec = quantize_weight(w)
    assert is_quantized(rec)
    assert rec["q"].dtype == jnp.int8
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 128))
    y_fp = lin(x, w)
    y_q = lin(x, rec)
    rel = float(jnp.abs(y_q - y_fp).mean() / jnp.abs(y_fp).mean())
    assert rel < 0.05, rel


def test_lin_quantized_is_one_prologue_one_matmul():
    """The fused serving path must trace to EXACTLY two kernels: the pdq
    prologue and the W8A8 matmul - no separate amax / quantize / act_stats
    launches and no requant->dequant pair on the output."""
    rec = quantize_weight(0.1 * jax.random.normal(jax.random.PRNGKey(0), (128, 128)))
    x = jnp.ones((8, 128))
    ops.set_impl("kernel")
    try:
        jaxpr = jax.make_jaxpr(lambda t: lin(t, rec))(x)
    finally:
        ops.set_impl("auto")
    n = _count_pallas_calls(jaxpr)
    assert n == 2, f"expected prologue + matmul, traced {n} pallas_calls"


def test_quantize_param_tree_selects_matrices_only():
    # "blocks" leaves carry a leading layer axis (the scanned layer stack)
    params = {"attn": {"wq": jnp.ones((32, 32)), "norm": jnp.ones((32,))},
              "embed": {"embedding": jnp.ones((100, 32))},
              "moe": {"we_gate": jnp.ones((4, 32, 16))},
              "blocks": {"we_gate": jnp.ones((2, 4, 32, 16)),
                         "wo": jnp.ones((2, 32, 16)),
                         "norm": jnp.ones((2, 32))}}
    out = quantize_param_tree(params)
    assert is_quantized(out["attn"]["wq"])
    assert not is_quantized(out["attn"]["norm"])
    assert not is_quantized(out["embed"]["embedding"])   # embeddings stay fp
    assert not is_quantized(out["moe"]["we_gate"])       # 3-D stacks stay fp
    assert not is_quantized(out["blocks"]["we_gate"])    # ... per layer too
    assert not is_quantized(out["blocks"]["norm"])
    # a stacked layer's matrices are quantized per layer, records restacked
    wo = out["blocks"]["wo"]
    assert is_quantized(wo) and wo["q"].shape == (2, 32, 16)
    assert wo["scale"].shape == (2, 16) and wo["mu_w"].shape == (2,)


def test_quantize_param_tree_groups_sibling_sets():
    """wq/wk/wv (and w_gate/w_up) collapse to ONE grouped record; each
    sibling key holds a segment view so the tree structure is unchanged."""
    key = jax.random.PRNGKey(0)
    d = 128

    def w(i, n):
        return 0.1 * jax.random.normal(jax.random.fold_in(key, i), (d, n))

    params = {"attn": {"wq": w(0, 128), "wk": w(1, 64), "wv": w(2, 64),
                       "wo": w(3, d)},
              "ffn": {"w_gate": w(4, 256), "w_up": w(5, 256),
                      "w_down": jnp.transpose(w(6, 256))},
              "cross": {"wq": w(7, 128), "wk": w(8, 64), "wv": w(9, 64),
                        "wo": w(10, d)}}
    out = quantize_param_tree(params)
    # siblings share one group record, in declaration order
    for k in ("wq", "wk", "wv"):
        assert is_quantized(out["attn"][k]) and "group" in out["attn"][k]
    segs = out["attn"]["wq"]["group"]["segs"]
    assert segs.sizes == (128, 64, 64)
    assert all(out["attn"][k]["group"]["segs"] == segs
               for k in ("wq", "wk", "wv"))
    assert [out["attn"][k]["seg"].index for k in ("wq", "wk", "wv")] == [0, 1, 2]
    # non-sibling leaves stay per-projection records
    assert "q" in out["attn"]["wo"] and "q" in out["ffn"]["w_down"]
    assert out["ffn"]["w_gate"]["group"]["segs"].sizes == (256, 256)
    # cross-attention: wk/wv read the encoder memory, wq the decoder stream
    assert out["cross"]["wk"]["group"]["segs"].sizes == (64, 64)
    assert "q" in out["cross"]["wq"]
    # different layers' groups are never interchangeable
    assert out["attn"]["wq"]["group"]["segs"] != out["ffn"]["w_gate"]["group"]["segs"]
    # a segment view still answers plain lin(), matching the ungrouped record
    x = jax.random.normal(jax.random.fold_in(key, 42), (4, d))
    y_view = lin(x, out["attn"]["wk"])
    y_rec = lin(x, quantize_weight(params["attn"]["wk"]))
    np.testing.assert_allclose(np.asarray(y_view), np.asarray(y_rec),
                               rtol=1e-5, atol=1e-5)


def test_lin_grouped_falls_back_per_projection():
    """Any unquantized / ungrouped member routes through per-projection lin
    with identical numerics."""
    key = jax.random.PRNGKey(1)
    w1 = 0.1 * jax.random.normal(key, (64, 32))
    w2 = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (64, 48))
    x = jax.random.normal(jax.random.fold_in(key, 2), (4, 64))
    # fp weights: exact fallback
    y1, y2 = lin_grouped(x, (w1, w2))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(x @ w1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(x @ w2), rtol=1e-6)
    # mixed quantized/fp: still per-projection
    r1 = quantize_weight(w1)
    y1q, y2f = lin_grouped(x, (r1, w2))
    np.testing.assert_allclose(np.asarray(y1q), np.asarray(lin(x, r1)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(y2f), np.asarray(x @ w2), rtol=1e-6)


def _decode_block_census(quant_kv: str) -> int:
    """Trace a full quantized GQA decode block (attn norm -> QKV -> attend ->
    wo, ffn norm -> gate/up -> down) under kernel impl and count
    pallas_calls."""
    from repro.models.attention import AttnDims, gqa_apply, gqa_init, init_cache
    from repro.models.layers import mlp_apply, mlp_init, rms_norm

    dims = AttnDims(d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
                    quant_kv=quant_kv)
    key = jax.random.PRNGKey(0)
    params = {"attn": gqa_init(key, dims, jnp.float32),
              "attn_norm": jnp.zeros((256,)),
              "ffn_norm": jnp.zeros((256,)),
              "ffn": mlp_init(jax.random.fold_in(key, 1), 256, 512, jnp.float32)}
    qp = quantize_param_tree(params)
    cache = init_cache(dims, 8, 64, jnp.float32)

    def block(p, h, cache, positions):
        a, cache = gqa_apply(p["attn"], dims, rms_norm(h, p["attn_norm"]),
                             positions, mode="decode", cache=cache)
        h = h + a
        return h + mlp_apply(p["ffn"], rms_norm(h, p["ffn_norm"])), cache

    h = jnp.ones((8, 1, 256))
    pos = jnp.zeros((8, 1), jnp.int32)
    ops.set_impl("kernel")
    try:
        jaxpr = jax.make_jaxpr(block)(qp, h, cache, pos)
    finally:
        ops.set_impl("auto")
    return _count_pallas_calls(jaxpr)


def test_quantized_gqa_decode_block_is_seven_kernels():
    """A full quantized GQA decode block (fp KV cache) must trace to EXACTLY
    7 pallas_calls: one prologue + one wide matmul for each of the grouped
    QKV triple and the wo projection, plus the fused SwiGLU MLP triple
    (the gate/up matmul's epilogue computes silu(g)*u AND w_down's PDQ
    prologue, so no standalone prologue launch runs between the MLP
    matmuls).  A regression to per-projection dispatch would trace 14;
    losing the SwiGLU fusion regresses to 8 (tools/check_census.py pins
    the same table in the lint job)."""
    n = _decode_block_census("none")
    assert n == 7, f"expected 7 pallas_calls per quantized decode block, got {n}"


def test_quantized_gqa_decode_block_int8kv_is_seven_kernels():
    """The int8-KV decode block also traces to EXACTLY 7 pallas_calls: the
    flash-decode attend kernel's output stage emits the wo projection's
    PDQ prologue (decode_attend_i8kv_fused_p), so wo costs ONE W8A8
    matmul launch - QKV pair + fused attend + wo matmul + fused MLP
    triple.  Losing the attend fold regresses to 9 (attend + wo
    prologue + wo matmul)."""
    n = _decode_block_census("dynamic")
    assert n == 7, f"expected 7 pallas_calls per int8-KV decode block, got {n}"
