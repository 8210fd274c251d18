"""Per-kernel correctness: Pallas (interpret=True) vs the pure-jnp oracle.

Shapes and dtypes are swept with hypothesis; every kernel must match ref.py
to float32 tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean envs: deterministic shim, see requirements-dev.txt
    from _hypo_compat import given, settings, strategies as st

from repro.kernels import kv_cache, ops, ref
from repro.kernels.act_stats import act_stats_p
from repro.kernels.kv_cache import decode_attend_i8kv_fused_p, decode_attend_i8kv_p
from repro.kernels.pdq_prologue import pdq_prologue_p
from repro.kernels.quantize import dequantize_p, quantize_p
from repro.kernels.w8a8_matmul import w8a8_matmul_p, w8a8_swiglu_matmul_p
from repro.models.linops import group_quantize_weights, quantize_weight

jax.config.update("jax_enable_x64", False)

HYPO = dict(max_examples=8, deadline=None, derandomize=True)


def _rand_i8(key, shape):
    return jax.random.randint(key, shape, -128, 128, dtype=jnp.int32).astype(jnp.int8)


# ---------------------------------------------------------------------------
# w8a8 matmul
# ---------------------------------------------------------------------------


@settings(**HYPO)
@given(
    m=st.sampled_from([128, 256]),
    n=st.sampled_from([128, 384]),
    k=st.sampled_from([128, 256]),
    requant=st.booleans(),
    per_channel=st.booleans(),
)
def test_w8a8_matmul_kernel_vs_ref(m, n, k, requant, per_channel):
    keys = jax.random.split(jax.random.PRNGKey(m * n + k), 4)
    x_q = _rand_i8(keys[0], (m, k))
    w_q = _rand_i8(keys[1], (k, n))
    s_x = jax.random.uniform(keys[2], (m, 1), minval=0.01, maxval=0.1)
    z_x = jax.random.randint(keys[3], (m, 1), -10, 10, dtype=jnp.int32)
    s_w = (jax.random.uniform(keys[2], (1, n), minval=0.001, maxval=0.01)
           if per_channel else jnp.full((1, n), 0.005))
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=0, keepdims=True)
    s_out = jnp.full((m, 1), 0.7, jnp.float32)
    z_out = jnp.full((m, 1), 3, jnp.int32)

    got = w8a8_matmul_p(x_q, w_q, s_x, z_x, s_w, colsum, s_out, z_out,
                        requant=requant, interpret=True)
    want = ref.w8a8_matmul_ref(x_q, w_q, s_x, z_x, s_w,
                               s_out if requant else None, z_out if requant else None)
    if requant:
        # rounding ties may differ by 1 ulp of the int grid
        assert np.abs(np.asarray(got, np.int32) - np.asarray(want, np.int32)).max() <= 1
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_w8a8_matmul_ops_padding_and_lead_dims():
    ops.set_impl("kernel")
    try:
        key = jax.random.PRNGKey(0)
        x_q = _rand_i8(key, (2, 3, 70))            # ragged K, leading dims
        w_q = _rand_i8(jax.random.PRNGKey(1), (70, 50))
        y = ops.w8a8_matmul(x_q, w_q, 0.05, 2, jnp.full((50,), 0.01))
        want = ref.w8a8_matmul_ref(
            x_q.reshape(6, 70), w_q, jnp.full((6, 1), 0.05), jnp.full((6, 1), 2),
            jnp.full((1, 50), 0.01))
        np.testing.assert_allclose(y.reshape(6, 50), want, rtol=1e-5)
    finally:
        ops.set_impl("auto")


# ---------------------------------------------------------------------------
# act_stats
# ---------------------------------------------------------------------------


@settings(**HYPO)
@given(
    m=st.sampled_from([256, 512]),
    k=st.sampled_from([512, 1024]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_act_stats_kernel_vs_ref(m, k, dtype):
    x = jax.random.normal(jax.random.PRNGKey(m + k), (m, k)).astype(dtype)
    s1, s2 = act_stats_p(x, interpret=True)
    w1, w2 = ref.act_stats_ref(x)
    np.testing.assert_allclose(s1, w1, rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-2)
    np.testing.assert_allclose(s2, w2, rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-2)


def test_act_stats_ops_gamma_stride():
    ops.set_impl("kernel")
    try:
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 100, 33))
        s1, s2 = ops.act_stats(x, gamma=4)
        w1, w2 = ref.act_stats_ref(x[:, ::4].reshape(-1, 33))
        np.testing.assert_allclose(s1.reshape(-1), w1, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s2.reshape(-1), w2, rtol=1e-4, atol=1e-4)
    finally:
        ops.set_impl("auto")


# ---------------------------------------------------------------------------
# quantize / dequantize
# ---------------------------------------------------------------------------


@settings(**HYPO)
@given(
    m=st.sampled_from([256, 300]),
    n=st.sampled_from([256, 290]),
    per_channel=st.booleans(),
)
def test_quantize_roundtrip_kernel_vs_ref(m, n, per_channel):
    x = 4.0 * jax.random.normal(jax.random.PRNGKey(m * n), (m, n))
    if per_channel:
        s = jnp.linspace(0.01, 0.2, n).reshape(1, n)
        z = jnp.zeros((1, n), jnp.int32)
    else:
        s = jnp.full((m, 1), 0.05)
        z = jnp.full((m, 1), 4, jnp.int32)
    mp, np_ = -(-m // 256) * 256, -(-n // 256) * 256
    xp = jnp.pad(x, ((0, mp - m), (0, np_ - n)))
    sp = jnp.pad(s, ((0, 0), (0, np_ - n)), constant_values=1.0) if per_channel \
        else jnp.pad(s, ((0, mp - m), (0, 0)), constant_values=1.0)
    zp = jnp.pad(z, ((0, 0), (0, np_ - n))) if per_channel \
        else jnp.pad(z, ((0, mp - m), (0, 0)))
    q = quantize_p(xp, sp, zp, interpret=True)[:m, :n]
    want = ref.quantize_ref(x, s, z)
    assert np.abs(np.asarray(q, np.int32) - np.asarray(want, np.int32)).max() <= 1
    y = dequantize_p(jnp.pad(want, ((0, mp - m), (0, np_ - n))), sp, zp,
                     interpret=True)[:m, :n]
    np.testing.assert_allclose(y, ref.dequantize_ref(want, s, z), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# int8-KV flash decode
# ---------------------------------------------------------------------------


@settings(**HYPO)
@given(
    s=st.sampled_from([256, 512]),
    hkv=st.sampled_from([1, 2]),
    g=st.sampled_from([1, 4]),
    dh=st.sampled_from([64, 128]),
    frac=st.sampled_from([0.4, 1.0]),
)
def test_decode_i8kv_kernel_vs_ref(s, hkv, g, dh, frac):
    keys = jax.random.split(jax.random.PRNGKey(s + hkv * 7 + g * 13 + dh), 5)
    H = hkv * g
    q = jax.random.normal(keys[0], (H, dh))
    k_q = _rand_i8(keys[1], (s, hkv, dh))
    v_q = _rand_i8(keys[2], (s, hkv, dh))
    k_s = jax.random.uniform(keys[3], (s, hkv), minval=0.01, maxval=0.05)
    v_s = jax.random.uniform(keys[4], (s, hkv), minval=0.01, maxval=0.05)
    length = jnp.int32(int(s * frac))

    want = ref.decode_attend_i8kv_ref(q, k_q, v_q, k_s, v_s, length)
    # kernel layout, as layer 1 of a two-layer stack of one batch row
    got = decode_attend_i8kv_p(
        q.reshape(1, hkv, g, dh),
        _stack_layer1(jnp.transpose(k_q, (1, 0, 2))),
        _stack_layer1(jnp.transpose(v_q, (1, 0, 2))),
        _stack_layer1(jnp.transpose(k_s, (1, 0))),
        _stack_layer1(jnp.transpose(v_s, (1, 0))),
        jnp.full((1,), length, jnp.int32), jnp.int32(1), bs=128,
        interpret=True,
    ).reshape(H, dh)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _stack_layer1(a):
    """``a`` as batch row 0 of layer 1 in a two-layer stack whose layer 0
    holds other values: a kernel that reads the wrong layer fails."""
    return jnp.stack([jnp.flip(a, -1), a])[:, None]


# lengths per row that sit on and around the S-blocks' edges (bs 128 of a
# 512 cache), and the KV-head shapes of the configurations: stablelm's 32 x
# 64 heads (G 1), yi-6b's 4 x 128 (G 8) and phi-3's 32 x 96 (G 1)
EDGE_LENS = [0, 1, 127, 128, 129, 512]
HEAD_CASES = [
    pytest.param(512, (32, 1, 64), EDGE_LENS, id="stablelm_heads"),
    pytest.param(512, (4, 8, 128), EDGE_LENS, id="yi_heads"),
    pytest.param(512, (32, 1, 96), EDGE_LENS, id="phi3_heads"),
]


@pytest.mark.parametrize("S,heads,bs", [
    pytest.param(1024, (32, 64), 256, id="stablelm"),
    pytest.param(1024, (4, 128), 256, id="yi"),
    pytest.param(1024, (32, 96), 128, id="phi3"),      # halved to fit
    pytest.param(200, (32, 64), 200, id="short"),      # one whole block
    pytest.param(1000, (32, 64), 32, id="ragged"),     # padded to 1024
])
def test_attend_tiling_from_shapes(S, heads, bs):
    """The attend's S-block follows from the cache's shapes: the largest of
    256, 128, ... (down to 32) that divides S, or S itself when shorter,
    halved while the K tile of every head outgrows the budget."""
    Hkv, Dh = heads
    assert kv_cache.attend_tiling(S, Hkv, Dh) == bs
    assert Hkv * Dh * bs <= kv_cache._TILE_BYTES


@pytest.mark.parametrize("s,shape,lens", [
    pytest.param(200, (2, 2, 64), [130, 57], id="200"),  # ragged: padded
    pytest.param(256, (2, 2, 64), [130, 57], id="256"),  # aligned
    *HEAD_CASES])
def test_decode_i8kv_ops_batched(s, shape, lens):
    """ops takes the cache in KERNEL layout (B, Hkv, S, Dh); the oracle
    keeps the logical (S, Hkv) layout.  A row of length 0 reads zeros."""
    Hkv, G, Dh = shape
    B = len(lens)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (B, Hkv * G, Dh))
    k_q = _rand_i8(keys[1], (B, Hkv, s, Dh))
    v_q = _rand_i8(keys[2], (B, Hkv, s, Dh))
    k_s = jax.random.uniform(keys[3], (B, Hkv, s), minval=0.01, maxval=0.05)
    v_s = jax.random.uniform(keys[4], (B, Hkv, s), minval=0.01, maxval=0.05)
    lens = jnp.array(lens, jnp.int32)
    ops.set_impl("kernel")
    try:
        got = ops.decode_attend_i8kv(q, k_q, v_q, k_s, v_s, lens, bs=128)
    finally:
        ops.set_impl("auto")
    want = jax.vmap(ref.decode_attend_i8kv_ref)(
        q, jnp.transpose(k_q, (0, 2, 1, 3)), jnp.transpose(v_q, (0, 2, 1, 3)),
        jnp.transpose(k_s, (0, 2, 1)), jnp.transpose(v_s, (0, 2, 1)), lens)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-4, atol=2e-4)
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("s,shape,lens", [
    pytest.param(200, (2, 2, 64), [1, 131, 200], id="200"),
    pytest.param(256, (2, 2, 64), [1, 131, 256], id="256"),
    *HEAD_CASES])
@pytest.mark.parametrize("wo_prologue", [False, True], ids=["plain", "fused"])
def test_decode_i8kv_writes_token_in_place(wo_prologue, s, shape, lens):
    """With ``new`` the attend launch writes the step's token into its layer
    of the stack, then attends it: the kernel's stacks equal the written
    stacks bit for bit (every other entry unchanged), and its output is the
    oracle's over them.  The token goes to the row's last position (the
    first, second and last block of the 200/256 caches), and to position 0
    of a row of length 0, whose output is zeros."""
    Hkv, G, Dh = shape
    L, B = 3, len(lens)
    keys = jax.random.split(jax.random.PRNGKey(11), 9)
    q = jax.random.normal(keys[0], (B, Hkv * G, Dh))
    cache = (_rand_i8(keys[1], (L, B, Hkv, s, Dh)),
             _rand_i8(keys[2], (L, B, Hkv, s, Dh)),
             jax.random.uniform(keys[3], (L, B, Hkv, s), minval=0.01, maxval=0.05),
             jax.random.uniform(keys[4], (L, B, Hkv, s), minval=0.01, maxval=0.05))
    lens = jnp.array(lens, jnp.int32)
    slots = jnp.maximum(lens - 1, 0)
    tok = (_rand_i8(keys[5], (B, Hkv, Dh)), _rand_i8(keys[6], (B, Hkv, Dh)),
           jax.random.uniform(keys[7], (B, Hkv), minval=0.01, maxval=0.05),
           jax.random.uniform(keys[8], (B, Hkv), minval=0.01, maxval=0.05))
    at = (1, jnp.arange(B)[:, None], jnp.arange(Hkv), slots[:, None])
    want = tuple(a.at[at].set(t) for a, t in zip(cache, tok))
    got = {}
    for impl in ("ref", "kernel"):
        ops.set_impl(impl)
        try:
            got[impl] = ops.decode_attend_i8kv(
                q, *cache, lens, layer=jnp.int32(1), new=(slots, *tok),
                bs=128, wo_prologue=wo_prologue,
                pro_dtype=jnp.float32 if wo_prologue else None)
        finally:
            ops.set_impl("auto")
    for impl, (_, stacks) in got.items():
        for w, g in zip(want, stacks):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=impl)
    o_ref, o_kernel = got["ref"][0], got["kernel"][0]
    if wo_prologue:
        o_ref, o_kernel = o_ref[0], o_kernel[0]
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(o_kernel)[live],
                               np.asarray(o_ref)[live], rtol=2e-4, atol=2e-4)
    assert not np.asarray(o_kernel)[~live].any()


@pytest.mark.parametrize("shape", [(32, 1, 64), (4, 8, 128)],
                         ids=["stablelm_heads", "yi_heads"])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_decode_i8kv_never_reads_blocks_past_the_length(shape, fused):
    """Every S-block wholly past a row's length holds NaN scales: the
    kernel still equals the oracle over the row's first ``len`` tokens and
    is finite, because it neither attends nor reads those blocks (a
    masked block that were attended would spread 0 x NaN).  A row of
    length 0 reads zeros."""
    Hkv, G, Dh = shape
    s, bs, L = 512, 128, 2
    lens = np.array(EDGE_LENS, np.int32)
    B = len(lens)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(keys[0], (B, Hkv, G, Dh))
    k_q = _rand_i8(keys[1], (L, B, Hkv, s, Dh))
    v_q = _rand_i8(keys[2], (L, B, Hkv, s, Dh))
    dead = np.arange(s)[None, None, :] >= (-(-lens // bs) * bs)[:, None, None]
    k_s, v_s = (jnp.where(dead[None], jnp.nan, jax.random.uniform(
        k, (L, B, Hkv, s), minval=0.01, maxval=0.05)) for k in keys[3:])
    kern = decode_attend_i8kv_fused_p if fused else decode_attend_i8kv_p
    got = kern(q, k_q, v_q, k_s, v_s, jnp.asarray(lens), jnp.int32(1), bs=bs,
               interpret=True)
    got = np.asarray(got[0] if fused else got).reshape(B, Hkv * G, Dh)
    assert np.isfinite(got).all()
    for b, n in enumerate(lens):
        if n == 0:
            assert not got[b].any()
            continue
        row = [jnp.swapaxes(a[1, b, :, :n], 0, 1) for a in (k_q, v_q, k_s, v_s)]
        want = ref.decode_attend_i8kv_ref(q[b].reshape(Hkv * G, Dh), *row,
                                          jnp.int32(n))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# fused PDQ prologue + pdq_dense (one prologue + one matmul serving path)
# ---------------------------------------------------------------------------


@settings(**HYPO)
@given(
    m=st.sampled_from([128, 256]),
    k=st.sampled_from([512, 1024]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_pdq_prologue_kernel_vs_ref(m, k, dtype):
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(m + k), (m, k)).astype(dtype)
    got = pdq_prologue_p(x, block=(128, 512), interpret=True)
    want = ref.pdq_prologue_ref(x.reshape(m, k))
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)   # s_x
    # quantization may differ by 1 at exact rounding ties
    assert np.abs(np.asarray(got[0], np.int32) - np.asarray(want[0], np.int32)).max() <= 1
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got[2], want[2], rtol=tol, atol=1e-2)    # s1
    np.testing.assert_allclose(got[3], want[3], rtol=tol, atol=1e-2)    # s2


def test_pdq_prologue_ops_padding_and_lead_dims():
    """Non-multiple (M, K) + leading batch dims exercise every _pad_to branch."""
    ops.set_impl("kernel")
    try:
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 65, 257))
        x_q, s_x, s1, s2 = ops.pdq_prologue(x)
        wq, wsx, ws1, ws2 = ref.pdq_prologue_ref(x.reshape(130, 257))
        assert x_q.shape == (2, 65, 257) and s_x.shape == (2, 65, 1)
        assert np.abs(np.asarray(x_q, np.int32).reshape(130, 257)
                      - np.asarray(wq, np.int32)).max() <= 1
        np.testing.assert_allclose(s_x.reshape(130, 1), wsx, rtol=1e-5)
        np.testing.assert_allclose(s1.reshape(130, 1), ws1, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s2.reshape(130, 1), ws2, rtol=1e-4, atol=1e-4)
    finally:
        ops.set_impl("auto")


@pytest.mark.parametrize("impl", ["ref", "kernel"])
@pytest.mark.parametrize("shape", [(6, 128, 64), (130, 257, 100)])
def test_pdq_dense_fp_matches_unfused_requant_dequant(impl, shape):
    """fp-out epilogue == requant->dequant to within ONE int8 step per row,
    for both the jnp oracle and the interpreted kernels, on block-multiple
    and ragged shapes."""
    M, K, N = shape
    w = 0.05 * jax.random.normal(jax.random.PRNGKey(0), (K, N))
    rec = quantize_weight(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    ops.set_impl(impl)
    try:
        y_fused = ops.pdq_dense(x, rec, out="fp")
        y_unfused, s_out = ops.pdq_dense_unfused(x, rec)
    finally:
        ops.set_impl("auto")
    step = np.asarray(s_out).reshape(M, 1)
    err = np.abs(np.asarray(y_fused) - np.asarray(y_unfused))
    assert (err <= step + 1e-6).all(), float((err / step).max())


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_pdq_dense_int8_out_matches_unfused(impl):
    M, K, N = 130, 257, 100       # ragged: every _pad_to branch
    w = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (K, N))
    rec = quantize_weight(w)
    x = jax.random.normal(jax.random.PRNGKey(3), (M, K))
    ops.set_impl(impl)
    try:
        y_q, s_out, z_out = ops.pdq_dense(x, rec, out="int8")
        x_q, s_x, s1, s2 = ops.pdq_prologue(x)
    finally:
        ops.set_impl("auto")
    assert y_q.dtype == jnp.int8 and y_q.shape == (M, N)
    assert s_out.shape == (M, 1) and z_out.dtype == jnp.int32
    # against the fully-unfused integer pipeline on the same quantized input
    acc = x_q.astype(jnp.int32) @ rec["q"].astype(jnp.int32)
    yf = s_x * rec["scale"][None, :] * acc.astype(jnp.float32)
    want = jnp.clip(jnp.round(yf / s_out) + z_out.astype(jnp.float32), -128, 127)
    assert np.abs(np.asarray(y_q, np.int32) - np.asarray(want, np.int32)).max() <= 1


def test_pdq_dense_per_channel_weight_scale_roundtrip():
    """Per-output-channel weight scales flow through both epilogues."""
    K, N = 128, 128
    w = jnp.concatenate([0.01 * jnp.ones((K, N // 2)),
                         0.2 * jnp.ones((K, N // 2))], axis=1)
    w = w * jax.random.normal(jax.random.PRNGKey(4), (K, N))
    rec = quantize_weight(w)
    assert rec["scale"].shape == (N,)
    x = jax.random.normal(jax.random.PRNGKey(5), (8, K))
    y = ops.pdq_dense(x, rec, out="fp")
    rel = float(jnp.abs(y - x @ w).mean() / jnp.abs(x @ w).mean())
    assert rel < 0.05, rel


def test_w8a8_fp_clamp_epilogue_kernel_vs_ref():
    m, k, n = 128, 128, 128
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x_q = _rand_i8(keys[0], (m, k))
    w_q = _rand_i8(keys[1], (k, n))
    s_x = jax.random.uniform(keys[2], (m, 1), minval=0.01, maxval=0.1)
    s_w = jnp.full((1, n), 0.005)
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=0, keepdims=True)
    lo = jnp.full((m, 1), -1.0)
    hi = jnp.full((m, 1), 1.5)
    got = w8a8_matmul_p(x_q, w_q, s_x, jnp.zeros((m, 1), jnp.int32), s_w,
                        colsum, jnp.ones((m, 1)), jnp.zeros((m, 1), jnp.int32),
                        lo, hi, requant=False, fp_clamp=True, interpret=True)
    want = jnp.clip(ref.w8a8_matmul_ref(x_q, w_q, s_x,
                                        jnp.zeros((m, 1), jnp.int32), s_w),
                    lo, hi)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# grouped projections: per-(row, N-block) epilogue + pdq_dense_grouped
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["requant", "fp_clamp"])
def test_w8a8_per_nblock_epilogue_kernel_vs_ref(mode):
    """per_nblock=True: each 128-lane output block applies its own
    (s_out, z_out) / [lo, hi] - the grouped-matmul epilogue contract."""
    m, k, n = 128, 128, 384                 # 3 N-blocks
    nb = n // 128
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    x_q = _rand_i8(keys[0], (m, k))
    w_q = _rand_i8(keys[1], (k, n))
    s_x = jax.random.uniform(keys[2], (m, 1), minval=0.01, maxval=0.1)
    z_x = jnp.zeros((m, 1), jnp.int32)
    s_w = jnp.full((1, n), 0.005)
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=0, keepdims=True)
    s_out = jax.random.uniform(keys[3], (m, nb), minval=0.3, maxval=0.9)
    z_out = jax.random.randint(keys[4], (m, nb), -5, 5, dtype=jnp.int32)
    lo = -jax.random.uniform(keys[5], (m, nb), minval=0.5, maxval=2.0)
    hi = -1.5 * lo
    requant = mode == "requant"
    got = w8a8_matmul_p(x_q, w_q, s_x, z_x, s_w, colsum, s_out, z_out,
                        lo, hi, requant=requant, fp_clamp=not requant,
                        per_nblock=True, interpret=True)
    y_fp = ref.w8a8_matmul_ref(x_q, w_q, s_x, z_x, s_w)
    expand = lambda a: jnp.repeat(a, 128, axis=-1)     # block -> channel
    if requant:
        want = jnp.clip(jnp.round(y_fp / expand(s_out)) + expand(z_out),
                        -128, 127)
        assert np.abs(np.asarray(got, np.int32) - np.asarray(want, np.int32)).max() <= 1
    else:
        want = jnp.clip(y_fp, expand(lo), expand(hi))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@settings(**HYPO)
@given(
    m=st.sampled_from([8, 130]),
    k=st.sampled_from([256, 257]),
    sizes=st.sampled_from([(64, 96), (128, 100, 200), (32, 32, 32)]),
    impl=st.sampled_from(["ref", "kernel"]),
)
def test_pdq_dense_grouped_segments_match_per_projection(m, k, sizes, impl):
    """Property (acceptance): every grouped output segment matches the
    per-projection pdq_dense result to within one int8 step of that
    segment's predicted grid - the shared (s1, s2) moments depend only on
    the input, so the grouped interval math is exact, not approximate."""
    key = jax.random.PRNGKey(m * k + sum(sizes))
    ws = [0.05 * jax.random.normal(jax.random.fold_in(key, i), (k, n))
          for i, n in enumerate(sizes)]
    x = jax.random.normal(jax.random.fold_in(key, 99), (m, k))
    grec = group_quantize_weights(ws)
    ops.set_impl(impl)
    try:
        ys = ops.pdq_dense_grouped(x, grec, out="fp")
        _, _, s1, s2 = ops.pdq_prologue(x)
        for i, w in enumerate(ws):
            rec = quantize_weight(w)
            y_ind = ops.pdq_dense(x, rec, out="fp")
            _, _, s_out, _ = ops.pdq_interval(rec, s1, s2)
            err = np.abs(np.asarray(ys[i]) - np.asarray(y_ind))
            step = np.asarray(s_out)
            assert (err <= step + 1e-6).all(), (i, float((err / step).max()))
    finally:
        ops.set_impl("auto")


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_pdq_dense_grouped_int8_out(impl):
    """Grouped int8 epilogue: per-segment grids applied per N-block."""
    key = jax.random.PRNGKey(21)
    sizes = (100, 64)
    ws = [0.05 * jax.random.normal(jax.random.fold_in(key, i), (256, n))
          for i, n in enumerate(sizes)]
    x = jax.random.normal(jax.random.fold_in(key, 9), (16, 256))
    grec = group_quantize_weights(ws)
    ops.set_impl(impl)
    try:
        ys, s_out, z_out = ops.pdq_dense_grouped(x, grec, out="int8")
        for i, w in enumerate(ws):
            rec = quantize_weight(w)
            y_ind, s_ind, z_ind = ops.pdq_dense(x, rec, out="int8")
            np.testing.assert_allclose(s_out[..., i:i + 1], s_ind, rtol=1e-6)
            assert np.abs(np.asarray(ys[i], np.int32)
                          - np.asarray(y_ind, np.int32)).max() <= 1
    finally:
        ops.set_impl("auto")
    assert s_out.shape == (16, 2) and z_out.dtype == jnp.int32


# ---------------------------------------------------------------------------
# block-divisibility guards on the raw kernels
# ---------------------------------------------------------------------------


def test_raw_kernels_reject_non_block_multiples():
    x = jnp.zeros((130, 300))
    q = jnp.zeros((130, 300), jnp.int8)
    s = jnp.ones((130, 1))
    z = jnp.zeros((130, 1), jnp.int32)
    with pytest.raises(AssertionError, match="block-multiple"):
        quantize_p(x, s, z)
    with pytest.raises(AssertionError, match="block-multiple"):
        dequantize_p(q, s, z)
    with pytest.raises(AssertionError, match="block-multiple"):
        act_stats_p(x)
    with pytest.raises(AssertionError, match="block-multiple"):
        pdq_prologue_p(x)
    with pytest.raises(AssertionError, match="block-multiple"):
        w8a8_matmul_p(q, jnp.zeros((300, 100), jnp.int8), s, z,
                      jnp.ones((1, 100)), jnp.zeros((1, 100), jnp.int32),
                      s, z, requant=True)
    with pytest.raises(AssertionError, match="block-multiple"):
        decode_attend_i8kv_p(jnp.zeros((1, 2, 2, 64)),
                             jnp.zeros((1, 1, 2, 200, 64), jnp.int8),
                             jnp.zeros((1, 1, 2, 200, 64), jnp.int8),
                             jnp.ones((1, 1, 2, 200)), jnp.ones((1, 1, 2, 200)),
                             jnp.ones((1,), jnp.int32), jnp.int32(0), bs=128)


# ---------------------------------------------------------------------------
# fused decode epilogues (ISSUE 10): attend + wo prologue, SwiGLU + w_down
# prologue - the launches behind the 7-pallas_call decode census
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("frac", [0.3, 1.0])
def test_decode_i8kv_fused_wo_prologue_kernel_vs_ref(frac):
    """decode_attend_i8kv_fused_p must return the SAME o as the plain attend
    kernel plus the wo prologue ref run over the flattened (H*Dh,) row."""
    s, hkv, g, dh = 256, 2, 2, 64
    H = hkv * g
    keys = jax.random.split(jax.random.PRNGKey(41), 5)
    q = jax.random.normal(keys[0], (H, dh))
    k_q = _rand_i8(keys[1], (hkv, s, dh))
    v_q = _rand_i8(keys[2], (hkv, s, dh))
    k_s = jax.random.uniform(keys[3], (hkv, s), minval=0.01, maxval=0.05)
    v_s = jax.random.uniform(keys[4], (hkv, s), minval=0.01, maxval=0.05)
    length = jnp.full((1,), int(s * frac), jnp.int32)
    args = (q.reshape(1, hkv, g, dh), _stack_layer1(k_q), _stack_layer1(v_q),
            _stack_layer1(k_s), _stack_layer1(v_s), length, jnp.int32(1))

    o_plain = decode_attend_i8kv_p(*args, bs=128, interpret=True)
    o, o_q, s_x, s1, s2 = decode_attend_i8kv_fused_p(*args, bs=128,
                                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_plain))
    wq, wsx, ws1, ws2 = ref.pdq_prologue_ref(o_plain.reshape(1, H * dh))
    np.testing.assert_allclose(s_x.reshape(1, 1), wsx, rtol=1e-5)
    np.testing.assert_allclose(s1.reshape(1, 1), ws1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s2.reshape(1, 1), ws2, rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(o_q, np.int32).reshape(1, H * dh)
                  - np.asarray(wq, np.int32)).max() <= 1


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_decode_i8kv_ops_wo_prologue_batched(impl):
    """ops.decode_attend_i8kv(wo_prologue=True) == plain attend + prologue
    ref, in BOTH impls (the ref path must be bit-identical to the unfused
    composition so CPU engine parity is unaffected)."""
    B, Hkv, G, Dh, s = 3, 2, 2, 64, 256
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(keys[0], (B, Hkv * G, Dh))
    k_q = _rand_i8(keys[1], (B, Hkv, s, Dh))
    v_q = _rand_i8(keys[2], (B, Hkv, s, Dh))
    k_s = jax.random.uniform(keys[3], (B, Hkv, s), minval=0.01, maxval=0.05)
    v_s = jax.random.uniform(keys[4], (B, Hkv, s), minval=0.01, maxval=0.05)
    lens = jnp.array([256, 57, 1], jnp.int32)
    ops.set_impl(impl)
    try:
        o, o_q, s_x, s1, s2 = ops.decode_attend_i8kv(
            q, k_q, v_q, k_s, v_s, lens, wo_prologue=True,
            pro_dtype=jnp.float32)
        o_plain = ops.decode_attend_i8kv(q, k_q, v_q, k_s, v_s, lens)
    finally:
        ops.set_impl("auto")
    np.testing.assert_allclose(o, o_plain, rtol=1e-6, atol=1e-6)
    wq, wsx, ws1, ws2 = ref.pdq_prologue_ref(
        np.asarray(o_plain).reshape(B, Hkv * G * Dh))
    np.testing.assert_allclose(s_x.reshape(B, 1), wsx, rtol=1e-5)
    np.testing.assert_allclose(s1.reshape(B, 1), ws1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s2.reshape(B, 1), ws2, rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(o_q, np.int32).reshape(B, -1)
                  - np.asarray(wq, np.int32)).max() <= 1
    if impl == "ref":
        # ref path is the EXACT unfused composition
        np.testing.assert_array_equal(np.asarray(o), np.asarray(o_plain))
        np.testing.assert_array_equal(np.asarray(o_q).reshape(B, -1),
                                      np.asarray(wq))


def test_w8a8_swiglu_matmul_kernel_vs_unfused():
    """The raw SwiGLU-epilogue matmul == plain clamped matmul + jnp
    silu(g)*u + prologue ref, including the padded-lane columns (zero
    weight cols produce hsw == 0, which the prologue must tolerate)."""
    M, K, N = 128, 256, 512          # P = 256: gate cols [0:256), up [256:512)
    P = N // 2
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x_q = _rand_i8(keys[0], (M, K))
    w_q = _rand_i8(keys[1], (K, N))
    s_x = jax.random.uniform(keys[2], (M, 1), minval=0.01, maxval=0.05)
    z_x = jnp.zeros((M, 1), jnp.int32)
    s_w = jax.random.uniform(keys[3], (1, N), minval=0.001, maxval=0.01)
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=0, keepdims=True)
    nb = N // 128
    lo = -20.0 * jnp.ones((M, nb))
    hi = 20.0 * jnp.ones((M, nb))

    y, hsw_q, sxo, s1o, s2o = w8a8_swiglu_matmul_p(
        x_q, w_q, s_x, z_x, s_w, colsum, lo, hi, interpret=True)
    y_want = w8a8_matmul_p(x_q, w_q, s_x, z_x, s_w, colsum,
                           jnp.ones((M, nb)), jnp.zeros((M, nb), jnp.int32),
                           lo, hi, requant=False, fp_clamp=True,
                           per_nblock=True, interpret=True)
    np.testing.assert_allclose(y, y_want, rtol=1e-5, atol=1e-5)
    hsw_want = jax.nn.silu(y_want[:, :P]) * y_want[:, P:]
    wq_, wsx, ws1, ws2 = ref.pdq_prologue_ref(hsw_want)
    np.testing.assert_allclose(sxo, wsx, rtol=1e-5)
    np.testing.assert_allclose(s1o, ws1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s2o, ws2, rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(hsw_q, np.int32)
                  - np.asarray(wq_, np.int32)).max() <= 1


@pytest.mark.parametrize("impl", ["ref", "kernel"])
@pytest.mark.parametrize("shape", [(8, 1, 256, 512), (130, 257, 384)])
def test_pdq_mlp_fused_matches_unfused(impl, shape):
    """ops.pdq_mlp == pdq_dense_grouped + jnp silu(g)*u + pdq_dense, in both
    impls (ref falls back to EXACTLY that composition; the kernel path
    must agree to float tolerance), with ragged shapes covering padding."""
    *lead, d_model, d_ff = shape
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    wg = 0.1 * jax.random.normal(keys[0], (d_model, d_ff))
    wu = 0.1 * jax.random.normal(keys[1], (d_model, d_ff))
    wd = 0.1 * jax.random.normal(keys[2], (d_ff, d_model))
    grec = group_quantize_weights((wg, wu))
    drec = quantize_weight(wd)
    x = jax.random.normal(keys[3], (*lead, d_model))
    ops.set_impl(impl)
    try:
        y = ops.pdq_mlp(x, grec, drec, out_dtype=jnp.float32)
        g, u = ops.pdq_dense_grouped(x, grec, out="fp", out_dtype=jnp.float32)
        want = ops.pdq_dense(jax.nn.silu(g) * u, drec, out="fp",
                             out_dtype=jnp.float32)
    finally:
        ops.set_impl("auto")
    assert y.shape == want.shape
    if impl == "ref":
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    else:
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
