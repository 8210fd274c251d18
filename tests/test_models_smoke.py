"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step + prefill/decode on CPU; asserts shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, reduced_config
from repro.models import build_model


def _batch(cfg, B, S, key):
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab),
    }
    if cfg.frontend == "vision":
        batch["patches"] = 0.01 * jax.random.normal(
            key, (B, cfg.frontend_tokens, cfg.d_model)).astype(jnp.bfloat16)
    if cfg.family == "encdec":
        batch["frames"] = 0.01 * jax.random.normal(
            key, (B, 8, cfg.d_model)).astype(jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_train_step_smoke(arch):
    cfg = reduced_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = _batch(cfg, B, S, jax.random.PRNGKey(1))
    loss, metrics = m.train_loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    # gradients flow and are finite
    g = jax.grad(lambda p: m.train_loss(p, batch)[0])(params)
    leaves = jax.tree.leaves(g)
    assert leaves, "no gradient leaves"
    assert all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32)))) for l in leaves), (
        f"{arch}: non-finite grads")


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_prefill_decode_smoke(arch):
    cfg = reduced_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = _batch(cfg, B, S, jax.random.PRNGKey(1))
    batch.pop("labels")
    mem_len = 8 if cfg.family == "encdec" else 0
    P = cfg.frontend_tokens if cfg.frontend == "vision" else 0
    caches = m.init_caches(B, S + P + 4, mem_len)
    logits, caches = m.prefill(params, batch, caches)
    assert logits.shape == (B, cfg.vocab)
    for step in range(2):
        pos = jnp.full((B, 1), S + P + step, jnp.int32)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        logits, caches = m.decode_step(params, caches, tok, pos)
        assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: non-finite decode logits"


# (arch, cfg overrides, decode steps after the prefill).  Every decode
# step runs the layer-stacked GQA caches in place (transformer.lm_apply);
# the other cache kinds keep the scan's xs/ys.
_DECODE_CASES = {
    "stablelm-1.6b": ("stablelm-1.6b", {}, 1),                 # fp GQA
    "gemma2-2b": ("gemma2-2b", {}, 1),                         # window ring
    "zamba2-7b": ("zamba2-7b", {}, 1),                         # SSM + shared
    "seamless-m4t-medium": ("seamless-m4t-medium", {}, 1),     # cross K/V
    "stablelm-1.6b-int8-kv": ("stablelm-1.6b", {"quant_kv": "dynamic"}, 4),
    "gemma2-2b-ring-past-window": ("gemma2-2b", {}, 20),       # window 16
    "zamba2-7b-shared-attn-steps": ("zamba2-7b", {}, 6),
}


@pytest.mark.parametrize("case", list(_DECODE_CASES))
def test_decode_matches_prefill(case):
    """Decoding the last ``n`` tokens one at a time after an (S-n)-prefill
    must reproduce the S-prefill logits (validates KV/ring/SSM/cross
    caches, the in-place decode update included)."""
    arch, overrides, n = _DECODE_CASES[case]
    cfg = reduced_config(arch, **overrides)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 24
    batch = _batch(cfg, B, S, jax.random.PRNGKey(2))
    batch.pop("labels")
    mem_len = 8 if cfg.family == "encdec" else 0
    caches = m.init_caches(B, S, mem_len)
    full, _ = m.prefill(params, batch, caches)
    caches = m.init_caches(B, S, mem_len)
    b2 = dict(batch)
    b2["tokens"] = batch["tokens"][:, : S - n]
    _, caches = m.prefill(params, b2, caches)
    for p in range(S - n, S):
        dec, caches = m.decode_step(params, caches, batch["tokens"][:, p:p + 1],
                                    jnp.full((B, 1), p, jnp.int32))
    scale = float(jnp.abs(full).max()) + 1e-6
    assert float(jnp.abs(full - dec).max()) / scale < 0.05


@pytest.mark.parametrize("arch,quant_kv", [("stablelm-1.6b", "none"),
                                           ("stablelm-1.6b", "dynamic"),
                                           ("gemma2-2b", "none")],
                         ids=["fp", "int8", "window_ring"])
def test_decode_step_writes_only_its_token(arch, quant_kv):
    """A decode step changes the layer-stacked GQA caches only at its own
    (layer, row, slot) entries: every other byte of every layer stays."""
    cfg = reduced_config(arch, quant_kv=quant_kv)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 20                           # past gemma2's reduced window 16
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab)
    _, before = m.prefill(params, {"tokens": toks}, m.init_caches(B, S + 4))
    pos = jnp.array([[S], [S - 3]], jnp.int32)   # row 1 rewrites a slot
    _, after = jax.jit(m.decode_step)(params, before, toks[:, -1:], pos)
    assert not jax.tree.leaves(before["head"]) + jax.tree.leaves(before["tail"])
    for blk_before, blk_after in zip(before["blocks"], after["blocks"]):
        W = blk_before["pos"].shape[-1]
        slots = np.asarray(pos[:, 0]) % W
        for name, a in blk_before.items():
            a, got = np.array(a), np.asarray(blk_after[name])
            for b, slot in enumerate(slots):
                if name == "len":
                    idx = (slice(None), b)
                elif quant_kv != "none" and name != "pos":  # (L, B, H, S, ..)
                    idx = (slice(None), b, slice(None), slot)
                else:                                       # (L, B, S, ...)
                    idx = (slice(None), b, slot)
                a[idx] = got[idx]
            np.testing.assert_array_equal(got, a, err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(blk_after["pos"])[:, np.arange(B), slots],
            np.broadcast_to(pos[:, 0], (cfg.n_blocks, B)))


def test_int8_kv_cache_decode_close_to_fp():
    """quant_kv='dynamic' decode stays near the fp cache path."""
    import dataclasses
    cfg = reduced_config("yi-6b")
    m_fp = build_model(cfg)
    m_q = build_model(dataclasses.replace(cfg, quant_kv="dynamic"))
    params = m_fp.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab)
    outs = {}
    for tag, m in (("fp", m_fp), ("q", m_q)):
        caches = m.init_caches(B, S, 0)
        _, caches = m.prefill(params, {"tokens": toks[:, :S - 1]}, caches)
        logits, _ = m.decode_step(params, caches, toks[:, S - 1:],
                                  jnp.full((B, 1), S - 1, jnp.int32))
        outs[tag] = logits
    scale = float(jnp.abs(outs["fp"]).max()) + 1e-6
    assert float(jnp.abs(outs["fp"] - outs["q"]).max()) / scale < 0.08
