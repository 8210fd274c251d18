"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the plain reference can make
the very same ones again without taking anything from the program.  The tree
has the program's layout for a dense decoder (one scanned block of layers):

  embed/embedding (V, d); final_norm (d,); head (); tail ();
  blocks[0]: attn_norm, ffn_norm (L, d); attn/wq, wk, wv (L, d, H*Dh),
             attn/wo (L, H*Dh, d); ffn/w_gate, w_up (L, d, F), ffn/w_down (L, F, d)

Projections are uniform in +-sqrt(3 / fan_in) (unit output variance), the
embedding is normal with standard deviation 0.02, and the norm gains (the
model scales by 1 + gain) are normal with standard deviation 0.1, so that a
norm applied wrongly shows in the logits.
"""
from __future__ import annotations

import functools


def dims(config: dict) -> dict:
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {"L": int(config["num_hidden_layers"]), "d": d, "H": h,
            "Hkv": int(config["num_key_value_heads"]), "Dh": d // h,
            "F": int(config["intermediate_size"]),
            "V": int(config["vocab_size"]), "dtype": config["torch_dtype"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["norm_eps"])}


@functools.lru_cache(maxsize=None)
def _maker(L, d, H, Hkv, Dh, F, V, dtype):
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)

    def uni(k, shape, fan_in):
        s = (3.0 / fan_in) ** 0.5
        return jax.random.uniform(k, shape, jnp.float32, -s, s).astype(dt)

    def make(key):
        ks = jax.random.split(key, 11)
        block = {
            "attn_norm": (0.1 * jax.random.normal(ks[0], (L, d))).astype(dt),
            "attn": {"wq": uni(ks[1], (L, d, H * Dh), d),
                     "wk": uni(ks[2], (L, d, Hkv * Dh), d),
                     "wv": uni(ks[3], (L, d, Hkv * Dh), d),
                     "wo": uni(ks[4], (L, H * Dh, d), H * Dh)},
            "ffn_norm": (0.1 * jax.random.normal(ks[5], (L, d))).astype(dt),
            "ffn": {"w_gate": uni(ks[6], (L, d, F), d),
                    "w_up": uni(ks[7], (L, d, F), d),
                    "w_down": uni(ks[8], (L, F, d), F)},
        }
        return {"embed": {"embedding": (0.02 * jax.random.normal(
                    ks[9], (V, d))).astype(dt)},
                "final_norm": (0.1 * jax.random.normal(ks[10], (d,))).astype(dt),
                "head": (), "tail": (), "blocks": (block,)}

    return jax.jit(make)


def make_params(config: dict, seed: int):
    import jax
    m = dims(config)
    make = _maker(m["L"], m["d"], m["H"], m["Hkv"], m["Dh"], m["F"], m["V"],
                  m["dtype"])
    return jax.block_until_ready(make(jax.random.PRNGKey(seed % (1 << 32))))


def check_layout(params, cfg) -> None:
    """The program's own parameter tree for ``cfg`` must have exactly this
    structure, shapes and dtypes: else the program would not run the model
    that the reference computes."""
    import jax

    from repro.models import build_model
    want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if jax.tree.structure(got) != jax.tree.structure(exp) or got != exp:
        raise RuntimeError(f"the program's parameter layout differs from the "
                           f"benchmark's: {exp} != {got}")
