"""Mixture-of-Experts FFN with expert parallelism.

Dispatch design (production path, used under shard_map over ('data','model')):

  1. tokens are sharded over *both* mesh axes; each device routes its local
     tokens and packs them into a per-global-expert capacity buffer
     (E, C_e, d) - slot overflow drops (capacity factor 1.25, standard).
  2. one all_to_all over the 'model' (expert) axis with split_axis=0 /
     concat_axis=1 lands the buffer already bucketed per *local* expert:
     (E_local, nshards * C_e, d).
  3. batched SwiGLU einsum over the local expert stack.
  4. reverse all_to_all; the source combines expert outputs with its gates.

Zero-padded slots are free: SwiGLU(0) = 0 and the combine gathers only real
slots.  A dense-masked path (each device computes all its local experts over
all tokens, psum over 'model') serves tiny-token decode steps where the
dispatch machinery would be all overhead.

Extras: shared experts (DeepSeek) and a dense FFN residual (Arctic), both
plain TP-sharded MLPs applied to every token; switch-style load-balance aux
loss.

A layer may hold a SHARE of the experts (``MoEConfig.n_held``): the router
keeps its published width and top-k over all ``n_experts``, the stacks hold
experts ``held_offset .. held_offset + n_held - 1`` only, and assignments
to experts not held add nothing - the part of the layer one chip of an
expert-parallel deployment computes.  Such a layer runs the dense-masked
form in every mode (no capacity, so no assignment to a held expert is ever
dropped).  Under PDQ its experts are quantized records
(``linops.quantize_experts``): one grouped gate/up W8A8 matmul over every
held expert and one down matmul over the experts stacked along K.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ops

from .layers import dense_init, mlp_init

# kernel name of the held experts' W8A8 matmuls (trace-visible)
EXPERT_KERNEL = "w8a8_expert_matmul"


def expert_blocks(rows: int) -> tuple[tuple[int, int, int], ...]:
    """(bm, bn, bk) of the gate/up and of the down expert matmul for
    ``rows`` rows.  The W8A8 kernel costs a fixed time per grid step, and
    the experts' matmuls are wide (N = 2 x experts x width) or deep
    (K = experts x width): a 512-deep K block cuts their steps 4x, and the
    down matmul's per-row epilogue lets its N block be 512 too (gate/up
    keeps 128: every expert's segment, 1408 wide, is a multiple of 128
    only).  Row blocks of 256 past one block of rows."""
    bm = 128 if rows <= 128 else 256
    return (bm, 128, 512), (bm, 512, 512)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0               # shared experts applied to all tokens
    dense_residual: bool = False    # Arctic: dense FFN in parallel with MoE
    d_ff_dense: int = 0             # width of shared/dense-residual FFN
    capacity_factor: float = 1.25
    router_scale: float = 1.0       # gate multiplier (deepseek routed_scaling)
    norm_topk: bool = True          # renormalise the top-k gates to sum 1
    n_held: int | None = None       # experts this layer holds (None: all)
    held_offset: int = 0            # id of the first expert held

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held


def moe_init(key, d_model: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(key, 6)
    E, ff = cfg.held, cfg.d_ff_expert
    p = {
        # the router keeps its published width: it scores every expert
        "router": dense_init(ks[0], d_model, cfg.n_experts, jnp.float32),
        "we_gate": jax.vmap(lambda k: dense_init(k, d_model, ff, dtype))(
            jax.random.split(ks[1], E)),
        "we_up": jax.vmap(lambda k: dense_init(k, d_model, ff, dtype))(
            jax.random.split(ks[2], E)),
        "we_down": jax.vmap(lambda k: dense_init(k, ff, d_model, dtype))(
            jax.random.split(ks[3], E)),
    }
    if cfg.n_shared:
        p["shared"] = mlp_init(ks[4], d_model, cfg.d_ff_dense or ff * cfg.n_shared, dtype)
    if cfg.dense_residual:
        p["dense"] = mlp_init(ks[5], d_model, cfg.d_ff_dense or ff, dtype)
    return p


def route(x: jax.Array, wr: jax.Array, cfg: MoEConfig,
          token_mask: jax.Array | None = None):
    """x: (T, d) -> gates (T, k), ids (T, k), aux load-balance loss.

    ``token_mask`` (T,) bool marks REAL tokens; masked (pad) tokens are
    routed nowhere: their gates are zeroed and their expert ids set to the
    out-of-range sentinel E, so they neither claim a capacity slot in
    ``_bucket`` (E is dropped as out-of-bounds) nor match any local expert
    in the dense-masked decode path.  This is the DESIGN.md Sec. 4 fix:
    bucketed-prefill pad tokens must not consume router capacity.
    """
    E = wr.shape[1]
    logits = jnp.matmul(x.astype(jnp.float32), wr,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    gates = gates * cfg.router_scale
    if token_mask is not None:
        gates = jnp.where(token_mask[:, None], gates, 0.0)
        ids = jnp.where(token_mask[:, None], ids, E)
    # switch-style aux: E * sum_e f_e * p_e
    density = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * mean_prob)
    return gates, ids, aux


def _bucket(x: jax.Array, flat_ids: jax.Array, E: int, C: int):
    """Scatter tokens into (E, C, d) capacity buckets; overflow drops.

    Returns the buffer plus (bucket, slot, valid) per flattened assignment.
    """
    N = flat_ids.shape[0]
    oh = (flat_ids[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - oh                        # earlier same-id count
    slot = jnp.take_along_axis(pos, flat_ids[:, None], axis=1)[:, 0]
    valid = slot < C
    buf = jnp.zeros((E, C, x.shape[-1]), x.dtype)
    buf = buf.at[flat_ids, slot].set(x, mode="drop")         # OOB slots dropped
    return buf, slot, valid


def _expert_ffn(p, h: jax.Array) -> jax.Array:
    """h: (E_local, C, d) through the stacked SwiGLU experts."""
    g = jnp.einsum("ecd,edf->ecf", h, p["we_gate"])
    u = jnp.einsum("ecd,edf->ecf", h, p["we_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["we_down"])


def moe_ffn_tokens(
    p,
    x: jax.Array,              # (T_local, d) tokens on this shard
    cfg: MoEConfig,
    *,
    axis_name: str | None = None,   # expert-parallel mesh axis ('model')
    token_mask: jax.Array | None = None,   # (T_local,) True = real token
) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE over already-flattened local tokens."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    nshards = 1 if axis_name is None else jax.lax.psum(1, axis_name)  # static int
    E_loc = E // nshards

    gates, ids, aux = route(x, p["router"], cfg, token_mask)
    flat_ids = ids.reshape(-1)                              # (T*k,)
    xk = jnp.repeat(x, k, axis=0)                           # (T*k, d)
    C = max(1, int(T * k * cfg.capacity_factor / E + 0.999))
    buf, slot, valid = _bucket(xk, flat_ids, E, C)          # (E, C, d)

    if axis_name is not None and nshards > 1:
        recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=1,
                                  tiled=True)               # (E_loc, nshards*C, d)
        out = _expert_ffn(p, recv)
        buf_out = jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                                     tiled=True)            # (E, C, d)
    else:
        buf_out = _expert_ffn(p, buf)

    # combine: gather each assignment's expert output, weight by its gate
    y_k = buf_out[flat_ids, jnp.minimum(slot, C - 1)]       # (T*k, d)
    y_k = jnp.where(valid[:, None], y_k, 0.0)
    y = jnp.sum((y_k * gates.reshape(-1, 1).astype(y_k.dtype)).reshape(T, k, d), axis=1)
    return y, aux


def moe_ffn_dense_masked(
    p,
    x: jax.Array,              # (T, d) tokens (replicated over 'model')
    cfg: MoEConfig,
    *,
    axis_name: str | None = None,
    token_mask: jax.Array | None = None,
    count_rows: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Every shard computes its local experts over all tokens, masked by
    gates; psum over the expert axis combines.  The decode path, and the
    only path of a layer that holds a share of the experts.

    The local stack holds experts ``held_offset + shard * E_loc`` on.
    ``count_rows`` records the routed assignments that land on them in the
    launch's telemetry (``ops.tel_count``)."""
    with jax.named_scope("moe_route"):
        gates, ids, aux = route(x, p["router"], cfg, token_mask)
    E_loc = _n_local(p)
    shard = 0 if axis_name is None else jax.lax.axis_index(axis_name)
    e_offset = cfg.held_offset + shard * E_loc
    local_eids = e_offset + jnp.arange(E_loc)               # (E_loc,)
    sel = (ids[None, :, :] == local_eids[:, None, None])    # (E_loc, T, k)
    w = jnp.sum(sel * gates[None], axis=-1)                 # (E_loc, T)
    if count_rows:
        ops.tel_count("expert_rows", jnp.sum(sel.astype(jnp.float32)))
    with jax.named_scope("moe_experts"):
        if "we_gate_up" in p:
            y = _expert_ffn_pdq(p, x, w.T)
        else:
            h = jnp.broadcast_to(x[None], (E_loc, *x.shape))    # (E_loc, T, d)
            out = _expert_ffn(p, h)                             # (E_loc, T, d)
            y = jnp.einsum("et,etd->td", w.astype(out.dtype), out)
    if axis_name is not None and jax.lax.psum(1, axis_name) > 1:
        y = jax.lax.psum(y, axis_name)
    return y, aux


def _n_local(p) -> int:
    """Experts in this layer's (local) stack, fp or quantized."""
    if "we_gate_up" in p:
        return len(p["we_gate_up"]["segs"].sizes) // 2
    return p["we_gate"].shape[0]


def _expert_ffn_pdq(p, x: jax.Array, w: jax.Array) -> jax.Array:
    """The held experts under PDQ, x (T, d) and gate weights w (T, E):
    ONE grouped W8A8 matmul over every expert's gate and up projection
    (one prologue, a surrogate interval per segment), the SwiGLU pairing
    weighted by each row's gates, then ONE W8A8 matmul over the experts'
    down projections stacked along K - the weighted sum over experts is
    that matmul's reduction."""
    gate_up, down = expert_blocks(x.shape[0])
    gu = ops.pdq_dense_grouped(x, p["we_gate_up"], out="fp",
                               out_dtype=x.dtype, block=gate_up,
                               name=EXPERT_KERNEL)
    E = len(gu) // 2
    g = jnp.stack(gu[:E], axis=1)                            # (T, E, F)
    u = jnp.stack(gu[E:], axis=1)
    h = jax.nn.silu(g) * u * w[..., None].astype(x.dtype)
    return ops.pdq_dense(h.reshape(x.shape[0], -1), p["we_down"], out="fp",
                         out_dtype=x.dtype, block=down, name=EXPERT_KERNEL)
