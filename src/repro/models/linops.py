"""Switchable linear ops: fp matmul or PDQ-int8 (W8A8) execution.

Models call ``lin(x, w)`` for every large projection.  When a weight leaf
has been replaced by a quantized record (see ``quantize_weight``), the
matmul runs int8 x int8 with the *PDQ-predicted* output requantization
scale - computed from the input moments BEFORE the matmul (paper Sec. 4),
so the fp accumulator never needs to be materialized to find its range.

Projections that consume the SAME input (Q/K/V off the attention norm,
gate/up off the ffn norm, MLA's wq_a/wkv_a) additionally share the
prologue: ``lin_grouped(x, (w1, w2, ...))`` runs ONE ``pdq_prologue`` and
ONE wide W8A8 matmul over the N-concatenated group record and splits the
output back into per-projection segments.  The sharing is exact, not
approximate: the surrogate interval of every segment is priced from the
same per-row moments ``(s1, s2)``, which depend only on the input
(DESIGN.md "Grouped execution").  ``lin_grouped`` transparently falls back
to per-projection ``lin`` calls when any member is unquantized or the
members are not views of one group record.  ``quantize_param_tree`` emits
grouped records for the known sibling sets automatically; each sibling key
keeps its place in the param tree as a lightweight *segment view*
(``{"group": <shared record>, "seg": SegRef(i)}`` - the shared arrays alias
one device buffer, so weight memory is not duplicated).

The int8 output is immediately dequantized to the compute dtype for
composability with the surrounding (residual / norm) ops; on TPU the wins
are int8 weight streaming (2x HBM) and the int8 epilogue (no fp32 output
round-trip).  See DESIGN.md Sec. 2.
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp

from repro.kernels import ops

# TPU lane width: grouped segments pad their N extent to this boundary so
# every (row, N-block) epilogue cell of the wide matmul belongs to exactly
# one segment.
LANE = 128

_GROUP_IDS = itertools.count()


def quantize_weight(w: jax.Array, alpha: float = 6.0, beta: float = 6.0) -> dict:
    """Deploy-time: per-output-channel symmetric int8 weight record with the
    Gaussian weight stats the PDQ surrogate needs (Eqs. 8-9)."""
    w32 = w.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(w32), axis=0), 1e-8)      # (h,)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(w32 / scale[None, :]), -127, 127).astype(jnp.int8)
    return {
        "q": q,
        "scale": scale,
        "colsum": jnp.sum(q.astype(jnp.int32), axis=0, keepdims=True),
        "mu_w": jnp.mean(w32),
        "var_w": jnp.var(w32),
        "alpha": jnp.float32(alpha),
        "beta": jnp.float32(beta),
    }


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class GroupSegs:
    """Static (trace-time) layout of a grouped weight record.

    ``sizes``   - original per-projection N extents;
    ``padded``  - the LANE-rounded extent each segment occupies in the
                  concatenated record;
    ``names``   - the sibling leaf names, for debugging;
    ``gid``     - unique id distinguishing otherwise shape-identical groups
                  (two layers' QKV triples must never be mixed in one
                  ``lin_grouped`` call).

    Registered static so it rides inside param pytrees as part of the
    treedef instead of becoming a traced leaf.
    """
    sizes: tuple[int, ...]
    padded: tuple[int, ...]
    names: tuple[str, ...] = ()
    gid: int = -1

    @property
    def offsets(self) -> tuple[int, ...]:
        out, off = [], 0
        for p in self.padded:
            out.append(off)
            off += p
        return tuple(out)

    @property
    def total(self) -> int:
        return sum(self.padded)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class SegRef:
    """Static segment index carried by a grouped-record view."""
    index: int


def group_quantize_weights(ws, alpha: float = 6.0, beta: float = 6.0,
                           names: tuple[str, ...] = ()) -> dict:
    """Deploy-time: concatenate sibling weights (same K) along N into ONE
    quantized record with per-segment surrogate stats.

    Each segment is padded to the LANE (128) boundary before concatenation
    so the per-(row, N-block) interval epilogue of the wide W8A8 matmul
    never straddles two segments.  Per-channel ``scale``/``colsum`` keep
    their exact per-projection values (padding channels get scale 1 /
    colsum 0 and are sliced away after the matmul); ``mu_w``/``var_w``/
    ``alpha``/``beta`` become (n_seg,) vectors so ``ops.pdq_interval``
    broadcasts to a per-(row, segment) interval.
    """
    ws = [jnp.asarray(w) for w in ws]
    assert len(ws) >= 2, "a group needs at least two projections"
    K = ws[0].shape[0]
    assert all(w.ndim == 2 and w.shape[0] == K for w in ws), (
        f"grouped projections must share the input dim: "
        f"{[tuple(w.shape) for w in ws]}")
    qs, scales, colsums, mus, vrs, als, bes = [], [], [], [], [], [], []
    sizes, padded = [], []
    for w in ws:
        rec = quantize_weight(w, alpha, beta)
        n = w.shape[1]
        p = n + (-n) % LANE
        qs.append(jnp.pad(rec["q"], ((0, 0), (0, p - n))))
        scales.append(jnp.pad(rec["scale"], (0, p - n), constant_values=1.0))
        colsums.append(jnp.pad(rec["colsum"], ((0, 0), (0, p - n))))
        mus.append(rec["mu_w"])
        vrs.append(rec["var_w"])
        als.append(rec["alpha"])
        bes.append(rec["beta"])
        sizes.append(n)
        padded.append(p)
    return {
        "q": jnp.concatenate(qs, axis=1),
        "scale": jnp.concatenate(scales),
        "colsum": jnp.concatenate(colsums, axis=1),
        "mu_w": jnp.stack(mus),
        "var_w": jnp.stack(vrs),
        "alpha": jnp.stack(als),
        "beta": jnp.stack(bes),
        "segs": GroupSegs(sizes=tuple(sizes), padded=tuple(padded),
                          names=tuple(names), gid=next(_GROUP_IDS)),
    }


def group_segment_view(grec: dict, index: int) -> dict:
    """A param-tree leaf standing in for segment ``index`` of ``grec``.

    The view aliases the shared record (same device buffers), so sibling
    keys keep their place in the tree without duplicating weight memory.
    Caveat: the aliasing holds only while the leaves stay the same
    ``jax.Array`` objects - a transform that materializes per leaf
    (checkpoint serialization, per-leaf device_put resharding) replicates
    the shared arrays once per sibling.  Quantized trees are serving-time
    artifacts rebuilt from fp checkpoints, so this stays off the hot path.
    """
    assert 0 <= index < len(grec["segs"].sizes)
    return {"group": grec, "seg": SegRef(index)}


def segment_record(view: dict) -> dict:
    """Materialize a per-projection record from a segment view (slices the
    concatenated arrays; used only by the per-projection fallback path)."""
    g = view["group"]
    i = view["seg"].index
    segs = g["segs"]
    off, n = segs.offsets[i], segs.sizes[i]
    return {
        "q": g["q"][:, off:off + n],
        "scale": g["scale"][off:off + n],
        "colsum": g["colsum"][:, off:off + n],
        "mu_w": g["mu_w"][i],
        "var_w": g["var_w"][i],
        "alpha": g["alpha"][i],
        "beta": g["beta"][i],
    }


def is_quantized(w) -> bool:
    return isinstance(w, dict) and ("q" in w or "group" in w)


def is_grouped(w) -> bool:
    return isinstance(w, dict) and "q" in w and "segs" in w


def is_segment_view(w) -> bool:
    return isinstance(w, dict) and "group" in w


def lin(x: jax.Array, w) -> jax.Array:
    """y = x @ w, fp or PDQ-int8 depending on the weight leaf.

    The quantized path is the fused serving pipeline (DESIGN.md Sec. 2):
    ONE prologue kernel reads x and emits (x_q, s_x, s1, s2), the surrogate
    prices the output interval from (s1, s2) in O(rows), and ONE W8A8
    matmul applies that interval in its fp-out epilogue - no separate
    amax / quantize / act_stats passes and no int8 requant -> dequant
    round-trip on the output.  Segment views are sliced back to a
    per-projection record first (compatibility path; grouped call sites
    should use ``lin_grouped``).
    """
    if not is_quantized(w):
        tp = ops.tp_ctx()
        if (tp is not None and getattr(w, "ndim", 0) == 2
                and w.shape[1] % tp[1] == 0):
            # serving TP (inside a shard_map body): this shard's N-columns
            # only, then a tiled all-gather.  Each column sums the same
            # full-K products; XLA may tile the narrower fp matmul
            # differently (reduction-order ulps - the int8 path in
            # kernels/ops is the bit-exact one), which greedy parity
            # absorbs (see kernels/ops.tp_shard).
            ax, size = tp
            n_loc = w.shape[1] // size
            w_loc = jax.lax.dynamic_slice_in_dim(
                w, jax.lax.axis_index(ax) * n_loc, n_loc, 1)
            y = x @ w_loc
            return jax.lax.all_gather(y, ax, axis=y.ndim - 1, tiled=True)
        return x @ w
    if is_segment_view(w):
        w = segment_record(w)
    return ops.pdq_dense(x, w, out="fp", out_dtype=x.dtype)


def _common_group(ws) -> dict | None:
    """The shared group record iff ``ws`` are views of ONE group, in
    segment order, covering every segment; else None."""
    if not ws or not all(is_segment_view(w) for w in ws):
        return None
    segs = ws[0]["group"]["segs"]
    if len(segs.sizes) != len(ws):
        return None
    for i, w in enumerate(ws):
        if w["group"]["segs"] != segs or w["seg"].index != i:
            return None
    return ws[0]["group"]


def lin_grouped(x: jax.Array, ws) -> tuple:
    """(x @ w1, x @ w2, ...) for projections sharing the input x.

    When every member is a segment view of one grouped record (the layout
    ``quantize_param_tree`` emits for known sibling sets), this runs the
    grouped serving pipeline: ONE prologue + ONE wide W8A8 matmul whose
    per-(row, segment) interval epilogue prices each segment's surrogate
    grid from the shared (s1, s2) moments - the activation is read from HBM
    once instead of once per projection, and the decode-shaped skinny
    matmuls fuse into a single MXU-friendly wide call.  Otherwise it falls
    back to per-projection ``lin`` (fp weights, mixed quantization, or
    records that were never grouped), which is numerically identical.
    """
    ws = tuple(ws)
    grec = _common_group(ws)
    if grec is not None:
        return ops.pdq_dense_grouped(x, grec, out="fp", out_dtype=x.dtype)
    return tuple(lin(x, w) for w in ws)


# Sibling sets that consume the same input and therefore share one
# prologue: Q/K/V off the attention norm, gate/up off the ffn norm, MLA's
# two input-side projections.  Cross-attention is special-cased: its wk/wv
# read the encoder memory while wq reads the decoder stream, so only the
# (wk, wv) pair shares an input.  The dispatch keys on the parent dict key
# being exactly 'cross' - param leaf/key names are a repo-wide contract
# (see models/layers.py header and distributed/sharding._RULES), so a
# renamed cross block must update all three places together.
GROUP_SIBLING_SETS = (("wq", "wk", "wv"), ("w_gate", "w_up"),
                      ("wq_a", "wkv_a"))
CROSS_SIBLING_SETS = (("wk", "wv"),)
# top-level param subtrees whose leaves carry a leading layer axis (the
# scanned pattern blocks and the encoder stack; models/transformer.py)
STACKED_KEYS = ("blocks", "enc_blocks")


def quantize_param_tree(params, path_pred=None, alpha: float = 6.0,
                        beta: float = 6.0, group_siblings: bool = True):
    """Replace selected 2-D weight leaves with quantized records.

    path_pred(path_str, leaf) -> bool selects leaves; default: every 2-D
    float leaf whose name starts with 'w' or ends with '_proj'.

    With ``group_siblings`` (default), known same-input sibling sets whose
    members all pass the predicate are emitted as ONE grouped record
    (``group_quantize_weights``) with each sibling key holding a segment
    view, so ``lin_grouped`` call sites hit the one-prologue + one-matmul
    path without any per-call concatenation.
    """
    def default_pred(path, leaf):
        name = path.split("/")[-1]
        return (leaf.ndim == 2 and jnp.issubdtype(leaf.dtype, jnp.floating)
                and (name.startswith("w") or name.endswith("_proj")
                     or name in ("in_proj", "out_proj")))

    pred = path_pred or default_pred

    def q_ok(path, leaf):
        return hasattr(leaf, "ndim") and pred(path, leaf)

    def join(path, k):
        return f"{path}/{k}" if path else str(k)

    def rec(node, path, key):
        if isinstance(node, dict):
            out = {}
            done = set()
            if group_siblings:
                sets = CROSS_SIBLING_SETS if key == "cross" else GROUP_SIBLING_SETS
                for names in sets:
                    if not all(n in node for n in names):
                        continue
                    leaves = [node[n] for n in names]
                    if not all(hasattr(l, "ndim") and l.ndim == 2 for l in leaves):
                        continue
                    if len({l.shape[0] for l in leaves}) != 1:
                        continue
                    if not all(q_ok(join(path, n), l)
                               for n, l in zip(names, leaves)):
                        continue
                    grec = group_quantize_weights(leaves, alpha, beta,
                                                  names=names)
                    for i, n in enumerate(names):
                        out[n] = group_segment_view(grec, i)
                    done.update(names)
            for k, v in node.items():
                if k in done:
                    continue
                if not path and k in STACKED_KEYS:
                    # layer-stacked (lax.scan) params: quantize each layer's
                    # slice, restacking the records along the layer axis
                    out[k] = jax.vmap(lambda t, k=k: rec(t, k, k))(v)
                    continue
                out[k] = rec(v, join(path, k), k)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(rec(v, join(path, str(i)), key)
                              for i, v in enumerate(node))
        if q_ok(path, node):
            return quantize_weight(node, alpha, beta)
        return node

    return rec(params, "", None)
