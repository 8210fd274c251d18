"""Public model API: build a ModelBundle from an ArchConfig.

The bundle's step functions are pure and jit/pjit-friendly; the dry-run
lowers them against ``input_specs(shape)`` ShapeDtypeStructs without any
allocation.

Shapes (assignment):
    train_4k     seq 4096,   global batch 256   -> train step
    prefill_32k  seq 32768,  global batch 32    -> prefill (serve) step
    decode_32k   seq 32768,  global batch 128   -> one-token decode step
    long_500k    seq 524288, global batch 1     -> one-token decode step
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops

from .config import ArchConfig
from .layers import chunked_xent_loss
from .transformer import _dtype, lm_apply, lm_init, lm_init_caches, lm_logits


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str           # 'train' | 'prefill' | 'decode'
    seq: int
    batch: int


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}


def _src_len(cfg: ArchConfig, seq: int) -> int:
    """Encoder-side length for encdec (audio frames downsample ~4x)."""
    return max(seq // 4, 8)


def _patch_count(cfg: ArchConfig) -> int:
    return cfg.frontend_tokens if cfg.frontend == "vision" else 0


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ArchConfig
    init: Callable[[jax.Array], Any]
    train_loss: Callable[..., Any]
    prefill: Callable[..., Any]
    decode_step: Callable[..., Any]
    init_caches: Callable[..., Any]
    input_specs: Callable[[str], dict[str, Any]]
    cache_slice: Callable[..., Any] = None
    cache_merge: Callable[..., Any] = None
    prefill_many: Callable[..., Any] = None
    cache_scatter: Callable[..., Any] = None
    prefill_chunk: Callable[..., Any] = None
    paged_cache: Callable[..., Any] = None


@dataclasses.dataclass(frozen=True)
class _PageMeta:
    """Per-leaf paging classification (a pytree leaf of the meta tree)."""
    kind: str            # 'seq' (pageable) | 'flat' (stays per-slot rows)
    seq_axis: int = -1
    n_leaf: int = 0      # this leaf's pages per sequence (>= pool n_pp)
    shape: tuple = ()
    dtype: Any = None


@dataclasses.dataclass(frozen=True)
class PagedCacheOps:
    """Device half of the paged KV-cache pool (serve/pages.py holds the
    allocator): closures that move data between the physical page pool and
    the logical (B, ...) cache layout the step functions consume.  Every
    movement is one fused row scatter per leaf (``ops.cache_scatter_pages``
    - the same scalar-prefetched machinery as the slot-row scatter), so
    the paged engine adds no host round-trips.

    Leaves whose shape does not grow with ``max_len`` (SSM/conv state,
    windowed rings shorter than max_len, encdec memories, the flat ``len``
    leaf) classify 'flat' and keep their per-slot rows inside the pool
    tree untouched - paging is per-leaf, not per-family.
    """
    page: int
    n_pp: int            # page-table width: max_len // page
    meta: Any            # cache-shaped tree of _PageMeta
    init: Callable[..., Any]       # (n_pages) -> physical pool tree
    gather: Callable[..., Any]     # (pool, pt, lengths) -> logical caches
    writeback: Callable[..., Any]  # (pool, logical, pt, positions) -> pool
    land: Callable[..., Any]       # (pool, sub, src_map, rows, js) -> pool
    copy: Callable[..., Any]       # (pool, copy_map) -> pool (COW)
    capture: Callable[..., Any]    # (pool, slot, page_ids) -> host record
    restore: Callable[..., Any]    # (pool, rec, pmap, src_map) -> pool


def build_model(cfg: ArchConfig) -> ModelBundle:
    cfg = cfg.validate()
    dtype = _dtype(cfg)

    def init(rng):
        return lm_init(rng, cfg)

    # ----------------------------------------------------------------- train
    def train_loss(params, batch):
        tokens = batch["tokens"]
        labels = batch["labels"]
        B, S_text = tokens.shape
        P = _patch_count(cfg)
        pos = jnp.broadcast_to(jnp.arange(P + S_text)[None], (B, P + S_text))
        h, _, aux = lm_apply(
            params, cfg, tokens=tokens, positions=pos, mode="train",
            frames=batch.get("frames"), patches=batch.get("patches"))
        h_text = h[:, P:]
        loss = chunked_xent_loss(params["embed"]["embedding"], h_text, labels,
                                 chunk=cfg.loss_chunk,
                                 logit_softcap=cfg.logit_softcap)
        return loss + 0.01 * aux, {"xent": loss, "aux": aux}

    # --------------------------------------------------------------- serving
    def init_caches(batch: int, max_len: int, mem_len: int = 0):
        return lm_init_caches(cfg, batch, max_len, mem_len)

    def prefill(params, batch, caches):
        tokens = batch["tokens"]
        B, S_text = tokens.shape
        P = _patch_count(cfg)
        pos = jnp.broadcast_to(jnp.arange(P + S_text)[None], (B, P + S_text))
        h, caches, _ = lm_apply(
            params, cfg, tokens=tokens, positions=pos, mode="prefill",
            caches=caches, frames=batch.get("frames"),
            patches=batch.get("patches"))
        logits = lm_logits(params, cfg, h[:, -1:])[:, 0]
        return logits, caches

    def prefill_many(params, batch, caches, seq_lens):
        """Batched bucketed prefill over right-padded prompts.

        batch['tokens']: (B, L) int32 where row b holds seq_lens[b] real
        tokens followed by padding up to the bucket length L.  ``caches``
        is a fresh B-row cache pool; every row is fully (re)written -
        pad entries are redirected onto the row's last real token (see
        attention._clamp_padded / ssm_apply) and masked out of MoE
        routing (moe.route token_mask, so they claim no expert-capacity
        slot - DESIGN.md Sec. 4), making the resulting rows bit-identical
        to B independent unpadded prefills.  Returns
        (logits (B, vocab) of each row's LAST REAL token, caches); land
        the rows into the serving pool with ``cache_scatter``.

        Because L is the only shape that varies across workloads, an
        engine lifetime compiles at most len(buckets) executables of this
        function - the per-request path recompiled per distinct prompt
        length instead.
        """
        tokens = batch["tokens"]
        B, S_text = tokens.shape
        P = _patch_count(cfg)
        pos = jnp.broadcast_to(jnp.arange(P + S_text)[None], (B, P + S_text))
        sl = seq_lens.astype(jnp.int32)
        # valid prefix incl patches; rows with seq_lens == 0 are DUMMY rows
        # of a partially-filled batch - their patch tokens are masked too,
        # so a dummy row routes nothing through MoE and claims no expert
        # capacity (the cache scatter drops its rows regardless)
        tot = jnp.where(sl > 0, sl + P, 0)
        h, caches, _ = lm_apply(
            params, cfg, tokens=tokens, positions=pos, mode="prefill",
            caches=caches, frames=batch.get("frames"),
            patches=batch.get("patches"), seq_lens=tot)
        h_last = h[jnp.arange(B), jnp.maximum(tot - 1, 0)][:, None]
        logits = lm_logits(params, cfg, h_last)[:, 0]
        return logits, caches

    def prefill_chunk(params, batch, caches, seq_lens, start_lens):
        """Continue a chunked prefill: row b of ``caches`` already holds
        ``start_lens[b]`` landed tokens; this call appends the next chunk
        (``seq_lens[b]`` real tokens, right-padded to the chunk bucket) and
        attends the whole cache buffer, so queries see both the landed
        prefix and the chunk.  Returns (logits of each row's last real
        token, caches) - the final chunk's logits seed decoding exactly as
        ``prefill_many``'s do.  Text-only families: the vision patch
        prepend and the encdec encoder pass assume a single whole-prompt
        prefill.
        """
        if cfg.frontend == "vision" or cfg.family == "encdec":
            raise NotImplementedError(
                f"chunked prefill supports text-only families, not "
                f"frontend={cfg.frontend!r} / family={cfg.family!r}")
        tokens = batch["tokens"]
        B, L = tokens.shape
        start = start_lens.astype(jnp.int32)
        pos = start[:, None] + jnp.arange(L, dtype=jnp.int32)[None]
        sl = seq_lens.astype(jnp.int32)
        h, caches, _ = lm_apply(
            params, cfg, tokens=tokens, positions=pos, mode="prefill",
            caches=caches, seq_lens=sl, chunked=True)
        h_last = h[jnp.arange(B), jnp.maximum(sl - 1, 0)][:, None]
        logits = lm_logits(params, cfg, h_last)[:, 0]
        return logits, caches

    def decode_step(params, caches, tokens, positions):
        """tokens: (B, 1); positions: (B, 1) absolute positions."""
        h, caches, _ = lm_apply(params, cfg, tokens=tokens, positions=positions,
                                mode="decode", caches=caches)
        logits = lm_logits(params, cfg, h[:, -1:])[:, 0]
        return logits, caches

    # -------------------------------------------------- cache slot helpers
    # head/tail cache leaves carry batch on axis 0; scanned block caches are
    # stacked (n_blocks, batch, ...) so batch is axis 1.
    def cache_slice(caches, lo: int, hi: int):
        return {
            "head": jax.tree.map(lambda c: c[lo:hi], caches["head"]),
            "tail": jax.tree.map(lambda c: c[lo:hi], caches["tail"]),
            "blocks": jax.tree.map(lambda c: c[:, lo:hi], caches["blocks"]),
        }

    def cache_merge(caches, sub, lo: int):
        return {
            "head": jax.tree.map(lambda c, s: c.at[lo:lo + s.shape[0]].set(s),
                                 caches["head"], sub["head"]),
            "tail": jax.tree.map(lambda c, s: c.at[lo:lo + s.shape[0]].set(s),
                                 caches["tail"], sub["tail"]),
            "blocks": jax.tree.map(lambda c, s: c.at[:, lo:lo + s.shape[1]].set(s),
                                   caches["blocks"], sub["blocks"]),
        }

    def cache_scatter(caches, sub, src_map):
        """Pool slot s takes sub batch row src_map[s]; src_map[s] == -1
        keeps the pooled slot bit-exactly.  One fused scatter per leaf
        (kernels/kv_cache.cache_scatter_p on TPU) lands an entire bucketed
        prefill batch at once, replacing the per-request slice/merge loop.
        src_map shape: (pool_slots,) int32, values in [-1, sub_batch).
        """
        scat = kernel_ops.cache_scatter_rows
        return {
            "head": jax.tree.map(lambda c, s: scat(c, s, src_map),
                                 caches["head"], sub["head"]),
            "tail": jax.tree.map(lambda c, s: scat(c, s, src_map),
                                 caches["tail"], sub["tail"]),
            "blocks": jax.tree.map(lambda c, s: scat(c, s, src_map, batch_axis=1),
                                   caches["blocks"], sub["blocks"]),
        }

    # ------------------------------------------------------ paged cache pool
    def paged_cache(batch: int, max_len: int, mem_len: int = 0,
                    page: int = 64) -> PagedCacheOps:
        """Build the device ops for a paged cache pool (see PagedCacheOps).

        Pageable leaves are found structurally: a leaf whose shape differs
        between ``init_caches(max_len)`` and ``init_caches(2 * max_len)``
        grows with the sequence, and the differing axis is its seq axis;
        everything else (SSM/conv state, sub-max_len window rings, encdec
        memories, ``len``) stays flat per-slot rows.  The physical pool
        replaces (batch, seq) with a single leading page axis: head/tail
        leaves become (n_pages, ..., page, ...), stacked block leaves
        (n_blocks, n_pages, ..., page, ...), so the existing
        ``distributed/sharding.serve_pool_specs`` row-axis specs shard the
        paged pool over 'data' unchanged.
        """
        assert max_len % page == 0, (
            f"page size {page} must divide max_len {max_len}")
        n_pp = max_len // page
        a = jax.eval_shape(lambda: init_caches(batch, max_len, mem_len))
        b = jax.eval_shape(lambda: init_caches(batch, 2 * max_len, mem_len))

        def classify(sa, sb, ba):
            diffs = [i for i, (x, y) in enumerate(zip(sa.shape, sb.shape))
                     if x != y]
            if not diffs:
                return _PageMeta("flat", shape=sa.shape, dtype=sa.dtype)
            assert len(diffs) == 1, (sa.shape, sb.shape)
            ax = diffs[0]
            S = sa.shape[ax]
            assert S % page == 0, (
                f"page size {page} does not divide seq extent {S} of cache "
                f"leaf {sa.shape}; pick a power-of-two page <= 128 that "
                f"divides max_len")
            assert S // page >= n_pp, (sa.shape, ax, page, n_pp)
            return _PageMeta("seq", seq_axis=ax, n_leaf=S // page,
                             shape=sa.shape, dtype=sa.dtype)

        secs = (("head", 0), ("tail", 0), ("blocks", 1))
        meta = {sec: jax.tree.map(functools.partial(classify, ba=ba),
                                  a[sec], b[sec]) for sec, ba in secs}
        # batch-1 pristine init: the gather scratch must start from each
        # leaf's INIT fill (``pos`` fills with -1 = empty-slot sentinel, not
        # zero), so unallocated page regions read bit-exactly like the
        # never-written region of a slot-row cache
        base1 = init_caches(1, max_len, mem_len)

        def tmap(fn, *trees):
            return {sec: jax.tree.map(functools.partial(fn, ba=ba),
                                      meta[sec], *(t[sec] for t in trees))
                    for sec, ba in secs}

        def init(n_pages: int):
            def one(m, *, ba):
                if m.kind == "flat":
                    return jnp.zeros(m.shape, m.dtype)
                shape = list(m.shape)
                shape[ba] = n_pages
                shape[m.seq_axis] = page
                return jnp.zeros(tuple(shape), m.dtype)
            return tmap(one)

        # the named scopes label these ops in a profiler trace (the op
        # name metadata of every HLO instruction they lower to)
        @jax.named_scope("paged_gather")
        def gather(pool, pt, lengths):
            """Physical pages -> a (B, ...) logical tree the unmodified
            decode step runs on.  pt: (B, n_pp) int32 page tables;
            lengths: (B,) written tokens per row.  -1 entries and pages at
            or beyond the write frontier gather nothing, leaving the
            scratch at the leaf's INIT fill - bit-exactly the
            never-written region of a slot-row cache.  The frontier mask
            also launders recycled pages: a page freshly allocated for
            decode growth (still holding its previous owner's bytes) is
            masked on first gather, written through the logical scratch,
            and comes back fully cleaned by ``writeback``."""
            B = pt.shape[0]
            keep = (jnp.arange(pt.shape[1], dtype=jnp.int32)[None, :] * page
                    ) < lengths[:, None]
            pt = jnp.where(keep, pt, -1)

            def one(m, pool_leaf, b1, *, ba):
                if m.kind == "flat":
                    return pool_leaf
                shape = list(m.shape)
                shape[ba] = B                  # local batch under shard_map
                z = jnp.broadcast_to(b1, tuple(shape))
                zp = kernel_ops.to_page_rows(z, m.seq_axis, page,
                                             batch_axis=ba)
                gmap = jnp.full((B, m.n_leaf), -1, jnp.int32)
                gmap = gmap.at[:, :pt.shape[1]].set(pt).reshape(B * m.n_leaf)
                out = kernel_ops.cache_scatter_pages(zp, pool_leaf, gmap,
                                                     batch_axis=ba)
                return kernel_ops.from_page_rows(out, tuple(shape),
                                                 m.seq_axis, page,
                                                 batch_axis=ba)
            return tmap(one, pool, base1)

        @jax.named_scope("paged_writeback")
        def writeback(pool, logical, pt, positions, n_steps=None,
                      max_steps: int = 1):
            """Scatter each live row's decode-written pages back into the
            pool: the pages holding positions ``positions[b]`` through
            ``positions[b] + n_steps[b] - 1`` (the N-step block a fused
            decode dispatch wrote; ``n_steps=None`` is the single-step
            case).  ``max_steps`` is the STATIC block bound, fixing the
            per-row window at ``W = (max_steps + page - 2) // page + 1``
            candidate pages (W == 1 reduces exactly to the old single-page
            map).  Whole pages are written, so a recycled page comes back
            fully cleaned (init fill beyond the last written token - the
            gather laundered it).  Free slots (-1 table entries) and
            beyond-window candidates land on the write-only DUMP page 0,
            where colliding writes are harmless: page 0 is never read."""
            B = pt.shape[0]
            p0 = positions[:, 0]
            j0 = jnp.clip(p0 // page, 0, pt.shape[1] - 1)
            if n_steps is None:
                j1 = j0
            else:
                last = p0 + jnp.maximum(n_steps, 1) - 1
                j1 = jnp.clip(last // page, 0, pt.shape[1] - 1)
            W = (int(max_steps) + page - 2) // page + 1

            def one(m, pool_leaf, lg, *, ba):
                if m.kind == "flat":
                    return lg                  # flat state IS the new rows
                N = pool_leaf.shape[ba]
                wmap = jnp.full((N,), -1, jnp.int32)
                for w in range(W):
                    jb = jnp.minimum(j0 + w, pt.shape[1] - 1)
                    valid = (j0 + w) <= j1
                    ent = pt[jnp.arange(B), jb]
                    tgt = jnp.where(valid & (ent > 0), ent, 0)
                    val = jnp.where(valid,
                                    jnp.arange(B) * m.n_leaf + jb, -1)
                    wmap = wmap.at[tgt].set(val)
                lp = kernel_ops.to_page_rows(lg, m.seq_axis, page,
                                             batch_axis=ba)
                return kernel_ops.cache_scatter_pages(pool_leaf, lp, wmap,
                                                      batch_axis=ba)
            return tmap(one, pool, logical)

        def land(pool, sub, src_map, land_rows, land_js):
            """Land a bucketed prefill batch: flat leaves scatter whole
            slot rows through ``src_map`` (the existing semantics); paged
            leaves scatter pages - pool page p takes page ``land_js[p]``
            of scratch row ``land_rows[p]`` (-1 keeps; shared prefix pages
            are excluded by the planner)."""
            def one(m, pool_leaf, s, *, ba):
                if m.kind == "flat":
                    return kernel_ops.cache_scatter_rows(pool_leaf, s,
                                                         src_map,
                                                         batch_axis=ba)
                lmap = jnp.where(land_rows >= 0,
                                 land_rows * m.n_leaf + land_js, -1)
                sp = kernel_ops.to_page_rows(s, m.seq_axis, page,
                                             batch_axis=ba)
                return kernel_ops.cache_scatter_pages(pool_leaf, sp, lmap,
                                                      batch_axis=ba)
            return tmap(one, pool, sub)

        def copy(pool, copy_map):
            """Pool-internal page copy (the COW arm): page p takes page
            ``copy_map[p]`` (-1 keeps)."""
            def one(m, pool_leaf, *, ba):
                if m.kind == "flat":
                    return pool_leaf
                return kernel_ops.cache_scatter_pages(pool_leaf, pool_leaf,
                                                      copy_map,
                                                      batch_axis=ba)
            return tmap(one, pool)

        def capture(pool, slot: int, page_ids):
            """Host (numpy) copy of one request's pages - padded to n_pp
            so the restore program compiles once - plus its flat per-slot
            rows: the spill record's payload."""
            ids = jnp.asarray(np.asarray(page_ids, np.int32))
            k = int(ids.shape[0])

            def one(m, pool_leaf, *, ba):
                if m.kind == "flat":
                    sel = pool_leaf[slot:slot + 1] if ba == 0 else \
                        pool_leaf[:, slot:slot + 1]
                    return np.asarray(sel)
                sel = np.asarray(jnp.take(pool_leaf, ids, axis=ba))
                pad = list(sel.shape)
                pad[ba] = n_pp - k
                return np.concatenate(
                    [sel, np.zeros(pad, sel.dtype)], axis=ba)
            return tmap(one, pool)

        def restore(pool, rec, pmap, src_map):
            """Scatter a spill record back in: paged leaves from its
            captured (n_pp-padded) pages through ``pmap`` (pool page ->
            record page index, -1 keeps), flat leaves from its captured
            rows through ``src_map`` (slot -> record row 0, -1 keeps)."""
            def one(m, pool_leaf, rv, *, ba):
                if m.kind == "flat":
                    return kernel_ops.cache_scatter_rows(pool_leaf, rv,
                                                         src_map,
                                                         batch_axis=ba)
                return kernel_ops.cache_scatter_pages(pool_leaf, rv, pmap,
                                                      batch_axis=ba)
            return tmap(one, pool, rec)

        return PagedCacheOps(page=page, n_pp=n_pp, meta=meta, init=init,
                             gather=gather, writeback=writeback, land=land,
                             copy=copy, capture=capture, restore=restore)

    # ------------------------------------------------------------ dry-run IO
    def input_specs(shape_name: str) -> dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every input of the step function."""
        sp = SHAPES[shape_name]
        f32, i32 = jnp.float32, jnp.int32
        P = _patch_count(cfg)
        if sp.kind == "train":
            S_text = sp.seq - P
            specs = {
                "tokens": jax.ShapeDtypeStruct((sp.batch, S_text), i32),
                "labels": jax.ShapeDtypeStruct((sp.batch, S_text), i32),
            }
            if cfg.frontend == "vision":
                specs["patches"] = jax.ShapeDtypeStruct((sp.batch, P, cfg.d_model), dtype)
            if cfg.family == "encdec":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (sp.batch, _src_len(cfg, sp.seq), cfg.d_model), dtype)
            return specs
        if sp.kind == "prefill":
            S_text = sp.seq - P
            specs = {
                "tokens": jax.ShapeDtypeStruct((sp.batch, S_text), i32),
            }
            if cfg.frontend == "vision":
                specs["patches"] = jax.ShapeDtypeStruct((sp.batch, P, cfg.d_model), dtype)
            if cfg.family == "encdec":
                specs["frames"] = jax.ShapeDtypeStruct(
                    (sp.batch, _src_len(cfg, sp.seq), cfg.d_model), dtype)
            return specs
        # decode: one new token against a seq-length cache
        mem_len = _src_len(cfg, sp.seq) if cfg.family == "encdec" else 0
        caches = jax.eval_shape(lambda: init_caches(sp.batch, sp.seq, mem_len))
        return {
            "tokens": jax.ShapeDtypeStruct((sp.batch, 1), i32),
            "positions": jax.ShapeDtypeStruct((sp.batch, 1), i32),
            "caches": caches,
        }

    return ModelBundle(cfg=cfg, init=init, train_loss=train_loss,
                       prefill=prefill, decode_step=decode_step,
                       init_caches=init_caches, input_specs=input_specs,
                       cache_slice=cache_slice, cache_merge=cache_merge,
                       prefill_many=prefill_many, cache_scatter=cache_scatter,
                       prefill_chunk=prefill_chunk, paged_cache=paged_cache)
