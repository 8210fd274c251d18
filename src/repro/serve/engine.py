"""Batched serving engine: bucketed batched prefill + continuous batching.

Production features:
  * fixed-slot KV cache pool with per-slot lengths (continuous batching -
    new requests claim freed slots without recompiling);
  * bucketed, batched prefill: prompts are right-padded to a small static
    set of length buckets, so an engine lifetime compiles at most
    ``len(buckets)`` prefill executables (the per-request path recompiled
    per distinct prompt length), and every admission round prefills ALL
    admissible same-bucket requests in ONE ``bundle.prefill_many`` call -
    the grouped PDQ prologue/matmul pipeline then runs at real batch sizes
    during prefill too.  The finished rows land in the pooled cache via one
    fused ``bundle.cache_scatter`` (kernels/kv_cache.cache_scatter_p);
  * an explicit admission scheduler (serve/core.py SchedulerCore): a
    deque-based pending queue, bucket-grouped admits in FIFO order,
    per-replica free-slot deques, least-loaded replica routing, and
    per-step accounting in ``engine.stats``;
  * chunked prefill (``chunked_prefill=True``): prompts longer than the
    largest bucket are split into bucket-sized chunks instead of compiling
    a cache-capacity-sized executable;
  * greedy or temperature sampling;
  * optional PDQ-int8 weight path (``quantize_weights=True``; see
    models/linops.py and DESIGN.md Sec. 2) and optional int8 KV cache
    (cfg.quant_kv='dynamic', kernels/kv_cache.py).

The scheduler lives in ``serve/core.py`` as plan builders + result
appliers; this class binds the plans to single-device jit programs.  With
``n_replicas=1`` (this class) the engine is the single-device engine;
``serve/sharded.py`` runs the same schedule over a ('data', 'model')
device mesh and ``serve/multihost.py`` over a ``jax.distributed``
multi-process mesh.

Padding never leaks: pad tokens are masked out of attention by causality,
skipped exactly by the SSM recurrence (dt=0), masked out of MoE routing
(models/moe.route token_mask), and their cache writes are redirected onto
the row's last real token (models/attention._clamp_padded), so a bucketed
prefill is bit-identical to an unpadded one.  Dummy rows of a
partially-filled prefill batch carry seq_lens == 0 and are masked out the
same way end to end - they claim no MoE expert capacity (PR-5 fix; the
scatter drops their cache rows regardless).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.fault import FaultInjector
from repro.kernels import ops
from repro.models import build_model
from repro.models.linops import quantize_param_tree

from . import telemetry as tmod
from .core import (DEFAULT_BUCKETS, ChunkedPlan, DecodePlan, PrefillPlan,
                   Request, SchedulerCore)
from .pages import SpillRecord

__all__ = ["DECODE_PAD", "DEFAULT_BUCKETS", "Request", "ServeEngine"]

# token-block sentinel: steps a row did not consume (its per-row budget ran
# out before the block did) come back as this instead of a sampled id.
# Token ids are non-negative, so -1 is unambiguous; the scheduler's apply
# loop never reads padded steps, and the multi-host token tracker treats it
# as end-of-row
DECODE_PAD = -1


def prefill_row_ladder(slots: int) -> tuple[int, ...]:
    """Row counts of a single-device engine's bucketed prefill launches,
    ascending: ``slots`` and every fourth part of it down to 1 or 2 rows
    (64 slots: 1, 4, 16, 64).  A launch costs about its rows, and each
    count is one more compiled prefill per length bucket at set-up; below
    32 slots a launch of every slot row costs at most 16x one of a single
    row, and the engine keeps that one shape."""
    if slots < 32:
        return (slots,)
    rows = [slots]
    while rows[-1] >= 4:
        rows.append(rows[-1] // 4)
    return tuple(sorted(rows))


def decode_scan(step_fn, sample_fn, n_block: int, collect: bool):
    """Build the N-step fused decode body shared by every engine: a
    ``lax.scan`` of ``n_block`` model steps carrying (cache state, token,
    position) ON DEVICE, sampling each step in-program with the per-(uid,
    step) keys, so one host dispatch consumes N decode rounds.

    Per-row budgets ride in ``n_steps``: a row past its budget FREEZES -
    it re-feeds its last token at its last position (rewriting one cache
    position with identical content, a bit-exact no-op) and emits
    ``DECODE_PAD``/ok=True, so every row costs the same FLOPs and the
    block stays one static executable.  PDQ telemetry is collected INSIDE
    the body (the collector's scalars must be traced per iteration) and
    summed over the block; ``pdq_guard``/``tp_shard`` are trace-time only
    and wrap the whole scan at the call site.

    Returns ``run(rng, params, state, tokens, positions, uids, steps,
    n_steps) -> (toks (B, N), ok (B, N), state, tel)``, ``tel`` the
    block-summed telemetry summary (``ops.TEL_FIELDS``).
    """
    def run(rng, params, state, tokens, positions, uids, steps, n_steps):
        def body(carry, t):
            state, tok, pos = carry
            with ops.pdq_telemetry(collect) as col:
                logits, state = step_fn(params, state, tok, pos)
                tel = col.summary()
            nxt, okt = sample_fn(rng, logits, uids, steps + t)
            act = t < n_steps
            otok = jnp.where(act, nxt, DECODE_PAD).astype(jnp.int32)
            ook = jnp.where(act, okt, True)
            ntok = jnp.where(act, nxt, tok[:, 0]).astype(tok.dtype)[:, None]
            npos = jnp.where(act, pos[:, 0] + 1, pos[:, 0])[:, None]
            return (state, ntok, npos), (otok, ook, tel)

        (state, _, _), (toks, oks, tels) = jax.lax.scan(
            body, (state, tokens, positions),
            jnp.arange(n_block, dtype=jnp.int32))
        return (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(oks, 0, 1), state,
                jnp.sum(tels, axis=0))

    return run


class ServeEngine(SchedulerCore):
    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 256,
                 quantize_weights: bool = False, temperature: float = 0.0,
                 rng: jax.Array | None = None,
                 buckets: tuple[int, ...] = DEFAULT_BUCKETS,
                 batch_prefill: bool = True,
                 chunked_prefill: bool = False,
                 decode_steps: int = 1,
                 n_replicas: int = 1,
                 fault: FaultInjector | None = None,
                 pdq_fallback: bool = False,
                 paged: bool = False,
                 page_size: int = 64,
                 pool_pages: int | None = None,
                 prefix_sharing: bool = True,
                 spill: bool = False,
                 telemetry: bool = True,
                 trace: bool = False,
                 tel: "tmod.Telemetry | None" = None):
        self.cfg = cfg
        self.bundle = build_model(cfg)
        self.params = (quantize_param_tree(params) if quantize_weights
                       else params)
        self.temperature = temperature
        # the BASE sampling key: never split or advanced.  Every sampled
        # token derives its key as fold_in(fold_in(rng, uid), step), so a
        # request's token stream depends only on (rng, uid, prompt, step) -
        # not on batch composition, chunking, engine restarts, or which
        # other requests shared its launches.  That is what makes chunked
        # == unchunked temperature streams and drain-resume regeneration
        # token-exact.
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.pdq_fallback = bool(pdq_fallback)
        mem_len = 8 if cfg.family == "encdec" else 0
        self.mem_len = mem_len
        if tel is None:
            tel = tmod.Telemetry(enabled=telemetry, trace=trace)
        self._init_scheduler(
            slots=slots, n_replicas=n_replicas, max_len=max_len,
            patch_tokens=(cfg.frontend_tokens if cfg.frontend == "vision"
                          else 0),
            buckets=buckets, batch_prefill=batch_prefill,
            chunked_prefill=chunked_prefill, decode_steps=decode_steps,
            fault=fault, tel=tel)
        self.prefill_rows = self._prefill_rows()
        self._warm_buckets: set[int] = set()
        if paged:
            assert batch_prefill, "the paged pool needs the bucketed path"
            self._paged_ops = self.bundle.paged_cache(
                slots, max_len, mem_len, page_size)
            n_pp = self._paged_ops.n_pp
            if pool_pages is None:
                # headroom parity with the slot-row pool (+1 dump page):
                # every slot can hold a full sequence simultaneously
                pool_pages = self.slots_per_replica * n_pp + 1
            self._init_paging(page_size=page_size, pool_pages=pool_pages,
                              n_pp=n_pp, prefix_sharing=prefix_sharing,
                              spill=spill)
        self._init_pools()
        self._build_sampler()
        self._build_jitted()

    def _prefill_rows(self) -> tuple[int, ...]:
        """The row ladder of this engine's prefill launches (the mesh
        engines keep one launch of every slot row)."""
        return prefill_row_ladder(self.slots)

    def _init_pools(self):
        """Allocate the serving cache pools.  The multi-host engine
        overrides this with shape-only stand-ins (its pools are created
        directly on the global mesh, so host allocations would be waste).
        """
        if self.paged:
            # physical page pool: (pool_pages, ..., page, ...) per paged
            # leaf, (slots, ...) rows for flat leaves (see models/api.py)
            self.caches = self._paged_ops.init(
                self.pool_pages * self.n_replicas)
        else:
            self.caches = self.bundle.init_caches(self.slots, self.max_len,
                                                  self.mem_len)
        # one spare cache pool fed to every prefill_many call: prefill is
        # functional, so the same zero pool is reused forever and the
        # written rows are landed into self.caches by cache_scatter.
        if self.batch_prefill:
            self._prefill_pool = self.bundle.init_caches(
                self.slots, self.max_len, self.mem_len)
        else:
            # legacy path: a single zero row - a new request must prefill
            # from an EMPTY cache row, not the freed slot's stale one (the
            # int8 decode kernel masks by cache['len'], and _cache_write
            # keeps max(stale_len, new_len), so stale tokens would attend)
            self._fresh_row = self.bundle.init_caches(1, self.max_len,
                                                      self.mem_len)

    # ------------------------------------------------------- device programs
    def _build_jitted(self):
        """Compile wrappers for the device-facing programs.  The sharded
        engine overrides this with shard_map-ed equivalents; the scheduler
        (serve/core.py) is shared.
        """
        # the scheduler core emits replica-LOCAL src_map rows, which only a
        # replica-aware (shard_map-ed) scatter resolves - the single-device
        # scatter here would silently land the wrong batch rows
        assert self.n_replicas == 1, (
            "n_replicas > 1 requires replica-aware device programs; "
            "use serve.sharded.ShardedServeEngine")
        # the decode fast path: N model steps + in-program sampling fused
        # into ONE dispatch (see decode_scan); host round-trips per token
        # drop to 1/N and the block compiles once
        self._decode = self._traced_decode(decode_scan(
            self.bundle.decode_step, self._sample_fn(),
            self.decode_steps, self.tel.enabled))
        # the per-request prefill survives ONLY as the legacy baseline
        # (batch_prefill=False); the scheduler core never reaches it on the
        # bucketed path
        self._prefill_one = (None if self.batch_prefill else
                             self._traced_jit(self.bundle.prefill,
                                              "prefill_compiles"))
        self._prefill_many = self._traced_jit(self.bundle.prefill_many,
                                              "prefill_compiles")
        # a launch of fewer rows than slots starts from a zero pool of its
        # own rows, made in the program (the spare pool has every row)
        bundle, max_len, mem_len = self.bundle, self.max_len, self.mem_len

        def prefill_fresh(params, batch, seq_lens):
            pool = bundle.init_caches(batch["tokens"].shape[0], max_len,
                                      mem_len)
            return bundle.prefill_many(params, batch, pool, seq_lens)

        self._prefill_few = self._traced_jit(prefill_fresh, "prefill_compiles")
        self._prefill_chunk = self._traced_jit(self.bundle.prefill_chunk,
                                               "chunk_compiles")
        # the pooled cache is rebound to the scatter result immediately, so
        # donate it: the update lands in place instead of copying the whole
        # pool per admission (no-op off-TPU, where donation is unsupported)
        self._scatter = jax.jit(self.bundle.cache_scatter, donate_argnums=(0,))
        if self.paged:
            self._build_paged_jitted()

    def _paged_decode_fn(self):
        """The paged-pool fused decode body, shared by every engine: gather
        the live rows' pages into the logical layout ONCE, run the N-step
        scan on it, write each row's page WINDOW back (the block may cross
        a page boundary; writeback masks by per-row budget).  Same
        decode_scan return shape: (toks (B, N), ok, pool, tel)."""
        po = self._paged_ops
        N = self.decode_steps
        scan = decode_scan(self.bundle.decode_step, self._sample_fn(),
                           N, self.tel.enabled)

        def decode_paged(rng, params, pool, pt, tokens, positions, uids,
                         steps, n_steps):
            logical = po.gather(pool, pt, positions[:, 0])
            toks, ok, logical, tel = scan(rng, params, logical, tokens,
                                          positions, uids, steps, n_steps)
            pool = po.writeback(pool, logical, pt, positions,
                                n_steps=n_steps, max_steps=N)
            return toks, ok, pool, tel

        return decode_paged

    def _build_paged_jitted(self):
        """Paged-pool device programs: ONE fused decode launch per N-step
        block - no host round-trips beyond the numpy page tables the plan
        already ships."""
        po = self._paged_ops
        self._decode_paged = self._traced_decode(self._paged_decode_fn(),
                                                 donate=(2,))
        self._land = jax.jit(po.land, donate_argnums=(0,))
        self._page_copy = jax.jit(po.copy, donate_argnums=(0,))
        self._restore_prog = jax.jit(po.restore, donate_argnums=(0,))

    def _traced_jit(self, fn, counter: str, donate: tuple = ()):
        """jit(fn) that bumps ``stats[counter]`` once per (re)trace - i.e.
        once per compiled executable, the quantity the bucket design caps.

        Every launch also returns the telemetry summary (``ops.TEL_FIELDS``:
        guard fallbacks, int8 clip hits, clipped-output count, expert
        rows) folded device-side by ops.pdq_telemetry - pure jnp
        reductions, so the pallas_call census is unchanged and the scalars
        ride the existing token gather instead of adding a host
        round-trip.  With telemetry off the summary is a constant zeros
        vector."""
        stats = self.stats
        guard = self.pdq_fallback
        collect = self.tel.enabled

        def wrapped(*args):
            stats[counter] += 1      # trace-time side effect
            with ops.pdq_guard(guard), ops.pdq_telemetry(collect) as col:
                out = fn(*args)
                return out, col.summary()

        return jax.jit(wrapped, donate_argnums=donate)

    def _traced_decode(self, fn, donate: tuple = ()):
        """jit for the fused decode block.  Unlike _traced_jit it does NOT
        open pdq_telemetry here: the scan body collects per-iteration (the
        summary must be traced inside the body) and ``fn`` already returns
        the block-summed summary as its last element.  pdq_guard is
        trace-time only, so wrapping the whole scan is safe."""
        stats = self.stats
        guard = self.pdq_fallback

        def wrapped(*args):
            stats["decode_compiles"] += 1      # trace-time side effect
            with ops.pdq_guard(guard):
                return fn(*args)

        return jax.jit(wrapped, donate_argnums=donate)

    # -------------------------------------------------------------- sampling
    def _sample_fn(self):
        """The pure (rng, logits, uids, steps) -> (tokens, ok) sampling
        body: per-row sampled token + per-row all-finite flag.

        Keys are derived per ROW from (base rng, uid, step) so a token's
        randomness is a pure function of the request identity and its
        position in the stream - not of which launch sampled it.  That is
        what lets the SAME function serve the host-dispatched prefill
        sampler, the fused decode scan, and the per-replica shard_map
        bodies with token-exact outputs."""
        temp = float(self.temperature)

        def sample(rng, logits, uids, steps):
            ok = jnp.isfinite(logits).all(axis=-1)
            if temp <= 0.0:
                toks = jnp.argmax(logits, -1)
            else:
                def one(lg, uid, step):
                    k = jax.random.fold_in(jax.random.fold_in(rng, uid), step)
                    return jax.random.categorical(k, lg / temp)
                toks = jax.vmap(one)(logits, uids, steps)
            return toks, ok

        return sample

    def _build_sampler(self):
        """Jit the shared sampling body for the host-side prefill path (the
        base key is passed in, not closed over, so engines sharing
        temperature share the executable)."""
        self._sampler = jax.jit(self._sample_fn())

    def _sample_rows(self, kind: str, plan, logits):
        """Sample every batch row of a launch; returns device arrays
        (tokens (rows,), ok (rows,)) without waiting for them.  Applies
        the fault injector's logits poisoning first (no-op outside fault
        tests)."""
        rows = self.fault.poison_rows(kind, plan)
        if rows:
            logits = jnp.asarray(logits).at[np.asarray(rows)].set(jnp.nan)
        return self._sampler(self.rng, logits,
                             jnp.asarray(plan.row_uids, jnp.int32),
                             jnp.asarray(plan.row_steps, jnp.int32))

    def _extras_batch(self, batch: dict, extras) -> dict:
        if extras:
            # extras are shared across requests (seed semantics): broadcast
            # their leading batch dim across the prefill rows
            Bp = batch["tokens"].shape[0]
            batch.update(jax.tree.map(
                lambda a: jnp.broadcast_to(jnp.asarray(a)[:1],
                                           (Bp,) + jnp.asarray(a).shape[1:]),
                dict(extras)))
        return batch

    # ------------------------------------------------------------ exec hooks
    def _launch_prefill(self, batch: dict, seq_lens):
        if batch["tokens"].shape[0] == self.slots:
            return self._prefill_many(self.params, batch, self._prefill_pool,
                                      seq_lens)
        return self._prefill_few(self.params, batch, seq_lens)

    def _warm_prefill_rows(self, bucket: int, extras) -> None:
        """The first launch of a length bucket first runs it at every row
        count of the ladder, on dummy rows that land nothing: every shape
        the bucket can take compiles with the first request that needs it
        (a server's warm-up), never later."""
        if len(self.prefill_rows) == 1 or bucket in self._warm_buckets:
            return
        self._warm_buckets.add(bucket)
        keep = np.full((self.slots,), -1, np.int32)
        for rows in self.prefill_rows:
            batch = self._extras_batch(
                {"tokens": jnp.zeros((rows, bucket), jnp.int32)}, extras)
            (logits, sub), _ = self._launch_prefill(
                batch, jnp.zeros((rows,), jnp.int32))
            if self.paged:
                no_page = jnp.full((self.n_replicas * self.pool_pages,), -1,
                                   jnp.int32)
                self.caches = self._land(self.caches, sub, jnp.asarray(keep),
                                         no_page, no_page)
            else:
                self.caches = self._scatter(self.caches, sub,
                                            jnp.asarray(keep))
            dummy = jnp.full((rows,), -1, jnp.int32)
            jax.block_until_ready(self._sampler(self.rng, logits, dummy,
                                                dummy))

    def _exec_prefill(self, plan: PrefillPlan, extras):
        self._warm_prefill_rows(plan.bucket, extras)
        batch = self._extras_batch({"tokens": jnp.asarray(plan.tokens)},
                                   extras)
        with self._dispatch_span("prefill"):
            (logits, sub), tel = self._launch_prefill(
                batch, jnp.asarray(plan.seq_lens))
            self._land_sub(plan, sub)
            toks, ok = self._sample_rows("prefill", plan, logits)
        # the pdq summary rides the token gather: one fetch for all three
        return self._fetch("prefill", tel, toks, ok)

    def _land_sub(self, plan, sub) -> None:
        """Land a finished prefill batch in the pool: page-wise through the
        plan's land maps (paged), or whole slot rows (slot-row pool)."""
        if self.paged:
            self.caches = self._land(self.caches, sub,
                                     jnp.asarray(plan.src_map),
                                     jnp.asarray(plan.land_rows),
                                     jnp.asarray(plan.land_js))
        else:
            self.caches = self._scatter(self.caches, sub,
                                        jnp.asarray(plan.src_map))

    def _exec_chunked(self, plan: ChunkedPlan, extras):
        if extras:
            raise NotImplementedError(
                "chunked prefill is text-only (no vision/encdec extras)")
        _, tokens, seq_lens = plan.first
        with self._dispatch_span("chunked"):
            (logits, sub), tel = self._prefill_many(
                self.params, {"tokens": jnp.asarray(tokens)},
                self._prefill_pool, jnp.asarray(seq_lens))
            for _, tokens, seq_lens, start_lens in plan.chunks:
                (logits, sub), t2 = self._prefill_chunk(
                    self.params, {"tokens": jnp.asarray(tokens)}, sub,
                    jnp.asarray(seq_lens), jnp.asarray(start_lens))
                tel = tel + t2    # lazy device add: one fetch per launch set
            self._land_sub(plan, sub)
            toks, ok = self._sample_rows("chunked", plan, logits)
        return self._fetch("chunked", tel, toks, ok)

    def _exec_decode(self, plan: DecodePlan):
        with self._dispatch_span("decode"):
            row_args = (jnp.asarray(plan.row_uids, jnp.int32),
                        jnp.asarray(plan.row_steps, jnp.int32),
                        jnp.asarray(plan.n_steps, jnp.int32))
            if self.paged:
                toks, ok, self.caches, tel = self._decode_paged(
                    self.rng, self.params, self.caches,
                    jnp.asarray(plan.page_tables), jnp.asarray(plan.tokens),
                    jnp.asarray(plan.positions), *row_args)
            else:
                toks, ok, self.caches, tel = self._decode(
                    self.rng, self.params, self.caches,
                    jnp.asarray(plan.tokens), jnp.asarray(plan.positions),
                    *row_args)
        toks, ok = self._fetch("decode", tel, toks, ok)
        # fault poisoning moved host-side: sampling now runs in-program, so
        # the injector marks rows bad AFTER the launch instead of NaN-ing
        # logits before it (same observable effect: the row evicts)
        return toks, self._poison_ok("decode", plan, ok)

    # ------------------------------------------------------ paged-pool hooks
    def _copy_map(self, replica: int, pairs) -> np.ndarray:
        # positions are global (the 'data' shard split localizes them);
        # VALUES stay replica-local page ids - the copy body indexes the
        # replica's own pool shard
        cmap = np.full((self.pool_pages * self.n_replicas,), -1, np.int32)
        base = replica * self.pool_pages
        for src, dst in pairs:
            cmap[base + dst] = src
        return cmap

    def _exec_page_copy(self, replica: int, pairs) -> None:
        cmap = self._copy_map(replica, pairs)
        self.caches = self._page_copy(self.caches, jnp.asarray(cmap))

    def _exec_spill(self, slot: int, uid: int, page_ids) -> SpillRecord:
        return SpillRecord(uid=uid, n_pages=len(page_ids),
                           length=int(self.lengths[slot]),
                           last_token=int(self.last_tokens[slot]),
                           data=self._paged_ops.capture(self.caches, slot,
                                                        page_ids))

    def _exec_restore(self, slot: int, rec: SpillRecord, page_ids) -> None:
        pmap = np.full((self.pool_pages * self.n_replicas,), -1, np.int32)
        for i, p in enumerate(page_ids):
            pmap[p] = i                       # pool page p <- record page i
        smap = np.full((self.slots,), -1, np.int32)
        smap[slot] = 0                        # flat leaves: record row 0
        self.caches = self._restore_prog(self.caches, rec.data,
                                         jnp.asarray(pmap),
                                         jnp.asarray(smap))

    # ------------------------------------------------- legacy per-request path
    def _submit_one(self, req: Request, extras) -> bool:
        """Legacy per-request prefill (benchmark baseline): slice one slot,
        prefill a batch of 1 at the EXACT prompt length (so XLA compiles a
        fresh executable per distinct length), merge back."""
        if not self._free_total():
            return False
        S = len(req.prompt)
        self._bucket(S)       # same cache-capacity guard as the bucketed path
        slot = self._take_slot(0)
        sub_caches = self._fresh_row      # zero row, never mutated (pure fns)
        batch = {"tokens": jnp.asarray(np.asarray(req.prompt)[None], jnp.int32)}
        if extras:
            batch.update(extras)
        (logits, sub_caches), tel = self._prefill_one(self.params, batch,
                                                      sub_caches)
        self.caches = self.bundle.cache_merge(self.caches, sub_caches, slot)
        toks, ok = self._sampler(self.rng, logits,
                                 jnp.asarray([req.uid], jnp.int32),
                                 jnp.asarray([0], jnp.int32))
        self._observe_pdq(tel)
        if not bool(np.asarray(ok)[0]):
            self._release_slot(slot)
            self._fail(req, "non-finite logits at prefill", "nonfinite")
            return True
        tok = int(np.asarray(toks)[0])
        self.stats["replica_admits"][0] += 1
        self._activate(slot, req, S, int(tok))
        self.stats["prefill_batches"] += 1
        self.stats["prefill_requests"] += 1
        self.stats["prefill_tokens"] += S
        self.stats["prefill_padded_tokens"] += S
        return True
