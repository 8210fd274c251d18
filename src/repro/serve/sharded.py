"""Mesh-distributed serving: data+tensor-parallel ``ShardedServeEngine``.

The engine extends the single-device ``ServeEngine`` scheduler core to a
jax device mesh with axes ``('data', 'model')``:

  * **data axis - replicas.**  The pooled KV/conv/SSM cache's slot axis is
    sharded over 'data' (``distributed/sharding.serve_pool_specs``): each
    of the ``data`` replicas owns a contiguous block of
    ``slots_per_replica`` cache rows.  ``prefill_many``, ``prefill_chunk``,
    ``cache_scatter`` and the decode step run as ONE shard_map-ed SPMD
    program spanning every replica - inside the body each replica executes
    the single-device program on its own slot block, so replica numerics
    match the single-device engine computing that block.  One qualifier:
    MoE expert capacity is sized from the LOCAL token count (spr rows, not
    the pool), so under a capacity_factor tight enough to drop tokens the
    drops can differ from a pool-wide batch - the same caveat class as
    batch-size-dependent capacity on one device (DESIGN.md Sec. 4);
    parity is exact while capacity absorbs the routing, which the default
    factors guarantee.
  * **model axis - tensor parallelism.**  Inside the shard_map body,
    ``kernels/ops.tp_shard`` column-splits every PDQ / fp projection over
    'model': the PDQ prologue's per-row scales (and surrogate moments) are
    computed locally on each shard (they are O(K) per row and every shard
    needs them), each shard runs the grouped W8A8 matmul over its N-column
    block with its slice of the per-(row, N-block) interval epilogue, and
    a tiled all-gather reassembles the columns.  Every output column is
    the identical full-K int8 accumulation + epilogue the single-device
    kernel runs, so quantized numerics stay bit-exact.
  * **coordinator.**  Admission stays a host-side singleton (the scheduler
    core): one pending queue, bucket-grouped FIFO admits, and per-bucket
    routing of admits to the least-loaded replicas (``_assign``).  One
    admission round = one SPMD prefill launch that lands every replica's
    admits at once; replicas with fewer admits carry dummy rows the
    scatter drops.  ``src_map`` is replica-local by the scheduler-core
    convention, so the per-replica scatter blocks resolve correctly.

CPU CI exercises the whole engine on a virtual mesh via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see
tests/test_serve_sharded.py).

The scheduler itself lives in ``serve/core.py`` (plan builders + result
appliers); this class only rebinds the three exec hooks' device programs
to shard_map-ed equivalents.  ``serve/multihost.py`` extends THIS engine
to real ``jax.distributed`` multi-process meshes by shipping the plans
to worker processes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import pool_shardings, serve_pool_specs
from repro.kernels import ops

from .engine import DEFAULT_BUCKETS, ServeEngine, decode_scan


class ShardedServeEngine(ServeEngine):
    """ServeEngine over a ('data', 'model') mesh.

    ``slots_per_replica`` rows per data-parallel replica (total pool =
    ``data * slots_per_replica`` slots); params are replicated over the
    mesh and tensor-parallel execution splits projection columns over
    'model' at trace time, so one weight buffer layout serves any mesh
    shape.
    """

    def __init__(self, cfg, params, *, mesh, slots_per_replica: int = 4,
                 max_len: int = 256, quantize_weights: bool = False,
                 temperature: float = 0.0, rng: jax.Array | None = None,
                 buckets: tuple[int, ...] = DEFAULT_BUCKETS,
                 chunked_prefill: bool = False, decode_steps: int = 1,
                 fault=None,
                 pdq_fallback: bool = False, paged: bool = False,
                 page_size: int = 64, pool_pages: int | None = None,
                 prefix_sharing: bool = True, spill: bool = False,
                 telemetry: bool = True, trace: bool = False, tel=None):
        assert {"data", "model"} <= set(mesh.axis_names), mesh.axis_names
        assert not spill, (
            "host spill is single-device only: the capture/restore hooks "
            "address the pool globally, not through the mesh sharding")
        self.mesh = mesh
        self.data_size = int(mesh.shape["data"])
        self.model_size = int(mesh.shape["model"])
        super().__init__(cfg, params, slots=self.data_size * slots_per_replica,
                         max_len=max_len, quantize_weights=quantize_weights,
                         temperature=temperature, rng=rng, buckets=buckets,
                         batch_prefill=True, chunked_prefill=chunked_prefill,
                         decode_steps=decode_steps,
                         n_replicas=self.data_size, fault=fault,
                         pdq_fallback=pdq_fallback, paged=paged,
                         page_size=page_size, pool_pages=pool_pages,
                         prefix_sharing=prefix_sharing,
                         telemetry=telemetry, trace=trace, tel=tel)

    # ------------------------------------------------------- device programs
    def _sharded(self, fn, in_specs, out_specs, tel: bool = False):
        """shard_map(fn) over the mesh with TP (and, when enabled, the
        per-shard PDQ->fp fallback guard) active inside the body.

        ``tel=True`` additionally opens the pdq telemetry collector INSIDE
        the body (the TP/guard context is per-shard, so the collector must
        be too) and psums the (3,) health summary over both mesh axes: the
        launch returns ``(out, summary)`` with the summary replicated, so
        the coordinator reads fleet totals off the same device sync as the
        sampled tokens."""
        T = self.model_size
        guard = self.pdq_fallback
        collect = bool(tel) and self.tel.enabled

        def body(*args):
            with ops.tp_shard("model", T), ops.pdq_guard(guard), \
                    ops.pdq_telemetry(collect) as col:
                out = fn(*args)
                if not tel:
                    return out
                return out, jax.lax.psum(col.summary(), ("data", "model"))

        specs = (out_specs, P()) if tel else out_specs
        return jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                             out_specs=specs, check_vma=False)

    def _traced_sharded_jit(self, fn, counter: str, in_specs, out_specs,
                            donate: tuple[int, ...] = (), tel: bool = False,
                            out_shardings=None):
        stats = self.stats
        mapped = self._sharded(fn, in_specs, out_specs, tel=tel)

        def wrapped(*args):
            if counter:
                stats[counter] += 1      # trace-time side effect
            return mapped(*args)

        kw = {} if out_shardings is None else {"out_shardings": out_shardings}
        return jax.jit(wrapped, donate_argnums=donate, **kw)

    def _traced_decode_sharded(self, fn, in_specs, donate: tuple[int, ...],
                               out_shardings=None):
        """shard_map + jit for the fused decode block (the sharded analogue
        of ServeEngine._traced_decode).  ``fn`` is a decode_scan-shaped
        body returning (toks, ok, state, tel): telemetry is collected
        INSIDE the scan (per iteration, per shard), so this wrapper only
        opens tp_shard/pdq_guard around it and psums the block-summed
        (3,) health vector over both mesh axes.  Sampling runs in-body:
        each replica samples its OWN slot block with the per-(uid, step)
        keys - the per-row keys make that bit-identical to global
        sampling, and the launch returns (slots, N) int32 tokens instead
        of gathering a replicated (slots, vocab) logits batch."""
        T = self.model_size
        guard = self.pdq_fallback
        dp = P("data")

        def body(*args):
            with ops.tp_shard("model", T), ops.pdq_guard(guard):
                toks, ok, state, tel = fn(*args)
            return toks, ok, state, jax.lax.psum(tel, ("data", "model"))

        mapped = jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                               out_specs=(dp, dp, serve_pool_specs(self.caches),
                                          P()),
                               check_vma=False)
        stats = self.stats

        def wrapped(*args):
            stats["decode_compiles"] += 1      # trace-time side effect
            return mapped(*args)

        kw = {} if out_shardings is None else {"out_shardings": out_shardings}
        return jax.jit(wrapped, donate_argnums=donate, **kw)

    def _sampled_prefill(self, fn):
        """Wrap a prefill-shaped body so it samples in-body: each replica
        samples its own rows right where the logits live, so the launch
        ships (slots,) tokens + ok flags instead of (slots, vocab) logits.
        fn(params, *args) -> (logits, sub) becomes
        wrapped(rng, params, *args, uids, steps) -> (toks, ok, sub)."""
        sample = self._sample_fn()

        def wrapped(rng, params, *rest):
            *args, uids, steps = rest
            logits, sub = fn(params, *args)
            toks, ok = sample(rng, logits, uids, steps)
            return toks, ok, sub

        return wrapped

    def _build_jitted(self):
        cs = serve_pool_specs(self.caches)
        dp = P("data")                       # slot/batch axis over replicas
        # N-step fused decode: scan + in-body sampling, one dispatch per
        # token BLOCK (see engine.decode_scan); state/tokens/positions/row
        # metadata all split over 'data', rng + params replicated
        self._decode = self._traced_decode_sharded(
            decode_scan(self.bundle.decode_step, self._sample_fn(),
                        self.decode_steps, self.tel.enabled),
            in_specs=(P(), P(), cs, dp, dp, dp, dp, dp), donate=())
        self._prefill_many = self._traced_sharded_jit(
            self._sampled_prefill(self.bundle.prefill_many),
            "prefill_compiles",
            in_specs=(P(), P(), dp, cs, dp, dp, dp), out_specs=(dp, dp, cs),
            tel=True)
        self._prefill_chunk = self._traced_sharded_jit(
            self._sampled_prefill(self.bundle.prefill_chunk),
            "chunk_compiles",
            in_specs=(P(), P(), dp, cs, dp, dp, dp, dp),
            out_specs=(dp, dp, cs), tel=True)
        self._scatter = self._traced_sharded_jit(
            self.bundle.cache_scatter, None,
            in_specs=(cs, cs, dp), out_specs=cs, donate=(0,))
        # the legacy per-request path is single-replica only (asserted in
        # the scheduler core); no _prefill_one on the mesh.
        self._prefill_one = None
        if self.paged:
            self._build_paged_jitted()

        # place the long-lived buffers once: params replicated over the
        # whole mesh, cache pools with their slot axis over 'data' (later
        # launches then never re-transfer them from the host).  The paged
        # pool's leading axis is PAGES, not slots, but serve_pool_specs
        # shards that same axis over 'data' - each replica owns its
        # pool_pages block, matching the scheduler's replica-local page ids.
        self.params = jax.device_put(self.params,
                                     NamedSharding(self.mesh, P()))
        pool_sh = pool_shardings(self.mesh, self.caches)
        self.caches = jax.device_put(self.caches, pool_sh)
        self._prefill_pool = jax.device_put(
            self._prefill_pool,
            pool_shardings(self.mesh, self._prefill_pool))

    def _build_paged_jitted(self):
        """Paged-pool programs as ONE shard_map-ed SPMD launch each: the
        plan ships replica-LOCAL page ids, the 'data' split hands every
        replica its own pool-page block + its rows of the maps, and the
        body runs the identical single-device gather/step/writeback (or
        land / copy) on local indices."""
        po = self._paged_ops
        cs = serve_pool_specs(self.caches)
        dp = P("data")
        pts = P("data", None)                # (slots, n_pp) page tables
        self._decode_paged = self._traced_decode_sharded(
            self._paged_decode_fn(),
            in_specs=(P(), P(), cs, pts, dp, dp, dp, dp, dp),
            donate=(2,))
        self._land = self._traced_sharded_jit(
            po.land, None, in_specs=(cs, cs, dp, dp, dp), out_specs=cs,
            donate=(0,))
        self._page_copy = self._traced_sharded_jit(
            po.copy, None, in_specs=(cs, dp), out_specs=cs, donate=(0,))

    # ------------------------------------------------------------ exec hooks
    # prefill sampling runs in-body on the mesh (each replica samples its
    # own rows), so the launch protocol differs from the single-device
    # engine's host-side _sample_rows: tokens/ok come back directly and
    # fault poisoning flips the ok rows host-side instead of NaN-ing logits
    def _exec_prefill(self, plan, extras):
        batch = self._extras_batch({"tokens": jnp.asarray(plan.tokens)},
                                   extras)
        (toks, ok, sub), tel = self._prefill_many(
            self.rng, self.params, batch, self._prefill_pool,
            jnp.asarray(plan.seq_lens),
            jnp.asarray(plan.row_uids, jnp.int32),
            jnp.asarray(plan.row_steps, jnp.int32))
        self._land_sub(plan, sub)
        self._observe_pdq(tel)
        ok = self._poison_ok("prefill", plan, np.asarray(ok))
        return np.asarray(toks), ok

    def _exec_chunked(self, plan, extras):
        if extras:
            raise NotImplementedError(
                "chunked prefill is text-only (no vision/encdec extras)")
        uids = jnp.asarray(plan.row_uids, jnp.int32)
        steps = jnp.asarray(plan.row_steps, jnp.int32)
        _, tokens, seq_lens = plan.first
        (toks, ok, sub), tel = self._prefill_many(
            self.rng, self.params, {"tokens": jnp.asarray(tokens)},
            self._prefill_pool, jnp.asarray(seq_lens), uids, steps)
        for _, tokens, seq_lens, start_lens in plan.chunks:
            # intermediate chunks sample throwaway tokens (same per-row
            # keys, discarded logits) - only the final chunk's row matters
            (toks, ok, sub), t2 = self._prefill_chunk(
                self.rng, self.params, {"tokens": jnp.asarray(tokens)}, sub,
                jnp.asarray(seq_lens), jnp.asarray(start_lens), uids, steps)
            tel = tel + t2        # lazy device add: one fetch per launch set
        self._land_sub(plan, sub)
        self._observe_pdq(tel)
        ok = self._poison_ok("chunked", plan, np.asarray(ok))
        return np.asarray(toks), ok
