"""Multi-process serving: ``MultiHostServeEngine`` over a ``jax.distributed``
mesh, with a coordinator protocol.

Topology.  N OS processes each own a slice of the global device set;
``launch/mesh.py`` lays them out contiguously along the 'data' axis of the
('data', 'model') serve mesh, so every data-parallel replica's cache-slot
block is addressable by exactly one process (``distributed/sharding.
process_replicas``).  All processes execute the SAME SPMD launch sequence
- multi-controller jax requires it - but scheduling is NOT replicated:

  * **coordinator (process 0)** runs the scheduler core (serve/core.py)
    as a host-side singleton: the pending queue, bucket grouping and
    least-loaded replica routing live only there, exactly as on one
    process.  Each device launch it decides is announced to the workers
    as a COMMAND: a fixed-shape int32 header (opcode + bucket length)
    followed by the plan's numpy payload, both shipped by a one-to-all
    psum broadcast that blocks on every local shard (see ``_broadcast``).
  * **workers (process > 0)** run ``serve_worker()``: receive a command,
    execute the identical launch, repeat until CMD_STOP.  They hold no
    scheduler state - just the global cache pool (of which they
    physically store their replicas' shards) and the in-flight chunked
    sub-pool.

Collective fast path.  Sampling runs IN-PROGRAM per replica (inside the
shard_map body, like ``ShardedServeEngine``): a host-side sample would
force a device->host gather of the (slots, vocab) logits, which across
processes is not even addressable.  Decode additionally runs as an
N-step fused block (``engine.decode_scan``): ONE broadcast + ONE device
launch consumes up to ``decode_steps`` tokens per row, and the jit's
replicated out_sharding makes XLA broadcast the (slots, N) sampled token
block + ok flags to every device via an in-program all-gather - every
process then reads the full block from its local shard, no host-side
device gathers, and command-stream traffic per token drops to 1/N.
Because each replica samples over exactly the logits the single-process
engine computed (PDQ column-TP epilogue included), tokens stay bit-exact
vs ``ShardedServeEngine`` on the same logical mesh, fp and int8.

Failure handling (see DESIGN.md "Failure handling").  The command header
carries a monotonically increasing sequence number and a per-process ack
slot: every process CONTRIBUTES to the header exchange (coordinator: the
command; worker p: its last-completed seq in slot p), so each command
doubles as a fleet heartbeat - the coordinator verifies every worker
acked the previous command before the new one executes, and a desynced
worker is a typed ``ProtocolError`` instead of a silent hang.  Aborts are
typed: ``CMD_ABORT`` ships a reason code (exception / deadline / desync)
and workers raise ``CoordinatorAbort`` carrying it.  Every blocking
broadcast and device launch is armed with a ``DeadlineWatchdog``
(``launch_timeout=`` seconds; None disarms): a thread blocked inside a
gloo collective cannot be interrupted, so on expiry a side thread dumps
the coordinator's scheduler snapshot (if ``snapshot_path`` is set),
prints a typed ABORT_DEADLINE line and ``os._exit``s with
``fault.EXIT_DEADLINE`` - the launcher (launch/serve.py) then reports
which process timed out, and a later run resumes from the snapshot.
Exec-launch exceptions are NOT isolated per request here
(``_isolate_exec = False``): a coordinator that kept scheduling after a
failed collective would desync the fleet, so protocol errors are
fleet-fatal and recovery is drain-and-resume.
"""
from __future__ import annotations

import collections
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.fault import DeadlineWatchdog, _default_deadline_abort, \
    save_snapshot
from repro.distributed.sharding import (make_global, pool_shardings,
                                        process_replicas, serve_pool_specs)

from . import telemetry as tmod
from .core import ChunkedPlan, DecodePlan, PrefillPlan, Request
from .engine import DECODE_PAD, DEFAULT_BUCKETS, decode_scan
from .sharded import ShardedServeEngine

# coordinator -> worker opcodes.  Header: int32[4 + 3 * n_processes] =
# [op, arg, seq, n_extras, ack_0..ack_{n-1}, ing_0..ing_{n-1},
#  tim_0..tim_{n-1}] - arg is
# the bucket length (prefill/chunk), the abort reason code, or the source
# process (ingress pull); seq numbers every command; ack_p is process p's
# last-completed command seq (the heartbeat); ing_p is the length of
# process p's local ingress queue (worker-side submits awaiting pickup),
# so EVERY command exchange doubles as an ingress announcement and the
# coordinator never needs a side channel to learn about remote submits;
# tim_p is the wall time (microseconds, int32-clamped) process p spent
# executing its PREVIOUS command - the telemetry piggyback.  The
# coordinator attributes slot p to the kind of the command it issued one
# seq earlier, folds it into per-process fleet launch histograms and,
# when tracing, reconstructs a retroactive worker span (ts = arrival -
# duration on the coordinator clock - no clock sync, good enough to read
# phase overlap).  Timing costs ZERO extra collectives: it rides the
# header exchange every command already performs.
CMD_STOP = 0
CMD_PREFILL = 1        # payload: tokens (slots, L), seq_lens, src_map,
                       #          row_uids, row_steps [+ n_extras arrays,
                       #          each a shape-tag header then the values]
CMD_CHUNK_FIRST = 2    # payload: tokens (slots, L), seq_lens, row_uids,
                       #          row_steps (kept for the later chunks)
CMD_CHUNK_NEXT = 3     # payload: tokens (slots, L), seq_lens, start_lens
CMD_CHUNK_END = 4      # payload: src_map
CMD_DECODE = 5         # payload: tokens (slots, 1), positions (slots, 1),
                       #          row_uids, row_steps, n_steps (per-row
                       #          block budgets); arg = the block size N
                       #          (lockstep-verified by every worker)
CMD_ABORT = 6          # coordinator died: workers raise (arg = reason)
CMD_INGRESS = 7        # pull process arg's queued submits: count int32[1]
                       # from arg, then per request meta int32[4] =
                       # [uid, prompt_len, max_new, deadline_ms] + prompt
CMD_POLL = 8           # no-op rendezvous: harvest acks + ingress counts
                       # while the scheduler is otherwise idle
CMD_PAGE_COPY = 9      # paged pool COW copy: payload copy map
                       # (n_replicas * pool_pages,) int32, -1 = keep

# opcode -> launch kind for the header timing piggyback (commands whose
# worker-side execution is a device launch worth a histogram/span; polls,
# ingress pulls and the chunk-end scatter are protocol overhead)
_CMD_KINDS = {CMD_PREFILL: "prefill", CMD_CHUNK_FIRST: "chunked",
              CMD_CHUNK_NEXT: "chunked", CMD_DECODE: "decode",
              CMD_PAGE_COPY: "page_copy"}

# extras keys the prefill payload can carry (shape-tag header word 0);
# float32 values ride the int32 psum exchange losslessly via a bitcast
# (every non-source process contributes zeros, and zeros-sum preserves
# the source's bit pattern exactly)
_EXTRA_KEYS = {"frames": 1, "patches": 2}
_EXTRA_IDS = {v: k for k, v in _EXTRA_KEYS.items()}

# typed abort reasons (CMD_ABORT arg)
ABORT_EXC = 1          # coordinator raised while scheduling
ABORT_DEADLINE = 2     # a deadline watchdog fired fleet-side
ABORT_DESYNC = 3       # heartbeat ack mismatch: a worker fell out of step
ABORT_REASONS = {ABORT_EXC: "coordinator exception",
                 ABORT_DEADLINE: "deadline exceeded",
                 ABORT_DESYNC: "worker desynchronized"}


class ProtocolError(RuntimeError):
    """The command stream itself is corrupt (bad opcode, failed ack)."""


class CoordinatorAbort(RuntimeError):
    """Raised on workers when the coordinator broadcasts CMD_ABORT."""

    def __init__(self, reason: int):
        self.reason = int(reason)
        super().__init__(
            "multi-host serve coordinator aborted: "
            f"{ABORT_REASONS.get(self.reason, f'reason {reason}')}")


class MultiHostServeEngine(ShardedServeEngine):
    """ShardedServeEngine over a multi-process ('data', 'model') mesh.

    Every process constructs the engine with IDENTICAL arguments (params
    are host-replicated: same init seed or same checkpoint).  Process 0
    then drives ``run(requests)``; every other process calls
    ``serve_worker()`` and follows the broadcast command stream.  Call
    ``stop_workers()`` on the coordinator when the engine is done so the
    workers' loops return.

    Vision/encdec extras (patches/frames side inputs) ride the prefill
    payload as shape-tagged float32 arrays bitcast over the int32
    exchange; unsupported combinations (unknown keys, non-float dtypes,
    chunked prefill + extras) are typed ``ProtocolError``s at submit
    entry.  Temperature sampling runs in-program with
    per-request keys derived from (rng, uid, step) - the same derivation
    the single-process engines use - so sampled streams match them
    token-for-token, chunked prefill included (every process holds the
    same base ``rng`` and receives the batch uids/steps with the plan).
    """

    # a failed launch here is fleet-fatal, not per-request: the workers
    # already rendezvoused on this command, so skipping it on the
    # coordinator alone would desync every later collective.  Recovery is
    # abort + drain-and-resume instead (run()'s except path).
    _isolate_exec = False

    def __init__(self, cfg, params, *, mesh, slots_per_replica: int = 4,
                 max_len: int = 256, quantize_weights: bool = False,
                 temperature: float = 0.0, rng: jax.Array | None = None,
                 buckets: tuple[int, ...] = DEFAULT_BUCKETS,
                 chunked_prefill: bool = False, decode_steps: int = 1,
                 fault=None,
                 pdq_fallback: bool = False,
                 launch_timeout: float | None = None,
                 snapshot_path: str | None = None,
                 paged: bool = False, page_size: int = 64,
                 pool_pages: int | None = None,
                 prefix_sharing: bool = True,
                 telemetry: bool = True, trace: bool = False):
        self.n_processes = jax.process_count()
        self.process_id = jax.process_index()
        self.is_coordinator = self.process_id == 0
        data = int(mesh.shape["data"])
        if data % self.n_processes:
            # a mesh row straddling a process boundary would make the TP
            # all_gather a cross-process collective and break the
            # replica->process slot-state attribution
            raise ValueError(
                f"mesh 'data' axis ({data}) must divide over the "
                f"{self.n_processes} jax.distributed processes")
        self._chunk_sub = None
        self._chunk_us = None          # (uids, steps) held across chunk cmds
        self._chunk_track = None       # host (uids, steps) for _track_remote
        self._chunk_nxt = None         # last chunk's sampled tokens
        self._stopped = False
        self.launch_timeout = launch_timeout
        self._hdr = 4 + 3 * self.n_processes
        self._seq = 1                  # next command number (coordinator)
        self._done_seq = 0             # last completed command (workers)
        self._last_exec_us = 0         # worker: previous command exec wall
        self._prev_kind = None         # coordinator: previous command kind
        # worker-side ingress: local submits queued for coordinator pickup
        # (announced as queue counts on every header exchange)
        self._ingress_lock = threading.Lock()
        self._out_q: collections.deque = collections.deque()
        self._ingress_counts = [0] * self.n_processes
        self._remote: dict[int, dict] = {}   # uid -> {'max_new', 'tokens'}
        self._remote_seq = 1
        # every process carries its own Telemetry keyed by its jax process
        # index; the coordinator's additionally aggregates the fleet (the
        # piggybacked worker timings land there)
        tel = tmod.Telemetry(enabled=telemetry, trace=trace,
                             pid=self.process_id)
        super().__init__(cfg, params, mesh=mesh,
                         slots_per_replica=slots_per_replica, max_len=max_len,
                         quantize_weights=quantize_weights,
                         temperature=temperature, rng=rng, buckets=buckets,
                         chunked_prefill=chunked_prefill,
                         decode_steps=decode_steps, fault=fault,
                         pdq_fallback=pdq_fallback, paged=paged,
                         page_size=page_size, pool_pages=pool_pages,
                         prefix_sharing=prefix_sharing, tel=tel)
        if self.is_coordinator:
            for p in range(1, self.n_processes):
                self.tel.tracer.name_process(p, f"jax process {p}")
                self.tel.tracer.name_thread(p, tmod.TID_LAUNCH, "launch")
        self.snapshot_path = snapshot_path
        self.stats["remote_ingress"] = 0   # requests pulled from workers
        # replica -> owning process, for per-host stats and routing debug
        self.host_replicas = process_replicas(self.mesh)
        if self.n_processes > 1:
            self._build_broadcast()

    # ------------------------------------------------------- device programs
    def _init_pools(self):
        """Shape-only stand-ins: _build_jitted reads the pool tree
        structure (specs/shardings) and then allocates the real pools
        directly on the global mesh - materializing host zeros here would
        be two full pool allocations thrown away per process."""
        self._prefill_pool = jax.eval_shape(
            lambda: self.bundle.init_caches(self.slots, self.max_len,
                                            self.mem_len))
        if self.paged:
            self.caches = jax.eval_shape(
                lambda: self._paged_ops.init(
                    self.pool_pages * self.n_replicas))
        else:
            self.caches = self._prefill_pool

    def _build_jitted(self):
        cs = serve_pool_specs(self.caches)
        dp = P("data")
        pool_sh = pool_shardings(self.mesh, self.caches)
        repl = NamedSharding(self.mesh, P())

        # long-lived global buffers.  Params: every process holds the same
        # host values; make_global donates each process's addressable
        # (replicated) shards.  Cache pools: allocated directly on the mesh
        # by a sharded-output jit - a device_put of the process-local zeros
        # cannot address the other processes' shards.
        self.params = jax.tree.map(
            lambda x: make_global(self.mesh, P(), np.asarray(x)), self.params)
        # the paged pool tree has the same structure and per-leaf ranks as
        # the slot-row scratch (page axis where the slot axis was), so ONE
        # specs/shardings tree serves both
        mk_scratch = jax.jit(
            lambda: self.bundle.init_caches(self.slots, self.max_len,
                                            self.mem_len),
            out_shardings=pool_sh)
        if self.paged:
            mk_pool = jax.jit(
                lambda: self._paged_ops.init(
                    self.pool_pages * self.n_replicas),
                out_shardings=pool_sh)
        else:
            mk_pool = mk_scratch
        self.caches = mk_pool()
        self._prefill_pool = mk_scratch()

        # the base sampling key, made global once: every process constructs
        # the engine with the same rng argument, so the replicated shards
        # agree bit-for-bit
        self._rng_glob = self._glob(np.asarray(self.rng), P())

        # device programs are the ShardedServeEngine builders verbatim
        # (per-replica in-body sampling, N-step fused decode scan, TP +
        # pdq guard in the shard_map body) with one multi-process twist:
        # replicated out_shardings make XLA all-gather the (slots, N)
        # sampled-token block + ok flags to every device IN-PROGRAM, so
        # each process reads the full block off its local shard - no
        # host-side cross-process gathers, and the pdq health summary
        # rides the same sync.
        self._decode = self._traced_decode_sharded(
            decode_scan(self.bundle.decode_step, self._sample_fn(),
                        self.decode_steps, self.tel.enabled),
            in_specs=(P(), P(), cs, dp, dp, dp, dp, dp), donate=(),
            out_shardings=(repl, repl, pool_sh, repl))
        ps = ((repl, repl, pool_sh), repl)
        self._prefill_many = self._traced_sharded_jit(
            self._sampled_prefill(self.bundle.prefill_many),
            "prefill_compiles",
            in_specs=(P(), P(), dp, cs, dp, dp, dp), out_specs=(dp, dp, cs),
            tel=True, out_shardings=ps)
        self._prefill_chunk = self._traced_sharded_jit(
            self._sampled_prefill(self.bundle.prefill_chunk),
            "chunk_compiles",
            in_specs=(P(), P(), dp, cs, dp, dp, dp, dp),
            out_specs=(dp, dp, cs), tel=True, out_shardings=ps)
        self._scatter = self._traced_sharded_jit(
            self.bundle.cache_scatter, None,
            in_specs=(cs, cs, dp), out_specs=cs, donate=(0,))
        self._prefill_one = None

        if self.paged:
            # paged N-step decode (same collective fast path as _decode);
            # land/copy ride the plain sharded launches
            po = self._paged_ops
            pts = P("data", None)
            self._decode_paged = self._traced_decode_sharded(
                self._paged_decode_fn(),
                in_specs=(P(), P(), cs, pts, dp, dp, dp, dp, dp), donate=(),
                out_shardings=(repl, repl, pool_sh, repl))
            self._land = self._traced_sharded_jit(
                po.land, None, in_specs=(cs, cs, dp, dp, dp), out_specs=cs,
                donate=(0,))
            self._page_copy = self._traced_sharded_jit(
                po.copy, None, in_specs=(cs, dp), out_specs=cs, donate=(0,))

    # --------------------------------------------------------- the protocol
    # Coordinator -> worker shipping is a psum-based one-to-all broadcast
    # (workers contribute zeros), like multihost_utils.broadcast_one_to_all
    # BUT blocked on EVERY local shard before returning.  Gloo matches
    # collective ops on a TCP device pair by posting order, and an op only
    # sequences a device that DEPENDS on it: blocking just the first local
    # shard (what np.asarray does) lets the other local devices' tail
    # collectives drain into the next program's ops and cross-pair them -
    # observed as gloo preamble-size aborts.  Every launch here therefore
    # blocks all addressable shards of anything carrying a collective
    # before the next program is dispatched.
    def _glob(self, x, spec):
        return make_global(self.mesh, spec, x)

    # ------------------------------------------------- deadline watchdogs
    def _deadline(self, reason: str) -> DeadlineWatchdog:
        """Arm a watchdog around one blocking rendezvous/launch.  Disarmed
        when ``launch_timeout`` is None or the fleet is one process
        (nothing to rendezvous with)."""
        seconds = self.launch_timeout if self.n_processes > 1 else None
        return DeadlineWatchdog(seconds, reason=reason,
                                on_timeout=self._deadline_abort)

    def _deadline_abort(self, reason: str, seconds: float) -> None:
        # the main thread is stuck inside a collective, but the host-side
        # scheduler state is consistent between result applications: dump
        # the drain record first so a restarted coordinator can resume,
        # then declare this process dead with the typed exit code.
        if self.is_coordinator and self.snapshot_path:
            try:
                save_snapshot(self.snapshot_path, self.snapshot())
            except Exception:
                pass
        _default_deadline_abort(f"process {self.process_id}: {reason}",
                                seconds)

    # -------------------------------------------------------- broadcasts
    def _build_broadcast(self):
        devs = np.array(jax.devices()).reshape(self.n_processes,
                                               jax.local_device_count())
        self._bc_mesh = Mesh(devs, ("proc", "dev"))
        self._bc_jit = jax.jit(
            lambda tree: jax.tree.map(lambda x: jnp.sum(x, axis=0), tree),
            out_shardings=NamedSharding(self._bc_mesh, P()))

    def _broadcast(self, arrays: tuple, *, all_ranks: bool = False,
                   src: int = 0) -> list[np.ndarray]:
        """psum-exchange int32 arrays across the fleet.  All processes must
        call with equal shapes.  Default: one-to-all from ``src`` (every
        other process contributes zero rows, everyone reads the source's
        values; the coordinator ships plans with src=0, an ingress pull
        reverses direction with src=worker).  With ``all_ranks`` every
        process contributes its OWN row - the command header uses this so
        worker acks + ingress counts ride the same exchange."""
        if self.n_processes == 1:
            return [np.asarray(a, np.int32) for a in arrays]
        row = self.process_id if all_ranks else src

        def pre(x):
            x = np.asarray(x, np.int32)
            full = np.zeros((self.n_processes,) + x.shape, np.int32)
            if all_ranks or self.process_id == src:
                full[row] = x            # others sum in their zero rows
            return make_global(self._bc_mesh, P("proc"), full)

        with self._deadline("collective broadcast"):
            out = self._bc_jit(tuple(pre(a) for a in arrays))
            jax.block_until_ready(out)   # every local shard, see above
        return [np.asarray(x.addressable_data(0)) for x in out]

    # ----------------------------------------------------- command stream
    def _cmd(self, op: int, arg: int = 0, n_extras: int = 0) -> None:
        if not self.is_coordinator:
            # a worker that drives scheduling (submit()/run()) would
            # contribute zero rows to its own command broadcast and hang
            # or desync the fleet - fail loudly at the first command
            raise RuntimeError(
                f"process {self.process_id} is a worker: only the "
                "coordinator (process 0) issues commands; call "
                "serve_worker() here")
        seq = self._seq
        N = self.n_processes
        hdr = np.zeros((self._hdr,), np.int32)
        hdr[0], hdr[1], hdr[2], hdr[3] = op, arg, seq, n_extras
        hdr[4] = seq - 1                 # coordinator's own ack slot
        hdr = self.fault.on_broadcast(seq, hdr)
        out, = self._broadcast((hdr,), all_ranks=True)
        self._seq += 1
        # piggybacked worker ingress announcement (see header layout)
        self._ingress_counts = [int(out[4 + N + p]) for p in range(N)]
        # piggybacked worker launch timings: slot p carries the wall time
        # of worker p's PREVIOUS command, so attribute it to the kind of
        # the command issued one seq earlier
        if self._prev_kind is not None and self.tel.enabled:
            tr = self.tel.tracer
            for p in range(1, N):
                us = int(out[4 + 2 * N + p])
                if us > 0:
                    self.tel.launch_histogram(
                        self._prev_kind, process=p).observe(us / 1e6)
                    if tr.enabled:
                        tr.add(f"launch:{self._prev_kind}",
                               ts=tr.now_us() - us, dur=us, pid=p,
                               tid=tmod.TID_LAUNCH, args={"process": p})
        self._prev_kind = _CMD_KINDS.get(op)
        # piggybacked heartbeat: the worker loop is sequential, so at this
        # rendezvous every live worker must have completed seq - 1 exactly
        for p in range(1, N):
            if int(out[4 + p]) != seq - 1:
                raise ProtocolError(
                    f"worker {p} acked command seq {int(out[4 + p])} at "
                    f"command seq {seq} (expected {seq - 1}): the fleet is "
                    "desynchronized")

    def _recv_cmd(self) -> tuple[int, int, int, int]:
        hdr = np.zeros((self._hdr,), np.int32)
        hdr[4 + self.process_id] = self._done_seq      # heartbeat/ack
        with self._ingress_lock:                       # queued submits
            hdr[4 + self.n_processes + self.process_id] = len(self._out_q)
        # previous command's exec wall time (telemetry piggyback)
        hdr[4 + 2 * self.n_processes + self.process_id] = self._last_exec_us
        hdr = self.fault.on_broadcast(self._done_seq + 1, hdr)
        out, = self._broadcast((hdr,), all_ranks=True)
        op, arg, seq, n_ex = (int(out[0]), int(out[1]), int(out[2]),
                              int(out[3]))
        if op == CMD_ABORT:
            raise CoordinatorAbort(arg)
        return op, arg, seq, n_ex

    def _send(self, arrays: list[np.ndarray]) -> None:
        self._broadcast(tuple(arrays))

    def _recv(self, shapes: list[tuple[int, ...]]) -> list[np.ndarray]:
        return self._broadcast(tuple(np.zeros(s, np.int32) for s in shapes))

    # ------------------------------------------------------ extras payload
    # Vision patches / encdec frames are float32 side inputs shared across
    # the batch (seed semantics, like the single-process engines).  They
    # ride the int32 exchange as [shape-tag header, bitcast values] pairs:
    # header int32[6] = [key_id, ndim, d0, d1, d2, d3], then the raveled
    # float32 buffer reinterpreted as int32 (psum over zero contributions
    # is bit-preserving, so no float rounding can occur in transit).
    def _norm_extras(self, extras) -> list[tuple[str, np.ndarray]]:
        if not extras:
            return []
        out = []
        for key in sorted(dict(extras)):       # deterministic wire order
            a = np.ascontiguousarray(np.asarray(extras[key], np.float32))
            out.append((key, a))
        return out

    def _send_extras(self, ex: list[tuple[str, np.ndarray]]) -> None:
        for key, a in ex:
            hdr = np.zeros((6,), np.int32)
            hdr[0], hdr[1] = _EXTRA_KEYS[key], a.ndim
            hdr[2:2 + a.ndim] = a.shape
            self._send([hdr])
            self._send([a.ravel().view(np.int32)])

    def _recv_extras(self, n: int) -> dict[str, np.ndarray]:
        ex = {}
        for _ in range(n):
            hdr, = self._recv([(6,)])
            key = _EXTRA_IDS.get(int(hdr[0]))
            nd = int(hdr[1])
            if key is None or not 1 <= nd <= 4:
                raise ProtocolError(
                    f"bad extras shape tag {hdr.tolist()} in prefill "
                    "payload (unknown key id or ndim out of range)")
            shape = tuple(int(d) for d in hdr[2:2 + nd])
            flat, = self._recv([(int(np.prod(shape)),)])
            ex[key] = flat.view(np.float32).reshape(shape)
        return ex

    # ------------------------------------------------- shared launch bodies
    # Each _do_* runs on EVERY process with identical host arrays (the
    # coordinator's plan, either local or just received) and performs the
    # same global-mesh launch; the replicated (tokens, ok) outputs are
    # locally addressable everywhere.
    def _us(self, uids, steps):
        # per-row sampling metadata: split over 'data' like the rows it
        # describes (sampling runs per replica inside the shard_map body)
        return (self._glob(np.asarray(uids, np.int32), P("data")),
                self._glob(np.asarray(steps, np.int32), P("data")))

    def _batch(self, tokens, extras) -> dict:
        batch = {"tokens": self._glob(tokens, P("data"))}
        for key, a in (extras or {}).items():
            # shared across requests (seed semantics): broadcast the
            # leading batch dim across the prefill rows, exactly like the
            # single-process engines' _extras_batch
            b = np.broadcast_to(a[:1], (self.slots,) + a.shape[1:])
            batch[key] = self._glob(np.ascontiguousarray(b), P("data"))
        return batch

    def _land_global(self, sub, src_map, land_rows, land_js) -> None:
        """Land a finished prefill: page-wise through the plan's land maps
        (paged pool) or whole slot rows (slot-row pool)."""
        if self.paged:
            self.caches = self._land(self.caches, sub,
                                     self._glob(src_map, P("data")),
                                     self._glob(land_rows, P("data")),
                                     self._glob(land_js, P("data")))
        else:
            self.caches = self._scatter(self.caches, sub,
                                        self._glob(src_map, P("data")))

    def _do_prefill(self, tokens, seq_lens, src_map, uids, steps,
                    extras=None, land_rows=None, land_js=None):
        u, s = self._us(uids, steps)
        with self._deadline("prefill launch"):
            with self._dispatch_span("prefill"):
                (nxt, ok, sub), tel = self._prefill_many(
                    self._rng_glob, self.params, self._batch(tokens, extras),
                    self._prefill_pool, self._glob(seq_lens, P("data")), u, s)
                self._land_global(sub, src_map, land_rows, land_js)
            self._block("prefill", (nxt, ok, tel, self.caches))
        nxt, ok = np.asarray(nxt), np.asarray(ok)
        self._observe_pdq(tel)      # psum'd fleet totals, replicated
        self._track_remote(nxt, ok, uids, steps)
        return nxt, ok

    def _do_chunk_first(self, tokens, seq_lens, uids, steps):
        self._chunk_us = self._us(uids, steps)
        self._chunk_track = (np.asarray(uids, np.int32),
                             np.asarray(steps, np.int32))
        u, s = self._chunk_us
        with self._deadline("chunked-prefill launch"):
            with self._dispatch_span("chunked"):
                (nxt, ok, self._chunk_sub), tel = self._prefill_many(
                    self._rng_glob, self.params,
                    {"tokens": self._glob(tokens, P("data"))},
                    self._prefill_pool, self._glob(seq_lens, P("data")), u, s)
            self._block("chunked", (nxt, ok, tel, self._chunk_sub))
        self._observe_pdq(tel)
        self._chunk_nxt = (np.asarray(nxt), np.asarray(ok))
        return self._chunk_nxt

    def _do_chunk_next(self, tokens, seq_lens, start_lens):
        u, s = self._chunk_us
        with self._deadline("chunked-prefill launch"):
            with self._dispatch_span("chunked"):
                (nxt, ok, self._chunk_sub), tel = self._prefill_chunk(
                    self._rng_glob, self.params,
                    {"tokens": self._glob(tokens, P("data"))},
                    self._chunk_sub, self._glob(seq_lens, P("data")),
                    self._glob(start_lens, P("data")), u, s)
            self._block("chunked", (nxt, ok, tel, self._chunk_sub))
        self._observe_pdq(tel)
        self._chunk_nxt = (np.asarray(nxt), np.asarray(ok))
        return self._chunk_nxt

    def _do_chunk_end(self, src_map, land_rows=None, land_js=None) -> None:
        with self._deadline("chunk cache scatter"):
            self._land_global(self._chunk_sub, src_map, land_rows, land_js)
            jax.block_until_ready(self.caches)
        if self._chunk_nxt is not None and self._chunk_track is not None:
            # only the LAST chunk's sampled token is the request's first
            # real token; commit it to remote trackers now that the
            # sequence is complete
            nxt, ok = self._chunk_nxt
            self._track_remote(nxt, ok, *self._chunk_track)
        self._chunk_sub = None
        self._chunk_us = None
        self._chunk_track = None
        self._chunk_nxt = None

    def _block(self, kind: str, outs) -> None:
        """The ``fetch:<kind>`` half of a multi-process launch: wait for
        every local shard (see ``_broadcast`` on why all of them)."""
        with self.tel.span(f"fetch:{kind}", tid=tmod.TID_LAUNCH,
                           phase="fetch", kind=kind):
            jax.block_until_ready(outs)

    def _do_decode(self, tokens, positions, uids, steps, n_steps,
                   page_tables=None):
        u, s = self._us(uids, steps)
        ns = self._glob(np.asarray(n_steps, np.int32), P("data"))
        with self._deadline("decode launch"):
            with self._dispatch_span("decode"):
                if self.paged:
                    nxt, ok, self.caches, tel = self._decode_paged(
                        self._rng_glob, self.params, self.caches,
                        self._glob(page_tables, P("data", None)),
                        self._glob(tokens, P("data")),
                        self._glob(positions, P("data")), u, s, ns)
                else:
                    nxt, ok, self.caches, tel = self._decode(
                        self._rng_glob, self.params, self.caches,
                        self._glob(tokens, P("data")),
                        self._glob(positions, P("data")), u, s, ns)
            self._block("decode", (nxt, ok, tel, self.caches))
        nxt, ok = np.asarray(nxt), np.asarray(ok)
        self._observe_pdq(tel)
        self._track_remote(nxt, ok, uids, steps)
        return nxt, ok

    def _do_page_copy(self, cmap) -> None:
        with self._deadline("page copy launch"):
            self.caches = self._page_copy(self.caches,
                                          self._glob(cmap, P("data")))
            jax.block_until_ready(self.caches)

    def _track_remote(self, nxt, ok, uids, steps) -> None:
        """Worker-side token mirror for its own remote submits: sampled
        tokens are replicated to every process in-program, so a worker
        reads its requests' streams straight off the plans it already
        executes - no result backhaul.  The (uid, step)-keyed append makes
        it robust to dummy rows and replays: a token only lands if its
        step equals the tokens mirrored so far.  ``nxt``/``ok`` may be
        (slots,) prefill rows or (slots, N) decode blocks; a row's block
        walk stops at the first bad token (non-finite row, DECODE_PAD
        budget padding, step replay, or max_new reached)."""
        if not self._remote:
            return
        uids = np.asarray(uids)
        steps = np.asarray(steps)
        nxt = np.asarray(nxt).reshape(len(uids), -1)
        ok = np.asarray(ok).reshape(len(uids), -1)
        for row, uid in enumerate(uids):
            rec = self._remote.get(int(uid))
            if rec is None:
                continue
            for t in range(nxt.shape[1]):
                tok = int(nxt[row, t])
                if (not bool(ok[row, t]) or tok == DECODE_PAD
                        or int(steps[row]) + t != len(rec["tokens"])
                        or len(rec["tokens"]) >= rec["max_new"]):
                    break
                rec["tokens"].append(tok)

    # --------------------------------------------------- coordinator driver
    def _exec_prefill(self, plan: PrefillPlan, extras):
        ex = self._norm_extras(extras)
        self._cmd(CMD_PREFILL, plan.bucket, n_extras=len(ex))
        payload = [plan.tokens, plan.seq_lens, plan.src_map,
                   plan.row_uids, plan.row_steps]
        if self.paged:          # page landing maps ride the same payload
            payload += [plan.land_rows, plan.land_js]
        self._send(payload)
        self._send_extras(ex)
        # launch with the NORMALIZED (wire-format float32) arrays so the
        # coordinator computes on bit-identical inputs to the workers
        return self._do_prefill(plan.tokens, plan.seq_lens, plan.src_map,
                                plan.row_uids, plan.row_steps,
                                extras=dict(ex), land_rows=plan.land_rows,
                                land_js=plan.land_js)

    def _exec_chunked(self, plan: ChunkedPlan, extras):
        if extras:
            # unreachable for well-formed use: _validate_extras rejects the
            # combination at submit()/run() entry, before any slot is held
            raise ProtocolError(
                "chunked-prefill commands carry no extras payload")
        b, tokens, seq_lens = plan.first
        self._cmd(CMD_CHUNK_FIRST, b)
        self._send([tokens, seq_lens, plan.row_uids, plan.row_steps])
        res = self._do_chunk_first(tokens, seq_lens,
                                   plan.row_uids, plan.row_steps)
        for b, tokens, seq_lens, start_lens in plan.chunks:
            self._cmd(CMD_CHUNK_NEXT, b)
            self._send([tokens, seq_lens, start_lens])
            res = self._do_chunk_next(tokens, seq_lens, start_lens)
        self._cmd(CMD_CHUNK_END)
        payload = [plan.src_map]
        if self.paged:
            payload += [plan.land_rows, plan.land_js]
        self._send(payload)
        self._do_chunk_end(plan.src_map, plan.land_rows, plan.land_js)
        return res

    def _exec_decode(self, plan: DecodePlan):
        # arg carries the BLOCK size N: a worker built with a different
        # decode_steps would trace a different executable and desync the
        # fleet, so it verifies lockstep before executing
        self._cmd(CMD_DECODE, self.decode_steps)
        payload = [plan.tokens, plan.positions,
                   plan.row_uids, plan.row_steps, plan.n_steps]
        if self.paged:          # (slots, n_pp) replica-local page tables
            payload += [plan.page_tables]
        self._send(payload)
        return self._do_decode(plan.tokens, plan.positions,
                               plan.row_uids, plan.row_steps, plan.n_steps,
                               page_tables=plan.page_tables)

    def _exec_page_copy(self, replica: int, pairs) -> None:
        cmap = self._copy_map(replica, pairs)
        self._cmd(CMD_PAGE_COPY)
        self._send([cmap])
        self._do_page_copy(cmap)

    def _validate_extras(self, prompt_len: int, extras) -> None:
        # entry-point rejection, BEFORE anything queues or a plan claims a
        # slot (raising mid-admission would drop dequeued peers / leak the
        # planned slot).  Unsupported combinations are typed protocol
        # errors: they describe what the COMMAND STREAM cannot carry.
        if not extras:
            return
        for key, v in dict(extras).items():
            if key not in _EXTRA_KEYS:
                raise ProtocolError(
                    f"extras key {key!r} is not part of the multi-host "
                    f"command protocol (known: {sorted(_EXTRA_KEYS)})")
            a = np.asarray(v)
            if a.dtype.kind != "f":
                raise ProtocolError(
                    f"extras[{key!r}] dtype {a.dtype} is not a float type: "
                    "the prefill payload bitcasts float32 over the int32 "
                    "exchange")
            if not 1 <= a.ndim <= 4:
                raise ProtocolError(
                    f"extras[{key!r}] ndim {a.ndim} exceeds the shape-tag "
                    "header (1..4 dims)")
        if self.chunked_prefill and prompt_len > self.buckets[-1]:
            raise ProtocolError(
                "chunked-prefill commands carry no extras payload: "
                f"oversized prompt ({prompt_len} > bucket "
                f"{self.buckets[-1]}) cannot combine with vision/encdec "
                "extras on a multi-host fleet")

    def run(self, requests, extras=None):
        if not self.is_coordinator:
            raise RuntimeError(
                f"process {self.process_id} is a worker: call "
                "serve_worker(), only process 0 drives run()")
        if extras:
            self._validate_extras(0, extras)   # even for an empty trace
        try:
            return super().run(requests, extras)
        except BaseException as e:
            self._fleet_abort(e)
            raise

    def _fleet_abort(self, e: BaseException) -> None:
        # the fleet is lost: first persist the drain record (resume
        # needs it even if the abort below hangs on a dead peer), then
        # best-effort unblock workers waiting at the next header
        # rendezvous (a worker already desynced inside a payload
        # collective is covered by the deadline watchdog / CI timeout
        # instead).  The workers then EXIT, so mark the fleet stopped -
        # a `finally: stop_workers()` cleanup must not broadcast into
        # dead peers and hang on the gloo timeout.  Shared with the
        # streaming service's step loop (serve/service.py), whose driver
        # bypasses run().
        if self.snapshot_path:
            try:
                save_snapshot(self.snapshot_path, self.snapshot())
            except Exception:
                pass
        reason = (ABORT_DESYNC if isinstance(e, ProtocolError)
                  else ABORT_EXC)
        try:
            self._cmd(CMD_ABORT, reason)
        except Exception:
            pass               # peer already gone: keep the original error
        finally:
            self._stopped = True

    def stop_workers(self) -> None:
        """Release the worker loops; the engine stays usable for stats."""
        if self.is_coordinator and not self._stopped:
            self._cmd(CMD_STOP)
            self._stopped = True

    # ------------------------------------------------------ worker ingress
    # The multi-host residual of the streaming front door: a request can
    # enter the fleet through ANY process.  A worker's submit_remote()
    # queues locally; the queue LENGTH rides every header exchange (see
    # _recv_cmd), so the coordinator learns about remote submits at its
    # next command - or at an explicit CMD_POLL when otherwise idle - and
    # pulls the payload with CMD_INGRESS.  Tokens need no backhaul: the
    # in-program broadcast already replicates every sampled token to every
    # process, and _track_remote mirrors the worker's own uids off the
    # plans it executes anyway.
    def submit_remote(self, prompt, *, max_new: int = 16,
                      deadline_ms: int = 0) -> int:
        """Worker-side submit: queue a request for coordinator pickup.
        Returns its fleet-unique uid (namespaced by process id so remote
        uids never collide with the coordinator's counter).  ``deadline_ms``
        is RELATIVE (processes share no clock): the coordinator arms the
        absolute deadline at ingestion; 0 = none."""
        assert not self.is_coordinator, \
            "the coordinator submits locally (submit()/ServeService)"
        uid = (self.process_id << 20) | self._remote_seq
        self._remote_seq += 1
        prompt = np.asarray(prompt, np.int32)
        self._remote[uid] = {"max_new": int(max_new), "tokens": []}
        with self._ingress_lock:
            self._out_q.append((uid, prompt, int(max_new), int(deadline_ms)))
        return uid

    def remote_tokens(self, uid: int) -> list[int]:
        """Tokens mirrored so far for a submit_remote() uid (worker-side)."""
        return list(self._remote[uid]["tokens"])

    def remote_done(self, uid: int) -> bool:
        rec = self._remote[uid]
        return len(rec["tokens"]) >= rec["max_new"]

    def poll_ingress(self) -> list[Request]:
        """Coordinator: pull every announced worker submit into Request
        objects (the streaming service enqueues them like local traffic).
        Issues a CMD_POLL rendezvous first when no counts are known yet -
        an idle fleet still discovers remote submits."""
        if (not self.is_coordinator or self.n_processes == 1
                or self._stopped):
            return []
        if not any(self._ingress_counts[1:]):
            self._cmd(CMD_POLL)          # refresh counts via the heartbeat
        out: list[Request] = []
        for p in range(1, self.n_processes):
            if self._ingress_counts[p]:
                out.extend(self._pull_ingress(p))
        self.stats["remote_ingress"] += len(out)
        return out

    def _pull_ingress(self, p: int) -> list[Request]:
        self._cmd(CMD_INGRESS, p)
        cnt, = self._broadcast((np.zeros((1,), np.int32),), src=p)
        reqs = []
        for _ in range(int(cnt[0])):
            meta, = self._broadcast((np.zeros((4,), np.int32),), src=p)
            uid, L, max_new, dl_ms = (int(x) for x in meta)
            prompt, = self._broadcast((np.zeros((L,), np.int32),), src=p)
            r = Request(uid=uid, prompt=prompt.astype(np.int32),
                        max_new=max_new)
            if dl_ms > 0:
                r.deadline = self._clock() + dl_ms / 1000.0
            reqs.append(r)
        return reqs

    def _serve_ingress(self, src: int) -> None:
        """Worker side of CMD_INGRESS: process ``src`` drains its queue
        onto the wire; every other process contributes zeros and discards
        the received requests (only the coordinator schedules)."""
        mine = src == self.process_id
        if mine:
            with self._ingress_lock:
                batch = list(self._out_q)
                self._out_q.clear()
        else:
            batch = []
        cnt, = self._broadcast(
            (np.array([len(batch)], np.int32),), src=src)
        for i in range(int(cnt[0])):
            if mine:
                uid, prompt, max_new, dl_ms = batch[i]
                meta = np.array([uid, len(prompt), max_new, dl_ms],
                                np.int32)
            else:
                meta = np.zeros((4,), np.int32)
            meta, = self._broadcast((meta,), src=src)
            L = int(meta[1])
            pr = batch[i][1] if mine else np.zeros((L,), np.int32)
            self._broadcast((pr,), src=src)

    # --------------------------------------------------------- worker loop
    def serve_worker(self) -> None:
        """Follow the coordinator's command stream until CMD_STOP.

        Each completed command's seq is acked on the NEXT header exchange
        (the piggybacked heartbeat); a coordinator abort raises the typed
        ``CoordinatorAbort``, an unknown opcode the typed
        ``ProtocolError``."""
        assert not self.is_coordinator, "process 0 is the coordinator"
        S = self.slots
        # paged payloads: land maps (Np,), page tables (S, n_pp)
        Np = self.pool_pages * self.n_replicas if self.paged else 0
        lnd = [(Np,), (Np,)] if self.paged else []
        while True:
            op, arg, seq, n_ex = self._recv_cmd()
            if op == CMD_STOP:
                return
            t0 = time.perf_counter()   # stamped on the NEXT header exchange
            if op == CMD_PREFILL:
                recv = self._recv([(S, arg), (S,), (S,), (S,), (S,)] + lnd)
                t, sl, m, u, st = recv[:5]
                ex = self._recv_extras(n_ex)
                self._do_prefill(t, sl, m, u, st, extras=ex,
                                 land_rows=recv[5] if self.paged else None,
                                 land_js=recv[6] if self.paged else None)
            elif op == CMD_CHUNK_FIRST:
                t, sl, u, st = self._recv([(S, arg), (S,), (S,), (S,)])
                self._do_chunk_first(t, sl, u, st)
            elif op == CMD_CHUNK_NEXT:
                t, sl, st = self._recv([(S, arg), (S,), (S,)])
                self._do_chunk_next(t, sl, st)
            elif op == CMD_CHUNK_END:
                recv = self._recv([(S,)] + lnd)
                self._do_chunk_end(recv[0],
                                   recv[1] if self.paged else None,
                                   recv[2] if self.paged else None)
            elif op == CMD_DECODE:
                if arg != self.decode_steps:
                    raise ProtocolError(
                        f"coordinator decode block size {arg} != this "
                        f"worker's decode_steps {self.decode_steps}: every "
                        "process must construct the engine with identical "
                        "arguments")
                recv = self._recv([(S, 1), (S, 1), (S,), (S,), (S,)]
                                  + ([(S, self.n_pp)] if self.paged else []))
                self._do_decode(*recv[:5],
                                page_tables=recv[5] if self.paged else None)
            elif op == CMD_PAGE_COPY:
                cmap, = self._recv([(Np,)])
                self._do_page_copy(cmap)
            elif op == CMD_INGRESS:
                self._serve_ingress(arg)
            elif op == CMD_POLL:
                pass        # pure rendezvous: ack + counts already rode it
            else:
                raise ProtocolError(
                    f"unknown multi-host serve opcode {op} at command seq "
                    f"{seq} (corrupt or desynchronized command stream)")
            if op in _CMD_KINDS:       # launch kinds only: the coordinator
                self._last_exec_us = int(min(   # skips non-exec commands
                    (time.perf_counter() - t0) * 1e6, 2**31 - 1))
            self._done_seq = seq

    # ------------------------------------------------------ per-host stats
    def host_stats(self) -> dict[int, dict[str, int]]:
        """Coordinator-side admit/occupancy totals per OWNING process,
        derived from the replica->process map (the scheduler only exists
        on process 0, so these are its authoritative counters)."""
        out: dict[int, dict[str, int]] = {}
        for proc, reps in self.host_replicas.items():
            out[proc] = {
                "replicas": len(reps),
                "admits": sum(self.stats["replica_admits"][r] for r in reps),
                "occupied": sum(self.stats["replica_occupancy"][r]
                                for r in reps),
                "slots": len(reps) * self.slots_per_replica,
            }
        return out
