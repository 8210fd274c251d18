"""ShardedServeEngine: mesh-distributed serving tests.

Pins the PR-4 contract: a ('data', 'model') mesh engine produces
token-for-token greedy parity with the single-device engine (slots
data-parallel, PDQ/fp projection columns tensor-parallel with an
all-gather epilogue), the sharded decode step keeps the single-device
kernel census per replica, and the coordinator routes admits to the
least-loaded replicas.

Multi-device cases run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main test
process keeps its single-device view (same pattern as
tests/test_distributed.py).
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import serve_pool_specs


def _run_subprocess(code: str, devices: int = 8):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------------- spec helper
def test_serve_pool_specs_slot_axis_layout():
    """Slot axis shards over 'data': axis 0 on head/tail leaves, axis 1 on
    lax.scan-stacked block leaves; nothing else is sharded."""
    caches = {
        "head": ({"k": jnp.zeros((8, 32, 2, 16)), "len": jnp.zeros((8,))},),
        "tail": (),
        "blocks": ({"k": jnp.zeros((6, 8, 32, 2, 16)),
                    "state": jnp.zeros((6, 8, 4, 16, 8))},),
    }
    specs = serve_pool_specs(caches)
    assert specs["head"][0]["k"] == P("data", None, None, None)
    assert specs["head"][0]["len"] == P("data")
    assert specs["blocks"][0]["k"] == P(None, "data", None, None, None)
    assert specs["blocks"][0]["state"] == P(None, "data", None, None, None)


# ----------------------------------------------------- mesh parity (greedy)
def _parity_case(body: str) -> str:
    """Prelude + test body, each dedented on its own (their indents differ)."""
    return textwrap.dedent(_PARITY_PRELUDE) + textwrap.dedent(body)


_PARITY_PRELUDE = """
    import jax
    import numpy as np
    from repro.configs import reduced_config
    from repro.models import build_model
    from repro.serve import Request, ServeEngine, ShardedServeEngine

    def requests(cfg, lens, max_new=4, seed=0):
        rng = np.random.default_rng(seed)
        return [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
                        max_new=max_new) for i, L in enumerate(lens)]

    def outputs(eng, cfg, lens, max_new=4):
        reqs = requests(cfg, lens, max_new)
        eng.run(reqs)
        assert all(r.done for r in reqs)
        return [tuple(r.generated) for r in reqs]
"""


def test_sharded_matches_single_device_mixed_trace():
    """Acceptance pin: a data=4 x model=2 mesh engine (2 slots/replica)
    serves the 12-request mixed-length trace token-for-token identically
    to the single-device engine, admission routes to the least-loaded
    replicas, and the compile counts stay bucket-bounded."""
    out = _run_subprocess(_parity_case("""
        MIXED = [3, 5, 8, 9, 12, 16, 17, 23, 30, 4, 11, 27]
        cfg = reduced_config("stablelm-1.6b")
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        ref = ServeEngine(cfg, params, slots=4, max_len=64, buckets=(8, 16, 32))
        want = outputs(ref, cfg, MIXED, max_new=6)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        eng = ShardedServeEngine(cfg, params, mesh=mesh, slots_per_replica=2,
                                 max_len=64, buckets=(8, 16, 32))
        got = outputs(eng, cfg, MIXED, max_new=6)
        assert got == want, [i for i, (a, b) in enumerate(zip(got, want))
                             if a != b]
        # coordinator accounting: every admit counted on some replica, and
        # the least-loaded routing spreads the trace across all replicas
        assert sum(eng.stats["replica_admits"]) == len(MIXED)
        assert min(eng.stats["replica_admits"]) >= 1
        assert eng.stats["replica_occupancy"] == [0, 0, 0, 0]   # drained
        assert eng.stats["prefill_compiles"] <= len(eng.buckets)
        assert eng.stats["decode_compiles"] == 1
        print("OK")
    """))
    assert "OK" in out


def test_sharded_parity_other_families():
    """SSM recurrent state and the MLA compressed cache survive the mesh:
    greedy decode equality on a 2x2 mesh for mamba2 and deepseek."""
    out = _run_subprocess(_parity_case("""
        for arch in ("mamba2-2.7b", "deepseek-v2-236b"):
            cfg = reduced_config(arch)
            params = build_model(cfg).init(jax.random.PRNGKey(0))
            lens = [3, 7, 11, 16, 5, 9]
            ref = ServeEngine(cfg, params, slots=2, max_len=48, buckets=(8, 16))
            want = outputs(ref, cfg, lens)
            mesh = jax.make_mesh((2, 2), ("data", "model"))
            eng = ShardedServeEngine(cfg, params, mesh=mesh,
                                     slots_per_replica=2, max_len=48,
                                     buckets=(8, 16))
            got = outputs(eng, cfg, lens)
            assert got == want, (arch, got, want)
            print("OK", arch)
        print("OK")
    """))
    assert "OK mamba2-2.7b" in out and "OK deepseek-v2-236b" in out


def test_sharded_quantized_and_chunked_parity():
    """The PDQ-int8 weight path (column-split W8A8 + all-gather epilogue)
    and chunked prefill both stay token-for-token exact on the mesh."""
    out = _run_subprocess(_parity_case("""
        cfg = reduced_config("stablelm-1.6b")
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        mesh = jax.make_mesh((2, 2), ("data", "model"))

        lens = [3, 9, 14, 6, 12, 30]
        ref = ServeEngine(cfg, params, slots=2, max_len=64, buckets=(8, 16, 32),
                          quantize_weights=True)
        want = outputs(ref, cfg, lens)
        eng = ShardedServeEngine(cfg, params, mesh=mesh, slots_per_replica=2,
                                 max_len=64, buckets=(8, 16, 32),
                                 quantize_weights=True)
        got = outputs(eng, cfg, lens)
        assert got == want, (got, want)
        print("OK int8")

        lens = [4, 20, 40, 11]          # 20/40 exceed the largest bucket
        ref = ServeEngine(cfg, params, slots=2, max_len=64, buckets=(8, 16),
                          chunked_prefill=True)
        want = outputs(ref, cfg, lens)
        eng = ShardedServeEngine(cfg, params, mesh=mesh, slots_per_replica=2,
                                 max_len=64, buckets=(8, 16),
                                 chunked_prefill=True)
        got = outputs(eng, cfg, lens)
        assert got == want, (got, want)
        assert eng.stats["chunked_requests"] == 2
        print("OK chunked")
    """))
    assert "OK int8" in out and "OK chunked" in out


def test_sharded_paged_pool_parity_and_preemption():
    """The paged KV pool on a 4x2 mesh: replica-local page tables ride
    the decode plan into ONE shard_map-ed gather/step/writeback launch,
    and a pool small enough to force mid-decode growth preempts + requeues
    with (uid, step)-keyed regeneration - both token-for-token equal to
    the single-device slot-row engine."""
    out = _run_subprocess(_parity_case("""
        MIXED = [3, 5, 8, 9, 12, 16, 17, 23, 30]
        cfg = reduced_config("stablelm-1.6b")
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        mesh = jax.make_mesh((4, 2), ("data", "model"))

        ref = ServeEngine(cfg, params, slots=8, max_len=64,
                          buckets=(8, 16, 32), temperature=0.9)
        want = outputs(ref, cfg, MIXED, max_new=6)
        eng = ShardedServeEngine(cfg, params, mesh=mesh, slots_per_replica=2,
                                 max_len=64, buckets=(8, 16, 32),
                                 temperature=0.9, paged=True, page_size=16)
        got = outputs(eng, cfg, MIXED, max_new=6)
        assert got == want, [i for i, (a, b) in enumerate(zip(got, want))
                             if a != b]
        print("OK paged parity")

        # 17-token prompts claim 2 pages each, max_new=30 forces a 3rd
        # mid-decode; 5 usable pages/replica under 2 slots -> preemption
        def grow():
            rng = np.random.default_rng(7)
            return [Request(uid=50 + i,
                            prompt=rng.integers(1, 200, 17).astype(np.int32),
                            max_new=30) for i in range(8)]
        ref2 = ServeEngine(cfg, params, slots=8, max_len=64,
                           buckets=(8, 16, 32), temperature=0.9)
        g0 = grow(); ref2.run(g0)
        eng2 = ShardedServeEngine(cfg, params, mesh=mesh, slots_per_replica=2,
                                  max_len=64, buckets=(8, 16, 32),
                                  temperature=0.9, paged=True, page_size=16,
                                  pool_pages=6)
        g1 = grow(); eng2.run(g1)
        assert ([tuple(r.generated) for r in g1]
                == [tuple(r.generated) for r in g0])
        assert eng2.stats["preemptions"] > 0
        print("OK paged preempt", eng2.stats["preemptions"])
    """))
    assert "OK paged parity" in out and "OK paged preempt" in out


# --------------------------------------------------------- kernel-count pin
def test_sharded_decode_block_is_eight_kernels_per_replica():
    """A quantized GQA block inside the shard_map body (TP over 'model')
    traces to the single-device census of 7 pallas_calls per replica (the
    8 of the pre-fusion grouped path, less the w_down prologue that the
    fused SwiGLU kernel emits): the column-split rides INSIDE the
    one-prologue-one-matmul pipeline (slice + all-gather add no kernel
    launches), and the fused gate/up kernel runs whole on every shard."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.kernels import ops
        from repro.models.attention import AttnDims, gqa_apply, gqa_init, init_cache
        from repro.models.layers import mlp_apply, mlp_init, rms_norm
        from repro.models.linops import quantize_param_tree
        from tests.test_hlo_and_linops import _count_pallas_calls

        dims = AttnDims(d_model=256, n_heads=4, n_kv_heads=2, head_dim=64)
        key = jax.random.PRNGKey(0)
        params = {"attn": gqa_init(key, dims, jnp.float32),
                  "attn_norm": jnp.zeros((256,)),
                  "ffn_norm": jnp.zeros((256,)),
                  "ffn": mlp_init(jax.random.fold_in(key, 1), 256, 512,
                                  jnp.float32)}
        qp = quantize_param_tree(params)
        cache = init_cache(dims, 8, 64, jnp.float32)

        def block(p, h, cache, positions):
            a, cache = gqa_apply(p["attn"], dims,
                                 rms_norm(h, p["attn_norm"]), positions,
                                 mode="decode", cache=cache)
            h = h + a
            return h + mlp_apply(p["ffn"], rms_norm(h, p["ffn_norm"])), cache

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cs = jax.tree.map(lambda c: P(*(("data",) + (None,) * (c.ndim - 1))),
                          cache)

        def sharded(p, h, cache, positions):
            def body(p, h, cache, positions):
                with ops.tp_shard("model", 2):
                    return block(p, h, cache, positions)
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(), P("data"), cs, P("data")),
                                 out_specs=(P("data"), cs),
                                 check_vma=False)(p, h, cache, positions)

        h = jnp.ones((8, 1, 256))
        pos = jnp.zeros((8, 1), jnp.int32) + 3
        ops.set_impl("kernel")
        try:
            jaxpr = jax.make_jaxpr(sharded)(qp, h, cache, pos)
        finally:
            ops.set_impl("auto")
        n = _count_pallas_calls(jaxpr)
        assert n == 7, f"expected 7 pallas_calls per sharded decode block, got {n}"
        print("OK", n)
    """)
    assert "OK 7" in out


def test_sharded_fused_mlp_matches_single_device_bitwise():
    """On the kernel path the quantized bf16 MLP under TP returns exactly
    the single-device result.  The unfused composition rounds gate/up to
    bf16 where the fused SwiGLU kernel keeps f32, so a TP-only fallback to
    it would change the logits (and greedy tokens) on the chip."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.kernels import ops
        from repro.models.layers import mlp_apply, mlp_init
        from repro.models.linops import quantize_param_tree

        p = quantize_param_tree({"ffn": mlp_init(jax.random.PRNGKey(0), 256,
                                                 384, jnp.bfloat16)})["ffn"]
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 256), jnp.bfloat16)
        mesh = jax.make_mesh((2, 2), ("data", "model"))

        def body(p, x):
            with ops.tp_shard("model", 2):
                return mlp_apply(p, x)

        sharded = jax.jit(jax.shard_map(body, mesh=mesh,
                                        in_specs=(P(), P("data")),
                                        out_specs=P("data"), check_vma=False))
        ops.set_impl("kernel")
        try:
            want = np.asarray(jax.jit(mlp_apply)(p, x), np.float32)
            got = np.asarray(sharded(p, x), np.float32)
        finally:
            ops.set_impl("auto")
        assert (got == want).all(), float(np.abs(got - want).max())
        print("OK")
    """, devices=4)
    assert "OK" in out


# --------------------------------------------- service eviction isolation
def test_sharded_service_cancel_and_deadline_evict_in_isolation():
    """The streaming service over a mesh engine (PR-7): a mid-flight
    cancel and a round-clock deadline each evict exactly their own
    request - every other stream stays token-for-token equal to the
    single-device batch run, and all replica slots come back."""
    out = _run_subprocess(_parity_case("""
        import time
        from repro.serve import ServeService

        cfg = reduced_config("stablelm-1.6b")
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        lens = [3, 9, 12, 5, 17, 7]
        ref = ServeEngine(cfg, params, slots=4, max_len=64,
                          buckets=(8, 16, 32))
        refs = requests(cfg, lens, max_new=16)
        ref.run(refs)
        want = {r.uid: tuple(r.generated) for r in refs}

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        eng = ShardedServeEngine(cfg, params, mesh=mesh, slots_per_replica=1,
                                 max_len=64, buckets=(8, 16, 32))
        eng._clock = lambda: float(eng._round)   # deadlines in rounds
        svc = ServeService(eng, max_pending=16).start()
        prompts = [r.prompt for r in requests(cfg, lens, max_new=16)]
        streams = [svc.submit(p, max_new=16,
                              deadline_s=(4.0 if i == 2 else None))
                   for i, p in enumerate(prompts)]
        got1 = []
        while len(got1) < 2:                     # uid 1: cancel mid-flight
            got1.extend(streams[1].drain()[0])
            time.sleep(0.005)
        svc.cancel(1, reason="client gone")
        res = {s.uid: s.result(timeout=600) for s in streams}
        svc.stop()

        toks, fin, err = res[1]
        assert fin == "cancel" and err == "client gone"
        all1 = tuple(got1) + tuple(toks)
        assert all1 == want[1][:len(all1)] and len(all1) < 16
        toks2, fin2, err2 = res[2]
        assert fin2 == "deadline" and len(toks2) < 16
        assert tuple(toks2) == want[2][:len(toks2)]
        for uid in (0, 3, 4, 5):                 # untouched peers: exact
            toks, fin, _ = res[uid]
            assert fin == "complete" and tuple(toks) == want[uid], uid
        assert eng.stats["cancelled"] == 1
        assert eng.stats["deadline_expired"] == 1
        assert eng.stats["replica_occupancy"] == [0, 0, 0, 0]
        assert eng._free_total() == eng.slots
        print("OK")
    """))
    assert "OK" in out


# ------------------------------------------------- N-step decode fast path
def test_sharded_nstep_decode_matches_single_step():
    """decode_steps=4 on a 4x2 mesh: N decode steps per dispatch run
    inside one shard_map-ed scan (per-replica in-body sampling, one
    (slots, N) backhaul) and must equal the single-device N=1 engine
    token-for-token - fp and int8-KV, greedy and temperature, slot-row
    and paged."""
    out = _run_subprocess(_parity_case("""
        import dataclasses
        MIXED = [3, 5, 8, 9, 12, 16, 17, 23, 30, 4, 11, 27]
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cells = [("fp greedy slotrow", None, 0.0, {}),
                 ("fp temp paged", None, 0.9,
                  dict(paged=True, page_size=16)),
                 ("int8 temp paged", "dynamic", 0.9,
                  dict(paged=True, page_size=16))]
        for name, qkv, temp, kw in cells:
            cfg = reduced_config("stablelm-1.6b")
            if qkv:
                cfg = dataclasses.replace(cfg, quant_kv=qkv)
            params = build_model(cfg).init(jax.random.PRNGKey(0))
            ref = ServeEngine(cfg, params, slots=4, max_len=64,
                              buckets=(8, 16, 32), temperature=temp)
            want = outputs(ref, cfg, MIXED, max_new=9)
            eng = ShardedServeEngine(cfg, params, mesh=mesh,
                                     slots_per_replica=2, max_len=64,
                                     buckets=(8, 16, 32), temperature=temp,
                                     decode_steps=4, **kw)
            got = outputs(eng, cfg, MIXED, max_new=9)
            assert got == want, (name, [i for i, (a, b) in
                                        enumerate(zip(got, want)) if a != b])
            assert eng.stats["decode_compiles"] == 1
            # full blocks: dispatches-per-token is exactly 1/4 (two
            # admission waves of lockstep rows, 8 decode tokens each ->
            # 2 dispatches per wave)
            assert eng.stats["decode_tokens"] == len(MIXED) * 8
            assert eng.stats["decode_steps"] == 4
            print("OK", name)
        print("OK all")
    """))
    assert "OK all" in out
