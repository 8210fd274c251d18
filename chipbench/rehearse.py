"""Compile a configuration's serving programs for a described TPU v5e, without
a chip, and print each program's device memory (``memory_analysis``).

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/rehearse.py \
        chipbench/configs/stablelm-1.6b.pdq-int8.json --slots 16 32

For each slot count: the prefill at the largest configured bucket (slots x
bucket tokens, the largest program), the N-step paged decode block, and the landing
of a prefill batch into the page pool.  Arguments are shapes only (the
engine's pools are built with ``jax.eval_shape``), so nothing is allocated
here; the Pallas kernels compile for the chip (Mosaic), not interpreted.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def rehearse(config: dict, slots: int) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import bench
    from chipbench.server import serve_config
    from repro.kernels import ops
    from repro.models import build_model
    from repro.models.linops import quantize_param_tree
    from repro.serve.engine import ServeEngine

    ops.set_impl("kernel")
    ops._interpret = lambda: False          # compile Mosaic, as on the chip
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    class ShapeEngine(ServeEngine):
        def _init_pools(self):
            n = self.pool_pages * self.n_replicas
            self.caches = jax.eval_shape(lambda: self._paged_ops.init(n))
            self._prefill_pool = jax.eval_shape(
                lambda: self.bundle.init_caches(self.slots, self.max_len,
                                                self.mem_len))

    cfg = bench.load_model(bench.family(config)).arch_config(config)
    sc = dataclasses.replace(serve_config(config), slots=slots)
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    if sc.quantize_weights:
        params = jax.eval_shape(quantize_param_tree, params)
    eng = ShapeEngine(cfg, params, slots=slots, max_len=sc.max_len,
                      buckets=sc.buckets, decode_steps=sc.decode_steps,
                      paged=True, page_size=sc.page_size)

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    # the capacity bucket (max_len - 1) does not trace (see PERF.md), so the
    # largest program the traffic reaches is the largest configured bucket
    B, cap = slots, max(sc.buckets)
    N = eng.pool_pages * eng.n_replicas
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    progs = {
        f"prefill_{cap}": (eng._prefill_many, (
            on(params), {"tokens": sds((B, cap))}, on(eng._prefill_pool),
            sds((B,)))),
        "decode": (eng._decode_paged, (
            on(key), on(params), on(eng.caches), sds((B, eng.n_pp)),
            sds((B, 1)), sds((B, 1)), sds((B,)), sds((B,)), sds((B,)))),
        "land": (eng._land, (on(eng.caches), on(eng._prefill_pool), sds((B,)),
                             sds((N,)), sds((N,)))),
    }
    out = {}
    for name, (fn, args) in progs.items():
        ma = fn.lower(*args).compile().memory_analysis()
        out[name] = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")}
        out[name]["total_bytes"] = (out[name]["argument_size_in_bytes"]
                                    + out[name]["output_size_in_bytes"]
                                    - out[name]["alias_size_in_bytes"]
                                    + out[name]["temp_size_in_bytes"])
        print(f"slots={slots} {name}: {json.dumps(out[name])}", flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--slots", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    with open(args.config) as f:
        config = json.load(f)
    for s in args.slots:
        rehearse(config, s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
