import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. builds the production mesh (16x16 single pod / 2x16x16 multi-pod),
  2. lowers the appropriate step function (train_step for train shapes,
     prefill / decode for serving shapes) against ShapeDtypeStruct inputs
     with full in/out shardings - no array is ever allocated,
  3. compiles it (proves the sharding config is coherent end-to-end),
  4. records memory_analysis / cost_analysis / per-collective byte counts
     into benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>.json,
     which §Roofline and benchmarks/roofline.py consume.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-done]
"""
import argparse
import json
import re
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALL_ARCHS, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh, mesh_info
from repro.models import SHAPES, build_model
from repro.models import context as mctx
from repro.optim import AdamWConfig
from repro.launch import hlo_analysis
from repro.train.train_step import (abstract_state, dist_context_for,
                                    state_specs)

ART_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    "benchmarks", "artifacts", "dryrun"))

# int8 optimizer moment states for the configs whose fp32 Adam would not fit
# 16 GB/chip on a single pod (DESIGN.md Sec. 5).
QUANT_OPT_STATE = {"arctic-480b", "deepseek-v2-236b"}

_COLL_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=\s*(?:\()?([a-z0-9]+\[[^\]]*\])")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "u4": 1, "s4": 1}


def _shape_bytes(text: str) -> int:
    m = _SHAPE_RE.match(text)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum collective payload bytes (per device) from partitioned HLO.

    Model: all-reduce counts 2x its shape (ring reduce+broadcast);
    all-gather counts its (full) result; reduce-scatter / all-to-all /
    collective-permute count their result bytes.
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind, shape = m.group(1), m.group(2)
        b = _shape_bytes(shape)
        if kind == "all-reduce":
            b *= 2
        out[kind] = out.get(kind, 0) + b
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def _sds(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def skip_reason(cfg, shape_name: str) -> str | None:
    if shape_name == "long_500k" and not cfg.long_context:
        return ("pure full-attention arch: long_500k needs sub-quadratic "
                "attention (DESIGN.md Arch-applicability)")
    return None


def lower_cell(arch: str, shape_name: str, mesh, variant: str = "baseline"):
    """Returns the lowered computation for one cell.

    variant='opt' applies the beyond-baseline schedule (EXPERIMENTS.md Perf):
    train -> remat policy 'save_heavy'; prefill -> sequence parallelism
    (tokens + KV cache sharded over 'model', parallel-q attention).
    """
    import dataclasses as _dc
    cfg = get_config(arch)
    if variant == "opt" and SHAPES[shape_name].kind == "train":
        cfg = _dc.replace(cfg, remat="save_heavy")
    if (variant == "opt" and SHAPES[shape_name].kind == "decode"
            and cfg.ssm is None and cfg.mla is None):
        # PDQ-int8 serving: int8 KV cache + W8A8 weights (paper tie-in);
        # the MLA latent cache has no int8 layout
        cfg = _dc.replace(cfg, quant_kv="dynamic")
    bundle = build_model(cfg)
    sp = SHAPES[shape_name]
    specs = bundle.input_specs(shape_name)
    ctx = dist_context_for(mesh)

    if sp.kind == "train":
        opt_cfg = AdamWConfig(quant_state=arch in QUANT_OPT_STATE)
        with mctx.use_context(ctx):
            st = abstract_state(bundle, opt_cfg)
            sspec = state_specs(st, mesh)
            state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                                    is_leaf=lambda x: isinstance(x, P))
            batch_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), shd.batch_spec(mesh, specs),
                is_leaf=lambda x: isinstance(x, P))
            from repro.optim import schedule as _sched
            from repro.train.train_step import make_step_fn
            step = make_step_fn(bundle, opt_cfg,
                                lambda s: _sched.warmup_cosine(s))
            fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, NamedSharding(mesh, P())),
                         donate_argnums=(0,))
            return fn.lower(st, specs)

    if variant == "opt" and sp.kind == "decode":
        from repro.models.linops import quantize_param_tree
        params = jax.eval_shape(
            lambda: quantize_param_tree(bundle.init(jax.random.PRNGKey(0))))
    else:
        params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    pspec = shd.param_specs(params, mesh)
    params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                             is_leaf=lambda x: isinstance(x, P))

    if sp.kind == "prefill":
        mem_len = specs.get("frames").shape[1] if "frames" in specs else 0
        caches = jax.eval_shape(
            lambda: bundle.init_caches(sp.batch, sp.seq, mem_len))
        sp_prefill = variant == "opt"
        cache_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            shd.cache_spec(mesh, caches, sp.batch, seq_over_model=sp_prefill),
            is_leaf=lambda x: isinstance(x, P))
        batch_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            shd.batch_spec(mesh, specs, seq_over_model=sp_prefill),
            is_leaf=lambda x: isinstance(x, P))
        with mctx.use_context(ctx):
            fn = jax.jit(bundle.prefill,
                         in_shardings=(params_sh, batch_sh, cache_sh),
                         out_shardings=(NamedSharding(mesh, P()), cache_sh),
                         donate_argnums=(2,))
            return fn.lower(params, specs, caches)

    # decode
    caches = specs["caches"]
    cache_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), shd.cache_spec(mesh, caches, sp.batch),
        is_leaf=lambda x: isinstance(x, P))
    tok_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        shd.batch_spec(mesh, {"tokens": specs["tokens"],
                              "positions": specs["positions"]}),
        is_leaf=lambda x: isinstance(x, P))
    with mctx.use_context(ctx):
        fn = jax.jit(bundle.decode_step,
                     in_shardings=(params_sh, cache_sh, tok_sh["tokens"],
                                   tok_sh["positions"]),
                     out_shardings=(NamedSharding(mesh, P()), cache_sh),
                     donate_argnums=(1,))
        return fn.lower(params, caches, specs["tokens"], specs["positions"])


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             variant: str = "baseline") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cfg = get_config(arch)
    reason = skip_reason(cfg, shape_name)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "variant": variant}
    if reason:
        rec.update(status="skipped", reason=reason)
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with mesh:
        lowered = lower_cell(arch, shape_name, mesh, variant)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        try:
            mem = compiled.memory_analysis()
            mem_rec = {
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
            }
        except Exception as e:  # pragma: no cover
            mem_rec = {"error": str(e)}
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        scaled = hlo_analysis.analyze(hlo)
    rec.update(
        status="ok",
        mesh_info=mesh_info(mesh),
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        cost_keys={k: float(v) for k, v in cost.items()
                   if isinstance(v, (int, float)) and k in (
                       "flops", "bytes accessed", "transcendentals",
                       "utilization operand 0 {}", "bytes accessed output {}")},
        memory=mem_rec,
        collectives=coll,
        scaled_dot_flops=float(scaled.dot_flops),
        scaled_collectives={k: float(v)
                            for k, v in scaled.collective_bytes.items()},
        scaled_collective_total=float(scaled.total_collective_bytes),
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ALL_ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=["baseline", "opt"])
    args = ap.parse_args()

    os.makedirs(ART_DIR, exist_ok=True)
    cells = []
    archs = list(ALL_ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    n_ok = n_skip = n_fail = 0
    for arch, shape, mp in cells:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        suffix = "" if args.variant == "baseline" else f"__{args.variant}"
        path = os.path.join(ART_DIR, f"{arch}__{shape}__{mesh_name}{suffix}.json")
        if args.skip_done and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("status") in ("ok", "skipped"):
                    print(f"[cached ] {arch} {shape} {mesh_name}")
                    n_ok += 1
                    continue
        try:
            rec = run_cell(arch, shape, mp, args.variant)
        except Exception as e:
            rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                   "status": "failed", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        tag = rec["status"]
        n_ok += tag == "ok"
        n_skip += tag == "skipped"
        n_fail += tag == "failed"
        extra = ""
        if tag == "ok":
            extra = (f"flops={rec['flops']:.3e} coll={rec['collectives']['total']:.3e}B "
                     f"lower={rec['lower_s']}s compile={rec['compile_s']}s")
        elif tag == "failed":
            extra = rec["error"][:200]
        print(f"[{tag:7s}] {arch} {shape} {mesh_name} {extra}", flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
