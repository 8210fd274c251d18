"""The benchmark's server process: it holds the chip.

Started by ``run.py`` with one JSON argument (the cell's configuration, the
seed, the prefill buckets its traffic uses).  It checks the device, makes
the weights on the device from the seed, builds the engine with
``build_engine(ServeConfig(...))``, wraps it in ``ServeService`` behind
``HttpFrontend``, warms up the cell's shapes, and prints a ready line.  Then
it answers commands, one JSON object per stdin line:

  mark         {"what": "open" | "close"}: the window's edges (compile
               count, device memory in use and its peak so far)
  trace_start  start a ``jax.profiler`` trace into a private temp directory
  trace_stop   stop it; the reduction runs later, at ``check``
  check        {"seqs": [...], "control": bool}: stop serving, read the
               memory peak, free the engine, reduce the trace, and compare
               each served sequence with the plain reference

Replies are single lines on the original stdout, tagged ``@@chipbench``;
everything else the process prints goes to stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

TAG = "@@chipbench "
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Server:
    def __init__(self, spec: dict, proto):
        self.spec = spec
        self.proto = proto
        self.config = spec["config"]
        self.seed = int(spec["seed"])
        self.compiles = 0
        self.window_compiles = None
        self.memory = {}
        self.trace_dir = None
        self.trace_t = None

    def reply(self, **msg) -> None:
        self.proto.write(TAG + json.dumps(msg) + "\n")
        self.proto.flush()

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import jax

        from chipbench import device, weights
        self.device = device.require_chip(int(self.spec["chips"]),
                                          Path(self.spec["root"]))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

        from repro.serve import HttpFrontend, ServeService, build_engine
        self.cfg = arch_config(self.config)
        params = weights.make_params(self.config, self.seed)
        weights.check_layout(params, self.cfg)
        eng = build_engine(serve_config(self.config), cfg=self.cfg,
                           params=params)
        # the engine holds what it serves (for PDQ, its quantized copy)
        del params
        gc.collect()
        self.eng = eng
        self._warm_engine_programs()
        serve = self.config["serve"]
        self.svc = ServeService(eng, max_pending=int(serve["max_pending"])).start()
        self.loop = asyncio.new_event_loop()
        self.loop_thread = threading.Thread(target=self.loop.run_forever,
                                            daemon=True)
        self.loop_thread.start()
        self.fe = HttpFrontend(self.svc, port=0)
        asyncio.run_coroutine_threadsafe(self.fe.start(), self.loop).result(60)
        self._warm_requests()

    def _warm_engine_programs(self) -> None:
        """Programs the traffic may reach that a warm-up request does not:
        the paged pool's copy-on-write page copy."""
        import jax.numpy as jnp
        import numpy as np
        eng = self.eng
        if eng.paged:
            cmap = np.full((eng.pool_pages * eng.n_replicas,), -1, np.int32)
            eng.caches = eng._page_copy(eng.caches, jnp.asarray(cmap))

    def _warm_requests(self) -> None:
        """One request per prefill bucket the traffic uses, each long
        enough to run two decode blocks: every program of the window
        compiles (or loads from the cache) here."""
        import numpy as np
        rng = np.random.default_rng([self.seed, 9])
        vocab = int(self.config["vocab_size"])
        n_new = 2 * int(self.config["serve"]["decode_steps"]) + 1
        streams = [self.svc.submit(rng.integers(1, vocab, b), max_new=n_new)
                   for b in self.spec["warm_buckets"]]
        for s in streams:
            toks, reason, err = s.result(timeout=900)
            if reason != "complete" or err:
                raise RuntimeError(f"warm-up request failed: {reason} {err}")

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    # ---------------------------------------------------------- commands
    def serve(self) -> None:
        import jax
        self.reply(port=self.fe.port, device=self.device,
                   setup_s=time.perf_counter() - T_START,
                   buckets=list(self.eng.buckets), slots=self.eng.slots,
                   compiles=self.compiles)
        for line in sys.stdin:
            msg = json.loads(line)
            cmd = msg["cmd"]
            if cmd == "mark":
                what = msg["what"]
                stats = jax.devices()[0].memory_stats() or {}
                self.memory[what] = stats
                if what == "open":
                    self.window_compiles = self.compiles
                else:
                    n = self.compiles - self.window_compiles
                    print(f"[server] compiles inside the window: {n}",
                          file=sys.stderr, flush=True)
                print(f"[server] device memory at window {what}: in use "
                      f"{stats.get('bytes_in_use')}, peak so far "
                      f"{stats.get('peak_bytes_in_use')}",
                      file=sys.stderr, flush=True)
                self.reply(ok=True)
            elif cmd == "trace_start":
                self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
                jax.profiler.start_trace(self.trace_dir)
                self.trace_t = time.perf_counter()
                self.reply(ok=True)
            elif cmd == "trace_stop":
                self.trace_t = time.perf_counter() - self.trace_t
                jax.profiler.stop_trace()
                self.reply(ok=True)
            elif cmd == "check":
                self.reply(**self.check(msg))
                return
            else:
                raise ValueError(f"unknown command {cmd!r}")

    def stop_serving(self) -> None:
        self.svc.request_drain()
        self.svc.join(120)
        asyncio.run_coroutine_threadsafe(self.fe.stop(), self.loop).result(60)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.loop_thread.join(10)
        if self.svc.error is not None:
            raise RuntimeError(f"service loop died: {self.svc.error!r}")
        st = self.eng.stats
        if st["pdq_fallbacks"]:
            raise RuntimeError(f"{st['pdq_fallbacks']} PDQ projections fell "
                               "back to fp")

    def check(self, msg: dict) -> dict:
        import jax

        from chipbench import reference, weights
        self.stop_serving()
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
        eng = self.eng
        eng.caches = eng._prefill_pool = eng.params = None
        self.eng = self.svc = self.fe = None
        del eng
        gc.collect()
        # the set-up's peak (a PDQ engine quantizes a bf16 tree) against
        # what the window keeps resident
        out = {"memory_peak_bytes": peak,
               "memory_setup_peak_bytes":
                   self.memory.get("open", {}).get("peak_bytes_in_use"),
               "memory_in_use_bytes":
                   self.memory.get("close", {}).get("bytes_in_use")}
        if self.trace_dir is not None:
            from chipbench import trace_reduce
            out["trace"] = trace_reduce.reduce_dir(self.trace_dir,
                                                   self.trace_t)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        t0 = time.perf_counter()
        params = weights.make_params(self.config, self.seed)
        res = reference.compare(params, self.config, msg["seqs"],
                                control=bool(msg.get("control")))
        out.update(res, seconds=time.perf_counter() - t0)
        return out


def arch_config(config: dict):
    """The program's model configuration, with every width the file states."""
    from repro.configs import get_config
    base = get_config(config["arch"])
    m = {"n_layers": config["num_hidden_layers"],
         "d_model": config["hidden_size"],
         "n_heads": config["num_attention_heads"],
         "n_kv_heads": config["num_key_value_heads"],
         "head_dim": config["hidden_size"] // config["num_attention_heads"],
         "d_ff": config["intermediate_size"],
         "vocab": config["vocab_size"],
         "rope_theta": float(config["rope_theta"]),
         "norm_eps": float(config["norm_eps"]),
         "dtype": config["torch_dtype"],
         "quant_kv": config["serve"]["kv"]}
    return dataclasses.replace(base, **m).validate()


def serve_config(config: dict):
    from repro.serve import ServeConfig
    s = config["serve"]
    return ServeConfig(arch=config["arch"], reduced=False,
                       slots=int(s["slots"]), max_len=int(s["max_len"]),
                       buckets=tuple(int(b) for b in s["buckets"]),
                       decode_steps=int(s["decode_steps"]),
                       quantize_weights=bool(s["quantize_weights"]),
                       paged=bool(s["paged"]), page_size=int(s["page_size"]),
                       prefix_sharing=bool(s["prefix_sharing"]))


def main() -> int:
    spec = json.loads(sys.argv[1])
    # the reply channel is the original stdout; everything printed by this
    # process (or the libraries it loads) goes to stderr instead
    proto = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    server = Server(spec, proto)
    try:
        server.setup()
        server.serve()
    except Exception as e:
        traceback.print_exc()
        try:
            server.reply(error=f"{type(e).__name__}: {e}")
        except Exception:
            pass
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
