"""Run one benchmark cell and print its result as the last line of stdout.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX.  It starts ``chipbench/server.py``, which
takes the chip, builds the cell's engine behind the HTTP front door and warms
up its shapes; then it drives the cell's traffic mix as HTTP clients, reads
the server's counters at the window's edges, asks the server to compare a
sample of the finished requests with the plain reference, and prints

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"], "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  ``--control 1`` serves the benchmark's
own calibration and is never used by a check: it puts the lower-precision
control in the program's place, so the comparison judges the control's gaps
against the configuration's limits and ``correct`` has to come out false.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import bench, loadgen  # noqa: E402
from chipbench.readers import percentile  # noqa: E402

READY_TIMEOUT_S = 1100.0
CHECK_TIMEOUT_S = 240.0
TRACE_S = 4.0          # the traced span: the last seconds of the window
# numbers of the reference comparison held against the configuration's limits
COMPARED = ("gap_max", "gap_mean")


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Child:
    """The server process and its line protocol (JSON per line, tagged)."""

    TAG = "@@chipbench "

    def __init__(self, proc):
        self.proc = proc

    @classmethod
    async def start(cls, spec: dict, root: Path, argv=None):
        env = dict(os.environ)
        # the program under test is the checkout's own, never another copy
        env["PYTHONPATH"] = os.pathsep.join([str(root / "src"), str(root)])
        env["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
        argv = argv or [sys.executable, str(root / "chipbench" / "server.py")]
        proc = await asyncio.create_subprocess_exec(
            *argv, json.dumps(spec), stdin=asyncio.subprocess.PIPE,
            stdout=asyncio.subprocess.PIPE, env=env, limit=1 << 26)
        return cls(proc)

    async def read(self, timeout: float) -> dict:
        while True:
            line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
            if not line:
                rc = await self.proc.wait()
                raise RuntimeError(f"server exited with code {rc}")
            text = line.decode()
            if text.startswith(self.TAG):
                msg = json.loads(text[len(self.TAG):])
                if msg.get("error"):
                    raise RuntimeError(f"server: {msg['error']}")
                return msg

    async def call(self, cmd: str, timeout: float = 60.0, **kw) -> dict:
        self.proc.stdin.write((json.dumps({"cmd": cmd, **kw}) + "\n").encode())
        await self.proc.stdin.drain()
        return await self.read(timeout)

    async def close(self) -> None:
        if self.proc.returncode is None:
            try:
                self.proc.stdin.close()
                await asyncio.wait_for(self.proc.wait(), 60)
            except (asyncio.TimeoutError, BrokenPipeError, ConnectionResetError):
                self.proc.kill()
                await self.proc.wait()


class Ctx:
    """What a traffic kind's ``drive`` sees: the cell, the port, the seed,
    and the window's edges, which it opens and closes."""

    def __init__(self, cell, child, port, seed, seconds, trace, traffic=None):
        self.cell = cell
        self.config = cell.config
        self.traffic = traffic or cell.traffic
        self.vocab = int(cell.config["vocab_size"])
        self.child = child
        self.port = port
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = trace
        self.t0 = self.t1 = None
        self.snap0 = self.snap1 = None
        self.lateness: list[float] = []
        self._trace_task = None

    async def open_window(self, t0: float | None = None) -> None:
        self.snap0 = await loadgen.snapshot(self.port)
        await self.child.call("mark", what="open")
        self.t0 = loadgen.now() if t0 is None else t0
        if self.trace:
            self._trace_task = asyncio.create_task(self._traced())

    async def _traced(self) -> None:
        await asyncio.sleep(max(0.0, self.t0 + self.seconds - TRACE_S
                                - loadgen.now()))
        await self.child.call("trace_start")

    async def close_window(self) -> None:
        self.t1 = loadgen.now()
        self.snap1 = await loadgen.snapshot(self.port)
        if self._trace_task is not None:
            await self._trace_task
            await self.child.call("trace_stop")
        await self.child.call("mark", what="close")


def log_window(ctx, reqs) -> None:
    """What the window did, for the reader of a run's log: the server's
    counters over the window and the tokens received in each second."""
    keys = ("prefill_batches", "prefill_requests", "decode_steps",
            "decode_tokens", "completed", "failed", "preemptions",
            "prefix_hits", "cow_copies")
    s0, s1 = ctx.snap0["stats"], ctx.snap1["stats"]
    log("window counters: " + json.dumps(
        {k: s1[k] - s0[k] for k in keys if k in s0 and k in s1}))
    per_s = [0] * int(ctx.seconds + 1)
    for r in reqs:
        for t in r.times:
            if ctx.t0 <= t < ctx.t0 + ctx.seconds:
                per_s[int(t - ctx.t0)] += 1
    log(f"tokens per second of the window: {per_s[:int(ctx.seconds)]}")


def sample_for_check(reqs, t0, n: int, seed: int):
    """The finished requests compared with the reference: those that
    completed inside the window, the longest of them and ``n - 1`` more
    drawn from the seed."""
    import numpy as np
    done = [r for r in reqs if r.completed and r.times and r.times[-1] >= t0]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), -r.idx))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


async def run_cell(args, cell) -> dict:
    kind = bench.load_kind(cell.traffic["kind"], cell.root)
    lo, hi = kind.prompt_range(cell.traffic)
    spec = {"config": cell.config, "seed": args.seed, "chips": cell.chips,
            "root": str(cell.root),
            "warm_buckets": loadgen.buckets_used(cell.config["serve"], lo, hi)}
    child = await Child.start(spec, cell.root, argv=args.server_argv)
    try:
        ready = await child.read(READY_TIMEOUT_S)
        log(f"server ready: {json.dumps(ready)}")
        ctx = Ctx(cell, child, ready["port"], args.seed, args.seconds,
                  args.trace)
        reqs = await kind.drive(ctx)
        t_stop = loadgen.now()
        lat = sorted(ctx.lateness)
        log(f"generator lateness: n={len(lat)} p50="
            f"{1e3 * percentile(lat, 50):.3f} ms p99="
            f"{1e3 * percentile(lat, 99):.3f} ms max={1e3 * lat[-1]:.3f} ms"
            if len(lat) > 1 else "generator lateness: too few requests")
        log_window(ctx, reqs)
        sample = sample_for_check(reqs, ctx.t0,
                                  int(cell.traffic["check_sample"]), args.seed)
        check = await child.call(
            "check", timeout=CHECK_TIMEOUT_S, control=bool(args.control),
            seqs=[{"prompt": r.prompt, "tokens": r.tokens} for r in sample])
    finally:
        await child.close()
    run = {"cell": cell.name, "config": cell.config, "traffic": cell.traffic,
           "seconds": ctx.seconds, "t_start": T_START, "t0": ctx.t0,
           "t1": ctx.t1, "t_stop": t_stop,
           "records": [r.as_record() for r in reqs],
           "snap0": ctx.snap0, "snap1": ctx.snap1,
           "trace": check.get("trace"), "device": ready["device"],
           "peaks": bench.load_peaks(cell.root)[ready["device"]["kind"]]}
    return finish(args, cell, run, check, ready)


def finish(args, cell, run, check, ready) -> dict:
    metrics = {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    for m in wanted:
        value = bench.load_metric(m["name"], cell.root).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    window = [r for r in run["records"] if not r["warmup"]]
    failed = [r for r in window if r["status"] is not None and (
        r["status"] != 200 or r["error"] or r["finish"] not in (
            None, "complete"))]
    limits = cell.config["check"]
    checks = {"requests_compared": {"value": check["n_seqs"], "min": 1},
              "tokens_compared": {"value": check["n_tokens"], "min": 1}}
    correct = check["n_seqs"] >= 1 and check["n_tokens"] >= 1
    # with --control 1 the control's gaps stand in for the program's
    prefix = "control_" if args.control else ""
    compared = [k for k in COMPARED if limits.get(k) is not None]
    for k in compared:
        got = check[prefix + k]
        checks[prefix + k] = {"value": got, "limit": limits[k]}
        correct = correct and got is not None and got <= limits[k]
    # a configuration with no limit set yet is never correct
    correct = correct and bool(compared)
    device = dict(ready["device"], memory_peak_bytes=check["memory_peak_bytes"])
    # what the window holds, apart from the set-up's transient peak
    device.update({k: check[k] for k in ("memory_in_use_bytes",
                                         "memory_setup_peak_bytes")
                   if check.get(k) is not None})
    if args.trace and run["trace"]:
        device.update(busy_s=run["trace"]["busy_s"],
                      window_s=run["trace"]["window_s"])
    log(f"reference: gap_max={check['gap_max']!r} "
        f"gap_mean={check['gap_mean']!r} "
        f"argmax_agree={check['argmax_agree']!r} "
        f"({check['n_tokens']} tokens "
        f"in {check['n_seqs']} requests, {check['seconds']:.1f} s)")
    out = {"correct": bool(correct), "attempted": len(window),
           "failed": len(failed), "metrics": metrics, "device": device}
    if args.trace and run["trace"]:
        out["breakdown"] = run["trace"]["breakdown"]
    out["checks"] = checks
    for name, c in checks.items():
        bound = (f"limit {c['limit']!r}" if "limit" in c
                 else f"at least {c['min']!r}")
        print(f"check {name}: {c['value']!r} ({bound})", file=sys.stderr)
    sys.stderr.flush()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=str(ROOT))
    args = ap.parse_args(argv)
    args.server_argv = None
    return run(args)


def run(args) -> int:
    try:
        cell = bench.load_cell(args.workload, Path(args.root))
        out = asyncio.run(run_cell(args, cell))
    except Exception as e:                       # no result line on failure
        import traceback
        traceback.print_exc()
        log(f"FAIL: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
