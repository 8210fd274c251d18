"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

``trace_layers.events_from_xplane`` turns the trace file into plain lists
(the one place that knows the profiler's format); ``reduce_events`` works on
those lists only, so a recorded trace can be checked without JAX.
``reduce_all`` adds ``trace_layers.reduce_layers``'s readings of the
program's own names (``host_spans``, ``idle_in_host_s``, ``scope_s``).

On a TPU the op line ("XLA Ops") holds every HLO instruction that ran, named
by its HLO text, with control-flow ops (a ``while`` of a scan) spanning the
ops of their bodies.  So:

  busy        the union of the op intervals, averaged over the chips used
  self time   an op's duration less that of the ops nested in it
  idle gaps   the holes in the union, each named by what the host was doing
              (the shortest host event covering most of the gap)
  custom      the Pallas kernels (``custom-call``), summed by signature: the
              kernels carry no name in the trace, so a roofline reader tells
              its kernel by the operand and result shapes
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OP_LINE = "XLA Ops"
TOP_N = 10
_LAYOUT = re.compile(r"\{[^{}]*\}")


def compact(hlo: str, limit: int = 400) -> str:
    """An op's HLO text without layouts and attributes, at most ``limit``
    characters: ``%name = shape op(operand shapes ...)``."""
    hlo = hlo.split(", custom_call_target=")[0]
    hlo = _LAYOUT.sub("", hlo)
    return hlo[:limit]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops) -> list[float]:
    """Each op's duration less the time of the ops nested inside it (ns)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [op[2] for op in ops]
    stack: list[int] = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return own


def _label(gap, host) -> str:
    s, e = gap
    width = e - s
    covering = [(hd, name) for name, hs, hd in host
                if min(e, hs + hd) - max(s, hs) >= 0.5 * width]
    if covering:
        return min(covering)[1]
    return "no host event"


def op_label(text: str) -> str:
    """``%name kind`` of an op's compact text."""
    name, _, rest = text.partition(" = ")
    m = re.search(r"[\])] ([a-z][a-z\-]*)\(", rest)
    return f"{name} {m.group(1)}" if m else name


def reduce_events(ev: dict, window_s: float) -> dict | None:
    devices = {k: v for k, v in ev["devices"].items() if v}
    if not devices:
        return None
    busy, gaps = [], []
    per_op: dict[str, float] = defaultdict(float)
    custom: dict[str, list] = {}
    for ops in devices.values():
        u = _union([(s, s + d) for _, s, d in ops])
        busy.append(sum(e - s for s, e in u) * 1e-9)
        gaps += [(a[1], b[0]) for a, b in zip(u, u[1:]) if b[0] > a[1]]
        for (text, _, d), own in zip(ops, self_times(ops)):
            per_op[op_label(text)] += own * 1e-9
            if " custom-call(" in text:
                sig = text.partition(" = ")[2]
                c = custom.setdefault(sig, [0, 0.0])
                c[0] += 1
                c[1] += d * 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label(g, ev["host"]), (g[1] - g[0]) * 1e-9] for g in gaps[:TOP_N]]
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP_N]
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "n_ops": sum(len(o) for o in devices.values()),
            "custom_calls": [[sig, n, s] for sig, (n, s) in custom.items()],
            "breakdown": {"device_ops": [[n, s] for n, s in top],
                          "idle_gaps": idle}}


def reduce_all(ev: dict, window_s: float) -> dict | None:
    """``reduce_events`` with ``trace_layers.reduce_layers`` merged in."""
    from chipbench import trace_layers
    out = reduce_events(ev, window_s)
    if out is not None:
        out.update(trace_layers.reduce_layers(ev))
    return out


def reduce_dir(trace_dir: str, window_s: float) -> dict | None:
    from chipbench import trace_layers
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    return reduce_all(trace_layers.events_from_xplane(files[0]), window_s)
