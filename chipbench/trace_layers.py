"""The program's own names in a ``jax.profiler`` trace (the reduction needs
no JAX; ``events_from_xplane``, which reads the file, needs it).

``trace_reduce`` names what it finds by HLO instruction and by the
shortest host event.  The program adds names of its own: every
``Telemetry.span`` writes a ``TraceAnnotation`` on the host plane
(``plan:decode``, ``dispatch:decode``, ``fetch:decode``, ``apply:decode``,
``ingress``, ...), the paged pool's gather and writeback run under
``jax.named_scope``, and every ``pallas_call`` carries a ``name=``.  From
those:

  host_spans      the program's spans by name: [count, seconds]
  idle_in_host_s  device-idle time (the holes in the union of op
                  intervals, as ``trace_reduce`` finds them) that overlaps
                  the union of the loop's own host work: the ``plan:*``,
                  ``dispatch:*`` and ``apply:*`` spans, ``ingress``,
                  ``page_copy`` and ``page_stats``
  scope_s         device self time by the program's name on the op: its
                  named scope, else its kernel's name ("" for neither)

Averaged over the chips traced, as ``busy_s`` is.  The events keep
``trace_reduce``'s shape, with the names in a parallel ``scopes`` list per
chip, so ``trace_reduce.reduce_events`` reads them unchanged.
"""
from __future__ import annotations

import re
from collections import defaultdict

from chipbench import trace_reduce

# named scopes first: a kernel that runs inside one counts for the scope
SCOPES = ("paged_gather", "paged_writeback")
KERNELS = ("w8a8_swiglu_matmul", "w8a8_matmul", "decode_attend_i8kv_fused",
           "decode_attend_i8kv", "cache_scatter", "pdq_prologue",
           "dequantize", "quantize", "act_stats")
_TOKEN = re.compile(r"[A-Za-z0-9_]+")
PROGRAM_SPAN = re.compile(r"^((plan|launch|dispatch|fetch|apply):[a-z_]+|"
                          r"ingress|idle|page_copy|page_stats|snapshot)$")
HOST_WORK = re.compile(r"^((plan|dispatch|apply):[a-z_]+|ingress|page_copy|"
                       r"page_stats)$")
OP_NAME_STAT = "tf_op"


def program_name(op_name: str) -> str:
    """The program's name on one op, from its op-name metadata (the JAX
    name stack, ``;``-joined over a fusion's ops): the first named scope
    in it, else the first kernel name."""
    found = set(_TOKEN.findall(op_name))
    for name in SCOPES + KERNELS:
        if name in found:
            return name
    return ""


# ---- the few fields of the XSpace protobuf that carry the op names; the
# profiler's Python events expose an op's own stats but not those of its
# metadata, where the op-name ("tf_op") lives
def _fields(buf: bytes, lo: int, hi: int):
    """(field number, wire type, value) of one message; length-delimited
    values come as (start, end) offsets, so nested messages are not
    copied."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val, i = None, i + 8
        elif wire == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, wire, val


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _map_values(buf, span):
    """The value of one protobuf map entry (key 1, value 2)."""
    for f, _, v in _fields(buf, *span):
        if f == 2:
            return v
    return None


def op_names(data: bytes) -> dict[str, dict[str, str]]:
    """{device plane: {op text: op-name metadata}} from an XSpace: each
    XLA op's XEventMetadata (name: the HLO text) and its ``tf_op`` stat."""
    out = {}
    for f, _, plane in _fields(data, 0, len(data)):
        if f != 1:                                  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pf, _, v in _fields(data, *plane):
            if pf == 2:                             # XPlane.name
                name = data[v[0]:v[1]].decode()
                if not name.startswith("/device:TPU:"):
                    break
            elif pf == 4:                           # event_metadata map
                metas.append(_map_values(data, v))
            elif pf == 5:                           # stat_metadata map
                sm = _map_values(data, v)
                sid, sname = None, ""
                for sf, _, sv in _fields(data, *sm):
                    if sf == 1:
                        sid = sv
                    elif sf == 2:
                        sname = data[sv[0]:sv[1]].decode()
                stat_names[sid] = sname
        if not name.startswith("/device:TPU:"):
            continue
        want = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        texts = out[name] = {}
        for em in metas:
            text, op = None, None
            for ef, _, ev in _fields(data, *em):
                if ef == 2:                         # XEventMetadata.name
                    text = data[ev[0]:ev[1]].decode("utf-8", "replace")
                elif ef == 5:                       # XEventMetadata.stats
                    sid, sval = None, None
                    for xf, _, xv in _fields(data, *ev):
                        if xf == 1:
                            sid = xv
                        elif xf == 5:               # XStat.str_value
                            sval = data[xv[0]:xv[1]].decode("utf-8",
                                                           "replace")
                    if sid in want:
                        op = sval
            if text is not None and op is not None:
                texts[text] = op
    return out


def events_from_xplane(path: str) -> dict:
    """``{"devices": {plane: [[op text, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...],
    "scopes": {plane: [program name of each op]}}`` from one ``.xplane.pb``."""
    import jax
    with open(path, "rb") as f:
        names = op_names(f.read())
    pd = jax.profiler.ProfileData.from_file(path)
    devices, scopes, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, labels = [], []
            meta = names.get(plane.name, {})
            for line in plane.lines:
                if line.name != trace_reduce.OP_LINE:
                    continue
                for ev in line.events:
                    ops.append([trace_reduce.compact(ev.name),
                                float(ev.start_ns), float(ev.duration_ns)])
                    labels.append(program_name(meta.get(ev.name, "")))
            devices[plane.name] = ops
            scopes[plane.name] = labels
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                         for ev in line.events if ev.duration_ns > 0]
    return {"devices": devices, "scopes": scopes, "host": host}


def _overlap(a, b) -> float:
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_layers(ev: dict) -> dict | None:
    devices = {k: v for k, v in ev["devices"].items() if v}
    if not devices:
        return None
    spans: dict[str, list] = {}
    work = []
    for name, s, d in ev["host"]:
        if PROGRAM_SPAN.match(name):
            c = spans.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += d * 1e-9
        if HOST_WORK.match(name):
            work.append((s, s + d))
    work = trace_reduce._union(work)
    idle = 0.0
    scope_s: dict[str, float] = defaultdict(float)
    for plane, ops in devices.items():
        u = trace_reduce._union([(s, s + d) for _, s, d in ops])
        gaps = [(a[1], b[0]) for a, b in zip(u, u[1:]) if b[0] > a[1]]
        idle += _overlap(gaps, work) * 1e-9
        names = ev.get("scopes", {}).get(plane)
        if names is None:
            continue
        for name, own in zip(names, trace_reduce.self_times(ops)):
            scope_s[name] += own * 1e-9
    n = len(devices)
    # a program that writes no spans leaves nothing to overlap with
    return {"host_spans": spans, "idle_in_host_s": idle / n if work else None,
            "scope_s": {k: v / n for k, v in sorted(scope_s.items())}}
