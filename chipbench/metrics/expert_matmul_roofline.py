"""expert_matmul_roofline: the held experts' W8A8 matmuls' share of their
roofline in the traced window: the least time their calls could take,
sum of max(operations / int8 peak, least bytes / HBM bandwidth), over the
device time they took.  Each call's shapes come from its signature in the
trace, told from other W8A8 calls by the configuration's expert widths
(roofline/expert_matmul.py).  A program or configuration without such
calls reads nothing."""
from chipbench import bench


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    rf = bench.load_roofline("expert_matmul", run["root"])
    pk = run["peaks"]
    least = took = 0.0
    for sig, n, seconds in tr["custom_calls"]:
        s = rf.match(sig, run["config"])
        if s is None:
            continue
        one = max(rf.flops(s["M"], s["K"], s["N"]) / pk["int8_ops"],
                  rf.bytes(s["M"], s["K"], s["N"], s["out_bytes"], s["E"])
                  / pk["hbm_bytes_per_s"])
        least += n * one
        took += seconds
    return 100.0 * least / took if took > 0 else None
