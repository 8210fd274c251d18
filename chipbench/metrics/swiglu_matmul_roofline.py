"""swiglu_matmul_roofline: the fused gate/up W8A8 matmul's share of its
roofline in the traced window: the least time its calls could take,
sum of max(operations / int8 peak, least bytes / HBM bandwidth), over the
device time they took.  Each call's shapes come from its signature in the
trace (roofline/swiglu_matmul.py)."""
from chipbench import bench


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    rf = bench.load_roofline("swiglu_matmul")
    pk = run["peaks"]
    least = took = 0.0
    for sig, n, seconds in tr["custom_calls"]:
        s = rf.match(sig)
        if s is None:
            continue
        one = max(rf.flops(s["M"], s["K"], s["N"]) / pk["int8_ops"],
                  rf.bytes(s["M"], s["K"], s["N"], s["out_bytes"])
                  / pk["hbm_bytes_per_s"])
        least += n * one
        took += seconds
    return 100.0 * least / took if took > 0 else None
