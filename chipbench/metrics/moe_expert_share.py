"""moe_expert_share: share of the device's busy time in the traced window
spent in the held experts' W8A8 matmuls (their gate/up and down calls, told
by the configuration's expert widths, roofline/expert_matmul.py): 100 x
their device time over ``busy_s``.  The prologues and the elementwise work
of the ``moe_experts`` scope are not counted: the trace reduction names
device time only by the scopes and kernels it lists.  A program or
configuration without such calls reads nothing."""
from chipbench import bench


def read(run):
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    rf = bench.load_roofline("expert_matmul", run["root"])
    took = sum(seconds for sig, _, seconds in tr["custom_calls"]
               if rf.match(sig, run["config"]) is not None)
    return 100.0 * took / tr["busy_s"] if took > 0 else None
