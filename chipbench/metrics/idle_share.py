"""idle_share: share of the traced window in which no operation ran on the
device: 1 - busy / window, busy being the union of the op intervals on the
chip's op line (trace_reduce)."""


def read(run):
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
