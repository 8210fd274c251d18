"""decode_row_occupancy: share of the decode rows the window's dispatches
ran that produced a token: d(decode_tokens) / (d(decode_steps) x slots x N)
from ``/v1/stats``, where ``decode_steps`` counts dispatches and N is the
configuration's decode steps per dispatch."""
from chipbench.readers import stat_delta


def read(run):
    dispatches = stat_delta(run, "decode_steps")
    if dispatches <= 0:
        return None
    s = run["config"]["serve"]
    rows = dispatches * int(s["slots"]) * int(s["decode_steps"])
    return 100.0 * stat_delta(run, "decode_tokens") / rows
