"""tpot_p50_ms: median over requests of the time per output token inside
the window: (last - first arrival) / (tokens - 1) of the tokens a request
received in the window.  A request counts when more than two decode blocks
of its tokens fall in the window, so that a block's tokens, which arrive
together, do not read as no time at all.

The median, because a decode cell's window holds some twenty requests:
the highest percentile with ten of them beyond it."""
from chipbench.readers import percentile, tokens_in_window


def read(run):
    t0 = run["t0"]
    t1 = t0 + run["seconds"]
    need = 2 * int(run["config"]["serve"]["decode_steps"]) + 1
    vals = []
    for r in run["records"]:
        ts = tokens_in_window(r, t0, t1)
        if len(ts) >= need:
            vals.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return 1e3 * percentile(vals, 50) if vals else None
