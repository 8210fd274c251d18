"""decode_host_ms: the serving loop's own host time per decode dispatch:
delta of ``serve_loop_seconds_total`` (``/metrics``) summed over every
phase and kind except ``fetch`` (waiting for the device) and ``idle``
(waiting for work), over delta of ``decode_steps`` (dispatches,
``/v1/stats``).  The phases sum to the loop thread's wall time, so this
is the wall time of the window's rounds less their waits."""
import re

from chipbench.readers import metric_delta, stat_delta

FAMILY = "serve_loop_seconds_total{"
WAITS = ("fetch", "idle")
_PHASE = re.compile(r'phase="([^"]*)"')


def read(run):
    keys = [k for k in run["snap1"]["metrics"] if k.startswith(FAMILY)
            and _PHASE.search(k).group(1) not in WAITS]
    dispatches = stat_delta(run, "decode_steps")
    if not keys or dispatches <= 0:
        return None
    return 1e3 * sum(metric_delta(run, k) for k in keys) / dispatches
