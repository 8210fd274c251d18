"""decode_fetch_ms: the serving loop's wait per decode dispatch for its
results to reach the host: delta of
``serve_loop_seconds_total{phase="fetch",kind="decode"}`` (``/metrics``)
over delta of ``decode_steps`` (dispatches, ``/v1/stats``).  The fetch is
the second half of the launch that ``decode_dispatch_ms`` times whole."""
from chipbench.readers import metric_delta, stat_delta

KEY = 'serve_loop_seconds_total{kind="decode",phase="fetch"}'


def read(run):
    if KEY not in run["snap1"]["metrics"]:
        return None
    dispatches = stat_delta(run, "decode_steps")
    if dispatches <= 0:
        return None
    return 1e3 * metric_delta(run, KEY) / dispatches
