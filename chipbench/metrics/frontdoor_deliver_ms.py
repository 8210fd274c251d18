"""frontdoor_deliver_ms: mean time from the scheduler's push of the oldest
token of an SSE write to that write drained to the socket, over the
window's writes: delta of sum over delta of count of
``serve_frontdoor_deliver_seconds`` on ``/metrics`` (one observation per
write that carried tokens)."""
from chipbench.readers import metric_delta

NAME = "serve_frontdoor_deliver_seconds"


def read(run):
    n = metric_delta(run, NAME + "_count")
    if n <= 0:
        return None
    return 1e3 * metric_delta(run, NAME + "_sum") / n
