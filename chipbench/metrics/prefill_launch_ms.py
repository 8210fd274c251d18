"""prefill_launch_ms: mean host-timed prefill launch in the window, from
``serve_launch_seconds{kind="prefill"}`` on ``/metrics`` (delta of sum over
delta of count).  The launch ends in the sampled tokens' host copy, so it
waits for the device."""
from chipbench.readers import metric_delta

KIND = "prefill"


def read(run):
    lbl = '{kind="%s"}' % KIND
    n = metric_delta(run, "serve_launch_seconds_count" + lbl)
    if n <= 0:
        return None
    return 1e3 * metric_delta(run, "serve_launch_seconds_sum" + lbl) / n
