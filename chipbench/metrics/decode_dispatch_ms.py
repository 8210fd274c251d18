"""decode_dispatch_ms: mean host-timed decode dispatch (one block of N steps) in the window, from
``serve_launch_seconds{kind="decode"}`` on ``/metrics`` (delta of sum over
delta of count).  The launch ends in the sampled tokens' host copy, so it
waits for the device."""
from chipbench.readers import metric_delta

KIND = "decode"


def read(run):
    lbl = '{kind="%s"}' % KIND
    n = metric_delta(run, "serve_launch_seconds_count" + lbl)
    if n <= 0:
        return None
    return 1e3 * metric_delta(run, "serve_launch_seconds_sum" + lbl) / n
