"""idle_in_host_share: share of the traced window in which the device idled
while the serving loop did its own host work (the ``plan:*``,
``dispatch:*`` and ``apply:*`` spans, ``ingress``, ``page_copy``,
``page_stats``): 100 x ``idle_in_host_s`` (trace_layers) / ``window_s``.
The part of ``idle_share`` that the host's own work holds the chip back.
A program that writes no spans reads nothing."""


def read(run):
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0 or tr.get("idle_in_host_s") is None:
        return None
    return 100.0 * tr["idle_in_host_s"] / tr["window_s"]
