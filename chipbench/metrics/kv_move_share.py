"""kv_move_share: share of the device's busy time in the traced window spent
moving the paged KV pool: 100 x the self time of the ops under the
``paged_gather`` and ``paged_writeback`` named scopes (``scope_s``,
trace_layers) over ``busy_s``.  A program that names neither scope reads
nothing."""
SCOPES = ("paged_gather", "paged_writeback")


def read(run):
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    scopes = tr.get("scope_s") or {}
    if not any(s in scopes for s in SCOPES):
        return None
    return 100.0 * sum(scopes.get(s, 0.0) for s in SCOPES) / tr["busy_s"]
