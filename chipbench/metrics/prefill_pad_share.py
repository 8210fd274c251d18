"""prefill_pad_share: share of the prefill tokens executed in the window
that were padding: 1 - d(prefill_tokens) / d(prefill_padded_tokens) from
``/v1/stats``.  Every prefill launch runs all slots at its bucket length."""
from chipbench.readers import stat_delta


def read(run):
    padded = stat_delta(run, "prefill_padded_tokens")
    if padded <= 0:
        return None
    return 100.0 * (1.0 - stat_delta(run, "prefill_tokens") / padded)
