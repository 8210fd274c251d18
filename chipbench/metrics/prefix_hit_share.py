"""prefix_hit_share: share of the prompt tokens admitted in the window that
the paged pool took from pages another request had landed:
100 x d(prefix_shared_pages) x page_size / d(prefill_tokens), from
``/v1/stats``.  ``prefix_shared_pages`` counts each admitted prompt's
shared full pages once, when its pages are claimed."""
from chipbench.readers import stat_delta

KEY = "prefix_shared_pages"


def read(run):
    if KEY not in run["snap1"]["stats"]:
        return None
    prompt = stat_delta(run, "prefill_tokens")
    if prompt <= 0:
        return None
    page = int(run["config"]["serve"]["page_size"])
    return 100.0 * stat_delta(run, KEY) * page / prompt
