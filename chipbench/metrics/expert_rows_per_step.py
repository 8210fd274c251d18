"""expert_rows_per_step: routed rows each held expert took per decode step
and expert layer in the window: d(expert_rows) / (d(decode_steps) x N x
experts held x expert layers), from ``/v1/stats``, where ``expert_rows``
counts every (row, expert) assignment of a decode step, in every expert
layer, that landed on an expert this chip holds (every row the dispatch
runs, live or not; ``decode_row_occupancy`` says how many were live),
``decode_steps`` counts dispatches and N is the configuration's decode
steps per dispatch.  Uniform routing reads slots x top-k / experts.  A
program without the counter, or a configuration without routed experts,
reads nothing."""
from chipbench.readers import stat_delta


def read(run):
    cfg = run["config"]
    held = cfg.get("n_routed_experts")
    if not held or "expert_rows" not in run["snap0"]["stats"]:
        return None
    dispatches = stat_delta(run, "decode_steps")
    if dispatches <= 0:
        return None
    layers = (int(cfg["num_hidden_layers"])
              - int(cfg.get("first_k_dense_replace", 0)))
    n = int(cfg["serve"]["decode_steps"])
    return stat_delta(run, "expert_rows") / (dispatches * n * int(held)
                                             * layers)
