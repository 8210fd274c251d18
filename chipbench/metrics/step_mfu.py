"""step_mfu: model FLOPs of the real tokens the window processed, over the
window times the cell's chips times the configuration's peak (int8 for
PDQ-int8, bf16 for bf16).

Model FLOPs (the family's ``prefill_flops`` and ``decode_flops``,
``models/<family>.py``): per prompt token 2 x the layers' matmul parameters
plus causal attention; per output token the same at its true context plus
the output head.  The client records give each request's prompt length and
each token's arrival; a prompt counts when its first token arrived in the
window, an output token when it arrived there."""
from chipbench import bench


def read(run):
    cfg = run["config"]
    mf = bench.load_model(bench.family(cfg), run["root"])
    t0 = run["t0"]
    t1 = t0 + run["seconds"]
    flops = 0.0
    for r in run["records"]:
        if not r["times"]:
            continue
        p = r["prompt_len"]
        if t0 <= r["times"][0] <= t1:
            flops += mf.prefill_flops(cfg, p)
        for j, t in enumerate(r["times"][1:], start=1):
            if t0 <= t <= t1:
                flops += mf.decode_flops(cfg, p + j)
    peak = run["chips"] * run["peaks"][cfg["peak"]]
    return 100.0 * flops / (run["seconds"] * peak)
