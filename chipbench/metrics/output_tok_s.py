"""output_tok_s: output tokens the clients received inside the window,
divided by the window's length."""
from chipbench.readers import tokens_in_window


def read(run):
    t0 = run["t0"]
    t1 = t0 + run["seconds"]
    n = sum(len(tokens_in_window(r, t0, t1)) for r in run["records"])
    return n / run["seconds"]
