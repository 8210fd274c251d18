"""setup_s: from the start of the benchmark process to the window's first
due request: the server's start, device check, weights, engine, compile or
cache load, shape warm-up, and the traffic's untimed warm-up."""


def read(run):
    return run["t0"] - run["t_start"]
