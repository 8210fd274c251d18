"""Closed loop: ``clients_per_slot x slots`` clients, each sending its next
request as soon as its last one ends.

Parameters (``traffic/<mix>.json``):
  clients_per_slot       clients per engine slot
  prompt_len, output_len length distributions (see loadgen._quantile)
  warmup_output_len      outputs of the first wave, spread so completions
                         do not come in lockstep
  stagger_s              clients send their first requests this far apart,
                         in client order, so the engine admits them in that
                         order
  warmup_s               untimed traffic before the window opens
  pool                   sizes drawn per stratified pool
  check_sample           finished requests compared with the reference
"""
from __future__ import annotations

import asyncio

import numpy as np

from chipbench import loadgen


def prompt_range(traffic: dict) -> tuple[int, int]:
    return loadgen.dist_range(traffic["prompt_len"])


async def drive(ctx) -> list:
    t = ctx.traffic
    n_clients = int(t["clients_per_slot"] * ctx.config["serve"]["slots"])
    rng = np.random.default_rng([ctx.seed, 1])
    tok_rng = np.random.default_rng([ctx.seed, 2])
    pool = int(t["pool"])
    # the first wave is the same for every seed: its stratified outputs
    # alternate between the clients admitted at once (the first ``slots``)
    # and those that queue behind them, so both halves span the range
    q = np.sort(loadgen.stratified(t["warmup_output_len"], n_clients, rng))
    first_out = np.concatenate([q[0::2], q[1::2]])
    plens = loadgen.stratified(t["prompt_len"], pool, rng)
    olens = loadgen.stratified(t["output_len"], pool, rng)
    reqs: list[loadgen.Req] = []
    stop = asyncio.Event()
    lateness: list[float] = []

    def next_req() -> loadgen.Req:
        i = len(reqs)
        k = i - n_clients
        if k < 0:
            p, o = int(plens[i % pool]), int(first_out[i])
        else:
            p, o = int(plens[(n_clients + k) % pool]), int(olens[k % pool])
        req = loadgen.Req(idx=i, prompt=loadgen.prompts(tok_rng, [p],
                                                        ctx.vocab)[0],
                          max_tokens=o)
        reqs.append(req)
        return req

    async def client(i: int) -> None:
        await asyncio.sleep(i * float(t["stagger_s"]))
        while not stop.is_set():
            req = next_req()
            req.due = loadgen.now()
            try:
                await loadgen.stream_completion(ctx.port, req)
            finally:
                if req.sent is not None:
                    lateness.append(req.sent - req.due)

    tasks = [asyncio.create_task(client(i)) for i in range(n_clients)]
    await asyncio.sleep(float(t["warmup_s"]))
    await ctx.open_window()
    await asyncio.sleep(ctx.seconds)
    await ctx.close_window()
    stop.set()
    for task in tasks:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    # warm-up is what was due before the window opened
    for req in reqs:
        req.warmup = req.due < ctx.t0
    ctx.lateness = lateness
    return reqs
