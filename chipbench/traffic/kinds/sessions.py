"""Sessions: ``clients_per_slot x slots`` clients in a closed loop, each
holding one conversation after another.  A conversation opens on one of a
few shared system prompts; each turn's prompt is the previous turn's prompt,
plus the tokens served for it, plus a new user turn, so every turn resends
the whole history.  A conversation ends when its next prompt would pass
``max_prompt`` tokens, and the client opens a new one.

Parameters (``traffic/<mix>.json``):
  clients_per_slot       clients per engine slot
  system_prompts         how many shared system prompts, each
  system_len             tokens long, drawn from the seed
  system_zipf            exponent of the Zipf law that picks a session's
                         system prompt (the first is the most used)
  user_len, answer_len   length distributions of a user turn and of the
                         tokens asked for an answer (see loadgen._quantile)
  max_prompt             the longest prompt sent
  stagger_s              clients open their first sessions this far apart
  warmup_s               untimed traffic before the window opens
  pool                   sizes drawn per stratified pool
  check_sample           finished requests compared with the reference

Every seed sends the same sizes in the same places: the user turns,
answers and system-prompt picks come from stratified pools in one fixed
order, and client ``c`` takes the ``k``-th entry of its own stride of each.
A window holds only about two turns a client, so sizes drawn anew for each
seed would change the window's work (its answer lengths and prefix hits)
by several per cent from seed to seed.  The seed draws the tokens: the
system prompts, and each client's user turns from a generator of its own,
so a client's conversations depend on the seed and on the served tokens
alone, not on how the clients interleave.
"""
from __future__ import annotations

import asyncio

import numpy as np

from chipbench import loadgen

SIZES_SEED = 0            # the order of the size pools, the same for every seed


def prompt_range(traffic: dict) -> tuple[int, int]:
    return (int(traffic["system_len"]) + loadgen.dist_range(
        traffic["user_len"])[0], int(traffic["max_prompt"]))


def zipf_picks(n_prompts: int, exponent: float, n: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` system-prompt indices at the quantiles (i + 0.5) / n of a Zipf
    law over ``n_prompts`` (weight 1 / rank ** exponent), in an order
    drawn from ``rng``."""
    w = 1.0 / np.arange(1, n_prompts + 1) ** float(exponent)
    cdf = np.cumsum(w / w.sum())
    u = (np.arange(n) + 0.5) / n
    picks = np.minimum(np.searchsorted(cdf, u, side="right"), n_prompts - 1)
    return rng.permutation(picks)


class Sizes:
    """A client's stride through the seed's stratified pools."""

    def __init__(self, pools: dict, client: int, n_clients: int):
        self.pools = pools
        self.client = client
        self.n_clients = n_clients
        self.taken = dict.fromkeys(pools, 0)

    def next(self, name: str) -> int:
        pool = self.pools[name]
        k = self.taken[name]
        self.taken[name] = k + 1
        return int(pool[(self.client + k * self.n_clients) % len(pool)])


def conversation_turns(t: dict, sizes: Sizes, tok_rng: np.random.Generator,
                       systems: list, vocab: int):
    """A generator of one client's requests: it yields ``(prompt,
    max_tokens, session, turn)`` and is sent the tokens served for it (an
    empty list ends the session: the turn failed)."""
    max_prompt = int(t["max_prompt"])
    session = 0
    while True:
        prompt = list(systems[sizes.next("system")])
        turn = 0
        while True:
            user = sizes.next("user")
            if len(prompt) + user > max_prompt:
                break
            prompt = prompt + loadgen.prompts(tok_rng, [user], vocab)[0]
            served = yield prompt, sizes.next("answer"), session, turn
            if not served:
                break
            prompt = prompt + list(served)
            turn += 1
        session += 1


async def drive(ctx) -> list:
    t = ctx.traffic
    n_clients = int(t["clients_per_slot"] * ctx.config["serve"]["slots"])
    rng = np.random.default_rng(SIZES_SEED)
    pool = int(t["pool"])
    pools = {"user": loadgen.stratified(t["user_len"], pool, rng),
             "answer": loadgen.stratified(t["answer_len"], pool, rng),
             "system": zipf_picks(int(t["system_prompts"]),
                                  float(t["system_zipf"]), pool, rng)}
    systems = loadgen.prompts(np.random.default_rng([ctx.seed, 2]),
                              [int(t["system_len"])] * int(t["system_prompts"]),
                              ctx.vocab)
    reqs: list[loadgen.Req] = []
    stop = asyncio.Event()
    lateness: list[float] = []

    async def client(c: int) -> None:
        await asyncio.sleep(c * float(t["stagger_s"]))
        turns = conversation_turns(
            t, Sizes(pools, c, n_clients),
            np.random.default_rng([ctx.seed, 4, c]), systems, ctx.vocab)
        served = None
        while not stop.is_set():
            prompt, max_tokens, session, turn = turns.send(served)
            req = loadgen.Req(idx=len(reqs), prompt=prompt,
                              max_tokens=max_tokens,
                              session=(c, session, turn))
            reqs.append(req)
            req.due = loadgen.now()
            try:
                await loadgen.stream_completion(ctx.port, req)
            finally:
                if req.sent is not None:
                    lateness.append(req.sent - req.due)
            served = req.tokens if req.completed else []

    tasks = [asyncio.create_task(client(c)) for c in range(n_clients)]
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
