"""Load-generator pieces shared by the traffic kinds (stdlib + numpy, no JAX).

A request goes to ``POST /v1/completions`` with ``stream: true`` and is timed
on this side: the clock of every streamed token is taken as its SSE event is
read.  Lengths are drawn so that every seed gets the same multiset of sizes
(stratified quantiles of the stated distribution) in its own order, which
keeps the work of a run independent of the seed.
"""
from __future__ import annotations

import asyncio
import json
import math
import time
from dataclasses import dataclass, field

import numpy as np

now = time.perf_counter


@dataclass
class Req:
    idx: int
    prompt: list
    max_tokens: int
    due: float = 0.0              # when it was due to be sent
    sent: float | None = None
    status: int | None = None
    times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    finish: str | None = None
    error: str | None = None
    warmup: bool = False
    session: tuple | None = None  # (client, session, turn) of a conversation

    @property
    def completed(self) -> bool:
        return self.status == 200 and self.finish == "complete"

    def as_record(self) -> dict:
        return {"idx": self.idx, "prompt_len": len(self.prompt),
                "max_tokens": self.max_tokens, "due": self.due,
                "sent": self.sent, "status": self.status, "times": self.times,
                "n_tokens": len(self.tokens), "finish": self.finish,
                "error": self.error, "warmup": self.warmup,
                "session": self.session}


# ------------------------------------------------------------------ sizes
def _quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "uniform":                      # integers min..max inclusive
        lo, hi = dist["min"], dist["max"]
        return lo + np.floor(u * (hi - lo + 1))
    if kind == "lognormal":                    # clipped to [min, max]
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(np.round(v), dist["min"], dist["max"])
    if kind == "const":
        return np.full(u.shape, dist["value"], float)
    raise ValueError(f"unknown length distribution {kind!r}")


def stratified(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` sizes at the quantiles (i + 0.5) / n of ``dist``, in an order
    drawn from ``rng``: the same multiset for every seed."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(_quantile(dist, u).astype(np.int64))


def dist_range(dist: dict) -> tuple[int, int]:
    if dist["dist"] == "const":
        return int(dist["value"]), int(dist["value"])
    return int(dist["min"]), int(dist["max"])


def buckets_used(serve: dict, lo: int, hi: int) -> list[int]:
    """The prefill buckets prompts of ``lo..hi`` tokens land in, the
    capacity bucket (max_len - 1) that the engine appends included."""
    bs = sorted({min(b, serve["max_len"] - 1) for b in serve["buckets"]}
                | {serve["max_len"] - 1})
    first, last = (next(b for b in bs if b >= n) for n in (lo, hi))
    return [b for b in bs if first <= b <= last]


def prompts(rng: np.random.Generator, lens, vocab: int) -> list[list[int]]:
    return [rng.integers(1, vocab, int(n)).tolist() for n in lens]


# ------------------------------------------------------------------ HTTP
async def stream_completion(port: int, req: Req) -> None:
    """Send ``req`` and record each streamed token's arrival time.  A
    cancelled task closes the connection, which cancels the request in the
    server."""
    body = json.dumps({"prompt": req.prompt, "max_tokens": req.max_tokens,
                       "stream": True}).encode()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(body) + body)
        req.sent = now()
        await writer.drain()
        status = (await reader.readline()).split()
        req.status = int(status[1]) if len(status) > 1 else 0
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if req.status != 200:
            req.error = (await reader.read())[:300].decode("utf-8", "replace")
            return
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            data = line[6:].strip()
            if data == b"[DONE]":
                break
            ev = json.loads(data)
            if "token" in ev:
                req.tokens.append(int(ev["token"]))
                req.times.append(now())
            else:
                req.finish = ev.get("finish_reason")
                req.error = ev.get("error")
    finally:
        writer.close()


async def http_get(port: int, path: str) -> bytes:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: bench\r\n\r\n".encode())
        await writer.drain()
        data = await reader.read()
    finally:
        writer.close()
    head, _, body = data.partition(b"\r\n\r\n")
    if not head.startswith(b"HTTP/1.1 200"):
        raise RuntimeError(f"GET {path}: {head[:100]!r}")
    return body


async def snapshot(port: int) -> dict:
    """The server's counters at this instant: ``/v1/stats`` and ``/metrics``."""
    stats, metrics = await asyncio.gather(http_get(port, "/v1/stats"),
                                          http_get(port, "/metrics"))
    return {"t": now(), "stats": json.loads(stats),
            "metrics": parse_prometheus(metrics.decode())}


def parse_prometheus(text: str) -> dict:
    """``{'name{label="v"}': value}`` for every sample line."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out
