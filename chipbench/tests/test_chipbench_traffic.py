"""Traffic kinds: deterministic by seed, and drawn from the stated
distributions (no JAX here)."""
from __future__ import annotations

import asyncio
import math
from statistics import NormalDist

import numpy as np
import pytest

from chipbench import bench, loadgen
from chipbench.tests.fake_server import FakeServer

UNIFORM = {"dist": "uniform", "min": 16, "max": 128}
LOGNORMAL = {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16,
             "max": 512}


@pytest.mark.parametrize("dist", [UNIFORM, LOGNORMAL], ids=["uniform", "lognormal"])
def test_stratified_sizes_are_one_multiset_in_seeded_orders(dist):
    a = loadgen.stratified(dist, 1000, np.random.default_rng(1))
    b = loadgen.stratified(dist, 1000, np.random.default_rng(1))
    c = loadgen.stratified(dist, 1000, np.random.default_rng(2))
    assert (a == b).all()
    assert not (a == c).all()
    assert sorted(a) == sorted(c)
    assert a.min() >= dist["min"] and a.max() <= dist["max"]


def test_uniform_sizes_match_the_distribution():
    a = loadgen.stratified(UNIFORM, 1130, np.random.default_rng(0))
    # 1130 = 10 x 113 quantiles: each integer 16..128 exactly 10 times
    counts = np.bincount(a)[16:129]
    assert (counts == 10).all()


def test_lognormal_sizes_match_the_distribution():
    a = loadgen.stratified(LOGNORMAL, 4000, np.random.default_rng(0))
    assert abs(np.median(a) - 192) <= 1
    clipped = 1 - NormalDist().cdf(math.log(512 / 192) / 0.8)
    assert abs((a == 512).mean() - clipped) < 2 / 4000 + 1e-3
    low = NormalDist().cdf(math.log(16.5 / 192) / 0.8)
    assert abs((a == 16).mean() - low) < 2 / 4000 + 1e-3


@pytest.mark.parametrize("lo,hi,want", [(16, 128, [64, 128]),
                                        (16, 512, [64, 128, 256, 512]),
                                        (600, 700, [1023]), (64, 64, [64])])
def test_buckets_used(lo, hi, want):
    serve = {"max_len": 1024, "buckets": [64, 128, 256, 512]}
    assert loadgen.buckets_used(serve, lo, hi) == want


class _Child:
    async def call(self, cmd, **kw):
        return {}


class _Cell:
    def __init__(self, traffic):
        self.traffic = traffic
        self.config = {"vocab_size": 1000, "serve": {"slots": 2,
                                                     "decode_steps": 4}}


def _drive(kind_name, traffic, seed, seconds):
    from chipbench.run import Ctx

    async def go():
        fake = await FakeServer(gap_s=0.002).start()
        try:
            ctx = Ctx(_Cell(traffic), _Child(), fake.port, seed, seconds, 0)
            reqs = await bench.load_kind(kind_name).drive(ctx)
            return ctx, reqs
        finally:
            await fake.stop()
    return asyncio.run(go())


@pytest.mark.parametrize("seed", [5, 2**31 + 3])
def test_closed_loop_sends_the_seeds_requests_in_order(seed):
    traffic = {"clients_per_slot": 2, "prompt_len": UNIFORM,
               "output_len": {"dist": "uniform", "min": 5, "max": 10},
               "warmup_output_len": {"dist": "uniform", "min": 1, "max": 10},
               "warmup_s": 0.2, "stagger_s": 0.01, "pool": 64,
               "check_sample": 2}
    ctx1, r1 = _drive("closed_loop", traffic, seed, 0.5)
    ctx2, r2 = _drive("closed_loop", traffic, seed, 0.5)
    n = min(len(r1), len(r2))
    assert n > 8
    assert ([(r.prompt, r.max_tokens) for r in r1[:n]]
            == [(r.prompt, r.max_tokens) for r in r2[:n]])
    # four clients: never more than four requests in flight
    done = [r for r in r1 if r.completed]
    assert done and all(len(r.tokens) == r.max_tokens for r in done)
    assert ctx1.t0 is not None and ctx1.snap1["stats"]["pending"] == 0
    assert all(r.warmup == (r.due < ctx1.t0) for r in r1)
    assert any(r.warmup for r in r1) and not all(r.warmup for r in r1)


SESSIONS = {"kind": "sessions", "clients_per_slot": 2, "system_prompts": 3,
            "system_len": 12, "system_zipf": 1.0,
            "user_len": {"dist": "uniform", "min": 2, "max": 6},
            "answer_len": {"dist": "uniform", "min": 3, "max": 8},
            "max_prompt": 48, "warmup_s": 0.2, "stagger_s": 0.01,
            "pool": 64, "check_sample": 2}


def _by_client(reqs):
    out = {}
    for r in reqs:
        out.setdefault(r.session[0], []).append(
            (r.prompt, r.max_tokens, r.session))
    return out


@pytest.fixture(scope="module")
def sessions_runs():
    return {seed: _drive("sessions", SESSIONS, seed, 0.6)
            for seed in (7, 2**31 + 11)}


def test_sessions_are_seeded(sessions_runs):
    seed = 2**31 + 11
    _, again = _drive("sessions", SESSIONS, seed, 0.6)
    a, b = _by_client(sessions_runs[seed][1]), _by_client(again)
    assert sorted(a) == sorted(b) == [0, 1, 2, 3]
    for c in a:
        n = min(len(a[c]), len(b[c]))
        assert n > 3
        # a client's conversations do not depend on how clients interleave
        assert a[c][:n] == b[c][:n]
    # another seed sends other tokens in the same sizes: the same work
    other = _by_client(sessions_runs[7][1])
    for c in a:
        n = min(len(a[c]), len(other[c]))
        assert other[c][0][0] != a[c][0][0]
        assert ([(len(p), m, sess) for p, m, sess in other[c][:n]]
                == [(len(p), m, sess) for p, m, sess in a[c][:n]])


def test_every_session_prompt_stays_within_the_largest_bucket(sessions_runs):
    lo, hi = bench.load_kind("sessions").prompt_range(SESSIONS)
    assert (lo, hi) == (14, 48)
    for _, reqs in sessions_runs.values():
        assert reqs and all(lo <= len(r.prompt) <= hi for r in reqs)


def test_a_turn_extends_the_previous_prompt_and_answer(sessions_runs):
    for _, reqs in sessions_runs.values():
        by = {r.session: r for r in reqs}
        later = [r for r in reqs if r.session[2] > 0]
        assert len(later) > 10
        for r in later:
            c, s, t = r.session
            prev = by[(c, s, t - 1)]
            history = prev.prompt + prev.tokens
            assert prev.completed and r.prompt[:len(history)] == history
            assert 2 <= len(r.prompt) - len(history) <= 6
        # a session ends only where its next turn might not fit (a user
        # turn is up to 6 tokens)
        last = {}
        for r in reqs:
            last[r.session[:2]] = r
        for (c, s), r in last.items():
            if (c, s + 1, 0) in by and r.completed:
                assert len(r.prompt) + len(r.tokens) + 6 > 48


def test_system_prompts_repeat_across_sessions(sessions_runs):
    for seed, (_, reqs) in sessions_runs.items():
        first = [tuple(r.prompt[:12]) for r in reqs if r.session[2] == 0]
        assert len(first) > 3 * len(set(first))
        assert len(set(first)) <= 3
        # Zipf: the first system prompt opens the most sessions
        counts = sorted(first.count(p) for p in set(first))
        assert counts[-1] > counts[0]


def test_zipf_picks_are_one_multiset_in_seeded_orders():
    kind = bench.load_kind("sessions")
    a = kind.zipf_picks(8, 1.0, 1000, np.random.default_rng(1))
    b = kind.zipf_picks(8, 1.0, 1000, np.random.default_rng(2))
    assert sorted(a) == sorted(b) and not (a == b).all()
    w = 1 / np.arange(1, 9)
    want = 1000 * w / w.sum()
    assert np.abs(np.bincount(a, minlength=8) - want).max() <= 1
