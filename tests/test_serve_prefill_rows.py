"""Prefill launches at a ladder of row counts.

A single-device engine of 32 slots or more launches each bucketed prefill
at the smallest row count of its ladder that holds the round's admits,
not at every slot row.  The rows a launch leaves out are dummies, so the
served tokens must not change; every row count of a bucket compiles with
the bucket's first launch, never later.
"""
import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import build_model
from repro.serve import Request, ServeEngine
from repro.serve.engine import prefill_row_ladder

SLOTS = 32
LENS = [3, 5, 8, 9, 12, 16, 17, 23, 30, 4, 11, 27] * 3 + [6, 14, 21, 29]

_MODELS = {}


def _model(arch):
    if arch not in _MODELS:
        cfg = reduced_config(arch)
        _MODELS[arch] = (cfg, build_model(cfg).init(jax.random.PRNGKey(0)))
    return _MODELS[arch]


def _serve(arch, *, every_row: bool, paged: bool):
    cfg, params = _model(arch)
    eng = ServeEngine(cfg, params, slots=SLOTS, max_len=64,
                      buckets=(8, 16, 32), paged=paged, page_size=16)
    if every_row:
        eng.prefill_rows = (SLOTS,)
    rng = np.random.default_rng(0)
    # more requests than slots, and budgets that end at different steps:
    # the later rounds admit a few requests at a time
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, L).astype(
                np.int32), max_new=2 + i % 5) for i, L in enumerate(LENS)]
    eng.run(reqs)
    assert all(r.done and r.finish_reason == "complete" for r in reqs)
    return eng, {r.uid: tuple(r.generated) for r in reqs}


@pytest.mark.parametrize("slots,ladder", [(8, (8,)), (16, (16,)),
                                          (32, (2, 8, 32)),
                                          (64, (1, 4, 16, 64)),
                                          (128, (2, 8, 32, 128))])
def test_the_row_ladder(slots, ladder):
    assert prefill_row_ladder(slots) == ladder


@pytest.mark.parametrize("arch,paged", [("stablelm-1.6b", False),
                                        ("stablelm-1.6b", True),
                                        ("deepseek-v2-236b", True)])
def test_fewer_rows_serve_the_same_tokens(arch, paged):
    """GQA on the slot-row and paged pools, and MLA + routed experts."""
    eng, got = _serve(arch, every_row=False, paged=paged)
    ref, want = _serve(arch, every_row=True, paged=paged)
    assert got == want
    assert eng.prefill_rows == (2, 8, SLOTS)
    assert eng.stats["prefill_tokens"] == ref.stats["prefill_tokens"]
    # some launches took fewer rows than slots, so fewer padded tokens
    assert (eng.stats["prefill_padded_tokens"]
            < ref.stats["prefill_padded_tokens"])


def test_every_row_count_compiles_with_the_first_launch_of_its_bucket():
    cfg, params = _model("stablelm-1.6b")
    eng = ServeEngine(cfg, params, slots=SLOTS, max_len=64, buckets=(8, 16),
                      paged=True, page_size=16)
    rng = np.random.default_rng(1)

    def serve(lens):
        reqs = [Request(uid=100 * len(lens) + i, prompt=rng.integers(
                    0, cfg.vocab, L).astype(np.int32), max_new=2)
                for i, L in enumerate(lens)]
        eng.run(reqs)
        assert all(r.done for r in reqs)

    serve([5])                      # one request: the 8-token bucket
    first = eng.stats["prefill_compiles"]
    assert first == len(eng.prefill_rows)
    serve([3, 4, 6])                # 8 rows
    serve([7] * 20)                 # 32 rows
    assert eng.stats["prefill_compiles"] == first
    serve([12])                     # the 16-token bucket compiles its own
    assert eng.stats["prefill_compiles"] == 2 * first
