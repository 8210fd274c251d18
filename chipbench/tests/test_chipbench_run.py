"""Whole runs of the harness on the CPU at a tiny size: the server child
with the device check replaced (tests/cpu_server.py), the timed path intact
or broken underneath, and ``correct`` read from the result line."""
from __future__ import annotations

import argparse
import json
import os
import shutil

import pytest

from chipbench import run
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("chipbench"))


def _run(root, capsys, workload="tiny.decode", fault="none", trace=0,
         server_argv="tiny", control=0):
    args = argparse.Namespace(
        workload=workload, seed=2**31 + 17, seconds=2.0, trace=trace,
        control=control, root=str(root),
        server_argv=tiny.server_argv(fault) if server_argv == "tiny" else None)
    rc = run.run(args)
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), out.err


@pytest.mark.parametrize("fault,correct", [("none", True), ("token", False),
                                           ("state", False)])
def test_correct_is_decided_by_the_reference(root, capsys, fault, correct):
    rc, res, err = _run(root, capsys, fault=fault)
    assert rc == 0, err[-3000:]
    assert res["correct"] is correct
    assert list(res)[-1] == "checks" and res["checks"]["gap_max"]["limit"] == 1e-3
    assert res["checks"]["requests_compared"]["value"] >= 1
    assert set(res["metrics"]) == {"tpot_p50_ms", "output_tok_s", "setup_s"}
    assert res["device"]["kind"] == "TPU v5 lite"
    assert err.strip().splitlines()[-1].startswith("check gap_max: ")


def test_the_lower_precision_control_fails_the_comparison(root, capsys):
    # the reference with every projection, key and value rounded to 8-bit
    # integers, in the program's place on the same served sample: the same
    # comparison that the float32 program passes has to fail it
    rc, res, err = _run(root, capsys, control=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert "gap_max" not in res["checks"]
    c = res["checks"]["control_gap_max"]
    assert c["value"] > c["limit"] == 1e-3
    assert err.strip().splitlines()[-1].startswith("check control_gap_max: ")


def test_traced_run_reports_per_layer_metrics(root, capsys):
    rc, res, err = _run(root, capsys, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True and res["attempted"] > 0
    # per-layer metrics only; a CPU run has no device trace to reduce
    assert set(res["metrics"]) == {"decode_row_occupancy",
                                   "prefill_pad_share"}
    assert "breakdown" not in res


def test_no_result_without_a_chip(root, capsys):
    rc, res, err = _run(root, capsys, server_argv=None)
    assert rc != 0 and res is None
    assert "no TPU" in err


def test_no_result_without_the_program(tmp_path, capsys):
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(tiny.REAL / "BENCHMARK.json", bare / "BENCHMARK.json")
    shutil.copytree(tiny.REAL / "chipbench", bare / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert sorted(os.listdir(bare)) == ["BENCHMARK.json", "chipbench"]
    rc, res, err = _run(bare, capsys, workload="pdq-int8.decode")
    assert rc != 0 and res is None


def _done(idx, n_prompt, n_tokens, t_end, finish="complete"):
    from chipbench import loadgen
    return loadgen.Req(idx=idx, prompt=[1] * n_prompt, max_tokens=n_tokens,
                       status=200, finish=finish, tokens=[2] * n_tokens,
                       times=[t_end] * n_tokens)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_check_sample_holds_the_longest_finished_request(seed):
    reqs = [_done(i, 10 + i, 20, 12.0 + i) for i in range(8)]
    reqs.append(_done(8, 500, 400, 9.0))                  # ended before t0
    reqs.append(_done(9, 400, 300, 15.0, finish=None))    # never finished
    a = run.sample_for_check(reqs, 10.0, 3, seed)
    assert a == run.sample_for_check(reqs, 10.0, 3, seed)
    assert a[0] is reqs[7] and len({r.idx for r in a}) == 3
    assert all(r.completed and r.times[-1] >= 10.0 for r in a)


@pytest.mark.parametrize("control,correct", [(0, True), (1, False)])
def test_the_control_stands_in_for_the_program_in_the_checks(control, correct):
    from types import SimpleNamespace
    cell = SimpleNamespace(per_layer=[], end_to_end=[], root=tiny.REAL,
                           config={"check": {"gap_max": None,
                                             "gap_mean": 0.0015}})
    check = {"n_seqs": 4, "n_tokens": 2000, "gap_max": 0.05,
             "gap_mean": 0.0005, "control_gap_max": 0.2,
             "control_gap_mean": 0.004, "argmax_agree": 0.99, "seconds": 1.0,
             "memory_peak_bytes": 9, "memory_in_use_bytes": 5}
    args = argparse.Namespace(trace=0, control=control)
    out = run.finish(args, cell, {"records": []}, check,
                     {"device": {"platform": "tpu", "kind": "TPU v5 lite",
                                 "count": 1}})
    key = "control_gap_mean" if control else "gap_mean"
    assert out["correct"] is correct
    assert set(out["checks"]) == {"requests_compared", "tokens_compared", key}
    assert out["checks"][key] == {"value": check[key], "limit": 0.0015}
    assert out["device"]["memory_in_use_bytes"] == 5
