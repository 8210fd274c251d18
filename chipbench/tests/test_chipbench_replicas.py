"""A configuration's chip layout: ``serve.replicas`` data-parallel replicas
on the cell's chips, built as a ('data', 'model') mesh; a layout that does
not use exactly the cell's chips is an error at set-up."""
from __future__ import annotations

import argparse
import json

import pytest

from chipbench import run, server
from chipbench.tests import tiny


def _serve(**kw):
    return {"serve": dict(tiny.TINY_CONFIG["serve"], **kw)}


@pytest.mark.parametrize("serve,chips,want", [
    ({}, 1, (1, 1)), ({}, 4, (1, 4)), ({"replicas": 4, "slots": 64}, 4, (4, 1)),
    ({"replicas": 2, "slots": 4}, 4, (2, 2)), ({"replicas": 2, "slots": 4}, 2, (2, 1)),
])
def test_layout_uses_every_chip_of_the_cell(serve, chips, want):
    assert server.layout(_serve(**serve), chips) == want


@pytest.mark.parametrize("serve,chips,match", [
    ({"replicas": 4, "slots": 64}, 1, "do not divide"),
    ({"replicas": 3, "slots": 6}, 4, "do not divide"),
    ({"replicas": 4, "slots": 6}, 4, "do not split"),
    ({"replicas": 0}, 1, "do not divide"),
])
def test_a_layout_off_the_cells_chips_is_an_error(serve, chips, match):
    with pytest.raises(ValueError, match=match):
        server.layout(_serve(**serve), chips)


def test_the_cell_slots_split_over_the_replicas():
    from types import SimpleNamespace
    mesh = SimpleNamespace(shape={"data": 4, "model": 1})
    sc = server.serve_config(dict(tiny.TINY_CONFIG, **_serve(slots=64)), mesh)
    assert (sc.slots, sc.slots_per_replica, sc.mesh) == (64, 16, mesh)
    one = server.serve_config(tiny.TINY_CONFIG)
    assert (one.slots, one.slots_per_replica, one.mesh) == (2, None, None)


def test_two_replicas_on_two_virtual_chips_serve_correctly(tmp_path, capsys,
                                                           monkeypatch):
    root = tiny.make_root(tmp_path)
    cfg = dict(tiny.TINY_CONFIG, name="tiny-replicas2",
               serve=dict(tiny.TINY_CONFIG["serve"], replicas=2, slots=4))
    tiny.write_json(root / "chipbench" / "configs" / "tiny-replicas2.json", cfg)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-replicas2", "source": cfg["source"],
                         "file": "chipbench/configs/tiny-replicas2.json",
                         "reduced": cfg["reduced"], "why": "test"})
    b["workloads"].append({"name": "tiny.replicas2", "config": "tiny-replicas2",
                           "traffic": "tiny_closed", "chips": 2, "why": "test"})
    for m in b["per_layer"]:
        m["workloads"].append("tiny.replicas2")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    # the server child sees two CPU devices
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2")
    args = argparse.Namespace(
        workload="tiny.replicas2", seed=2**31 + 41, seconds=2.0, trace=1,
        control=0, root=str(root), server_argv=tiny.server_argv("none"))
    rc = run.run(args)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 2
    assert '"slots": 4, "replicas": 2' in out.err
    # the cell's four rows, over both replicas, never above full
    assert 0 < res["metrics"]["decode_row_occupancy"]["value"] <= 100
