"""The harness finds a cell's files by name, checks the real BENCHMARK.json
against the contract's shape, and refuses any device but a known TPU."""
from __future__ import annotations

import hashlib
import json
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import bench, device
from chipbench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _hashes(root: Path) -> dict:
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "chipbench").rglob("*") if p.is_file()}


def test_new_config_traffic_and_metric_files_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    before = _hashes(root)
    cfg = dict(tiny.TINY_CONFIG, name="tiny-wide", hidden_size=128)
    tiny.write_json(root / "chipbench" / "configs" / "tiny-wide.json", cfg)
    tiny.write_json(root / "chipbench" / "traffic" / "long.json",
                    dict(tiny.TINY_TRAFFIC, clients_per_slot=3,
                         prompt_len={"dist": "uniform", "min": 4, "max": 32}))
    (root / "chipbench" / "metrics" / "tokens_per_request.py").write_text(
        "def read(run):\n    return 42.0\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-wide", "source": cfg["source"],
                         "file": "chipbench/configs/tiny-wide.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-wide.long", "config": "tiny-wide",
                           "traffic": "long", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "tokens_per_request", "unit": "tokens",
                           "better": "higher", "source": "host_clock",
                           "layer": "scheduler", "moves": "tpot_p50_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = bench.load_cell("tiny-wide.long", root)
    assert cell.config["hidden_size"] == 128
    assert cell.traffic["clients_per_slot"] == 3
    assert bench.load_kind(cell.traffic["kind"], root).prompt_range(
        cell.traffic) == (4, 32)
    assert [m["name"] for m in cell.per_layer] == ["tokens_per_request"]
    assert bench.load_metric("tokens_per_request", root).read({}) == 42.0
    after = _hashes(root)
    assert all(after[p] == h for p, h in before.items())
    with pytest.raises(KeyError):
        bench.load_cell("no.such.cell", root)
    with pytest.raises(FileNotFoundError):
        bench.load_metric("no_such_metric", root)


def test_the_benchmark_file_names_a_file_for_everything():
    b = bench.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = set()
    for c in b["configs"]:
        assert NAME.match(c["name"]) and (bench.ROOT / c["file"]).is_file()
        assert c["file"].startswith("chipbench/")
    for w in b["workloads"]:
        cell = bench.load_cell(w["name"])
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        bench.load_kind(cell.traffic["kind"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in names
        names.add(m["name"])
        assert hasattr(bench.load_metric(m["name"]), "read")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


def _fake_devices(monkeypatch, platform, kind, n=1):
    import jax
    devs = [SimpleNamespace(platform=platform, device_kind=kind)] * n
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_refuses_the_cpu():
    with pytest.raises(device.NoChip, match="no TPU"):
        device.require_chip(1, bench.ROOT)


def test_refuses_a_device_kind_missing_from_the_peaks_table(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v99 imaginary")
    with pytest.raises(device.NoChip, match="not in"):
        device.require_chip(1, bench.ROOT)


def test_refuses_fewer_chips_than_the_cell_asks_for(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite", n=1)
    with pytest.raises(device.NoChip, match="needs 4 chips"):
        device.require_chip(4, bench.ROOT)


def test_accepts_a_known_tpu(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite", n=4)
    assert device.require_chip(4, bench.ROOT) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}
