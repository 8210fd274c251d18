"""Find a cell's configuration, traffic mix and metrics by name (no JAX here).

Every lookup is relative to a root that holds ``BENCHMARK.json``: the
configuration file is the one the entry names, a traffic mix is
``chipbench/traffic/<traffic>.json``, its generator is
``chipbench/traffic/kinds/<kind>.py`` and a metric is
``chipbench/metrics/<name>.py``.  A new cell, mix or metric is a new file.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
PKG = "chipbench"


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    centry = cfgs[w["config"]]
    with open(root / centry["file"]) as f:
        config = json.load(f)
    with open(root / PKG / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(root=root, name=name, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def _load_file(path: Path, modname: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, root: Path = ROOT):
    """The reader module of metric ``name``: ``read(run) -> float | None``."""
    return _load_file(Path(root) / PKG / "metrics" / f"{name}.py",
                      f"{PKG}_metric_{name.replace('.', '_')}")


def load_kind(kind: str, root: Path = ROOT):
    """The generator module of a traffic kind."""
    return _load_file(Path(root) / PKG / "traffic" / "kinds" / f"{kind}.py",
                      f"{PKG}_kind_{kind}")


def load_roofline(kernel: str, root: Path = ROOT):
    """Operations and bytes of one kernel: ``flops(**shape)``, ``bytes(**shape)``."""
    return _load_file(Path(root) / PKG / "roofline" / f"{kernel}.py",
                      f"{PKG}_roofline_{kernel}")


def load_peaks(root: Path = ROOT) -> dict:
    with open(Path(root) / PKG / "roofline" / "peaks.json") as f:
        return json.load(f)["devices"]
