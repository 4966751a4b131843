"""Finds a cell and everything that belongs to it by name.

``BENCHMARK.json`` at the root of the checkout names each cell's
configuration and traffic; the rest is found as files:

  bench/configs/<config>.json   the model configuration as it is run
  bench/traffic/<traffic>.json  the traffic mix: which general job runs
                                it (``job``) and its parameters
  bench/limits/<cell>.json      the limit of each number ``correct``
                                compares in that cell
  bench/metrics/<metric>.py     the reader of one per-layer metric

A new cell is new files and new entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = ROOT


def benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    b = benchmark(root)
    try:
        w = next(w for w in b["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in b['workloads']]}") from None
    conf = next(c for c in b["configs"] if c["name"] == w["config"])
    e2e = [m for m in b["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in b["per_layer"]
                 if m["moves"] in reported and _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(root / conf["file"]),
                traffic=_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                limits=_json(root / "bench" / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, root=root)


def reader(metric: str, root: Path = ROOT) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
