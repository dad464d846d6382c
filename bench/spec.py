"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (an entry of ``workloads``) names a configuration, whose sizes live in
``bench/configs/<config>.json``, and a traffic mix, whose parameters live in
``bench/traffic/<traffic>.json``.  A per-layer metric is read by
``bench/metrics/<metric>.py``.  Everything is found by name, so a new cell,
mix or metric is a new file and no edit.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: tuple = ()   # the cells that report it; empty: every cell

    def reported_in(self, cell: str) -> bool:
        return not self.workloads or cell in self.workloads


@dataclass(frozen=True)
class Cell:
    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _metrics(entries, cell: str) -> List[Metric]:
    out = [Metric(e["name"], e["unit"], tuple(e.get("workloads", ())))
           for e in entries]
    return [m for m in out if m.reported_in(cell)]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(name=name,
                config=load_json(ROOT / conf["file"]),
                traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
                chips=int(w["chips"]),
                end_to_end=_metrics(bench["end_to_end"], name),
                per_layer=_metrics(bench["per_layer"], name))


def metric_reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
