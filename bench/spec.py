"""Find a cell's files by the names in BENCHMARK.json.

A cell (one entry of `workloads`) names a configuration and a traffic
mix.  Everything that belongs to one of them sits in a file of its own,
so that a later change adds a cell, a configuration, a mix or a
per-layer metric by adding files and never edits one:

  bench/configs/<config>.json   sizes as run, numerics, the correctness limit
  bench/configs/<config>.py     the plain float32 reference and weight layout
  bench/traffic/<traffic>.json  parameters of the one load generator
  bench/metrics/<metric>.py     a reader of one per-layer metric

This module imports nothing of the program under test.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Metric:
    name: str
    unit: str
    workloads: Optional[List[str]]        # None: every cell reports it
    moves: Optional[str] = None           # per-layer: the end-to-end metric

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass
class Cell:
    """One workload with its configuration and traffic resolved."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> Dict[str, Any]:
    return json.loads(Path(path).read_text())


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """Resolve workload `name`; a name BENCHMARK.json lacks is an error."""
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    config = json.loads(
        (bench_dir / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())

    def metrics(key: str) -> List[Metric]:
        out = [Metric(name=m["name"], unit=m["unit"],
                      workloads=m.get("workloads"), moves=m.get("moves"))
               for m in bench[key]]
        return [m for m in out if m.applies_to(name)]

    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"))


def _load_module(path: Path, prefix: str) -> ModuleType:
    """Import a file by path; its name may hold dots (`a.b.py`)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = prefix + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config_name: str,
                     bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The configuration's plain reference, `configs/<config>.py`."""
    return _load_module(bench_dir / "configs" / f"{config_name}.py",
                        "bench_ref_")


def metric_reader(metric_name: str, bench_dir: Path = BENCH_DIR):
    """The `read(ctx)` function of `metrics/<metric>.py`."""
    return _load_module(bench_dir / "metrics" / f"{metric_name}.py",
                        "bench_metric_").read
