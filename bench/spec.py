"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Its configuration is the JSON file the ``configs`` entry names; the
mix is ``bench/traffic/<traffic>.json``; the code that drives the
configuration's system is ``bench/systems/<system>.py``, named by the
configuration's ``system`` key; each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell or a metric adds files and
entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, List, Optional

from bench import traffic as traffic_mod


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: Optional[str] = None          # per-layer metrics: the end-to-end one moved


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    root: Path
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = traffic_mod.load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, workload)]
    e2e_names = {m.name for m in e2e}
    per_layer = [Metric(m["name"], m["unit"], m["moves"])
                 for m in bench["per_layer"]
                 if _applies(m, workload) and m["moves"] in e2e_names]
    return Cell(workload, root, w["config"], config, w["traffic"], mix, int(w["chips"]),
                e2e, per_layer)


def load_system(cell: Cell):
    """The module that runs the configuration's system: ``bench.systems.<system>``."""
    return importlib.import_module(f"bench.systems.{cell.config['system']}")


def load_reader(cell: Cell, metric: str) -> Callable:
    """``read(observation) -> float | None`` from ``bench/metrics/<metric>.py``."""
    path = cell.root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
