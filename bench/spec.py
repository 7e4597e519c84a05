"""Finds a cell's parts by the names in ``BENCHMARK.json``.

- a configuration: the JSON file its entry names (``file``);
- a traffic mix: ``bench/traffic/<traffic>.json``, whose ``runner`` names
  ``bench/runners/<runner>.py``, the code that builds the system under test
  and drives it with that mix;
- a per-layer metric: ``bench/metrics/<name>.py``, whose ``read(record)``
  returns the number or None when the run has nothing to read.

A new cell, mix or metric is new files plus new entries; nothing here or in
``run.py`` changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib


@dataclasses.dataclass
class Cell:
    root: pathlib.Path
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def runner(self):
        return load_module(self.root / "bench" / "runners"
                           / f"{self.traffic['runner']}.py")

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")


def load_module(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root, workload: str) -> Cell:
    """The cell called ``workload`` of the benchmark at ``root``."""
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} (have {sorted(by_name)})")
    w = by_name[workload]
    (config_entry,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    config = json.loads((root / config_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    layer = [m for m in bench["per_layer"] if _reports(m, workload)]
    return Cell(root, w, config, traffic, e2e, layer)
