"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

* a configuration: the file its ``configs`` entry names;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: ``bench/metrics/<name>.py``, a module whose
  ``read(ctx)`` returns the metric's value, or None when the run holds
  nothing for it to read (the metric is then left out of the line).

Adding a cell, a configuration or a metric is adding files and entries:
nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path


class CellError(ValueError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list             # metric entries this cell reports
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(root: Path, workload: str) -> Cell:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)])


def reader(root: Path, name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    if spec is None or not path.exists():
        raise CellError(f"no reader for per-layer metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
