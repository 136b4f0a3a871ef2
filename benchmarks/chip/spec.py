"""Find a cell's files by the names in ``BENCHMARK.json``.

* a configuration: the ``file`` its ``configs`` entry names;
* a traffic mix: ``traffic/<traffic>.json``;
* a cell's engine settings and correctness limit: ``cells/<workload>.json``;
* a per-layer metric: the reader ``metrics/<quantity>.py``, where the
  quantity is the metric's name up to its first ``.`` (``engine_step_ms``
  reads both ``engine_step_ms.batch`` and ``engine_step_ms.slo``).

A later change adds a configuration, a mix, a cell or a metric by adding
files and entries, never by editing one.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    settings: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def _reports(metric: dict, workload: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read(run)`` function of per-layer metric ``name``."""
    quantity = name.split(".", 1)[0]
    path = Path(root) / HERE.relative_to(ROOT) / "metrics" / f"{quantity}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{quantity}", path)
    if mod_spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def cell(workload: str, root: Path = ROOT) -> Cell:
    """Everything one workload of ``BENCHMARK.json`` needs, resolved."""
    root = Path(root)
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    data = root / HERE.relative_to(ROOT)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = [m["name"] for m in e2e]
    layer = [m for m in bench["per_layer"] if _reports(m, workload, names)]
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=_read_json(root / conf["file"]),
        mix=_read_json(data / "traffic" / f"{entry['traffic']}.json"),
        settings=_read_json(data / "cells" / f"{workload}.json"),
        end_to_end=e2e, per_layer=layer,
        readers={m["name"]: reader(m["name"], root) for m in layer})
