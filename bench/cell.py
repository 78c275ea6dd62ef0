"""Find a benchmark cell and everything it names, by name, from files.

``BENCHMARK.json`` at the root of the checkout lists the configurations,
cells (``workloads``) and metrics.  Everything that belongs to one of
them sits in a file of its own under ``bench/``:

* a configuration: the file its ``configs`` entry names
  (``bench/configs/<config>.json``);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* the correctness limits of a cell: ``bench/limits/<cell>.json``;
* the reader of a per-layer metric: ``bench/layer_metrics/<metric>.py``,
  a module with ``read(ctx) -> float | None``;
* a configuration's plain reference: ``bench/references/<name>.py``.

So a later change adds a cell, a configuration or a metric by adding
files and entries, without editing a file that is already there.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    readers: Dict[str, Callable] = field(default_factory=dict)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(Path(root) / "BENCHMARK.json") as fh:
        return json.load(fh)


def _named(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read`` function of ``bench/layer_metrics/<name>.py``."""
    path = Path(root) / "bench" / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    w = _named(bench["workloads"], name, "workload")
    c = _named(bench["configs"], w["config"], "configuration")
    with open(root / c["file"]) as fh:
        config = json.load(fh)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as fh:
        traffic = json.load(fh)
    with open(root / "bench" / "limits" / f"{name}.json") as fh:
        limits = json.load(fh)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a workloads list is reported in every
    # cell that reports the end-to-end metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)
    cell.readers = {m["name"]: load_reader(m["name"], root)
                    for m in per_layer}
    return cell
