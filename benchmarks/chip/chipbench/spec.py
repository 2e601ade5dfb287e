"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, one traffic mix, one cell's
limits or one per-layer metric sits in a file of its own, found by name:

    configs/<config>.json       the configuration as it is run
    traffic/<traffic>.json      the tuning job's parameters
    limits/<cell>.json          the correctness limits of the cell
    metrics/<metric>.py         the reader of one per-layer metric
    references/<reference>.py   the plain reference a configuration names

so a cell, a configuration or a metric is added without editing a file that
is already there.  A configuration resolves only where it states the model
its trial builds (``modelfile.py``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

from . import modelfile

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]   # the cell's end-to-end metric entries
    per_layer: List[Dict[str, Any]]    # the cell's per-layer metric entries


def load_benchmark(root: str = REPO_ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str, reported: List[str]) -> bool:
    """A metric with a ``workloads`` key is the listed cells'; one without is
    every cell's that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in reported


def resolve(cell_name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r}; cells: {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _json(os.path.relpath(os.path.join(REPO_ROOT, cfg_entry["file"]),
                                   BENCH_DIR))
    modelfile.check(config, cfg_entry["file"])
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, cell_name, reported)]
    return Cell(
        name=cell_name,
        chips=int(w["chips"]),
        config=config,
        traffic=_json("traffic", f"{w['traffic']}.json"),
        limits=_json("limits", f"{cell_name}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[[Any], Optional[float]]:
    """``metrics/<name>.py``'s ``read(run) -> float | None``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    return _load_module(path, "chipbench_metric_" + name.replace(".", "_")).read


def reference_module(name: str):
    path = os.path.join(BENCH_DIR, "references", f"{name}.py")
    return _load_module(path, "chipbench_reference_" + name)


def peaks(device_kind: str) -> Dict[str, float]:
    table = _json("peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]
