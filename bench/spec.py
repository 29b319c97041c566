"""``BENCHMARK.json`` and the files it names, found by name.

A cell (one entry of ``workloads``) resolves to

* its configuration: ``bench/configs/<config>.json`` (tables and graph
  model; see :mod:`bench.data`);
* its traffic mix: ``bench/traffic/<traffic>.json``: the name of an
  operation, ``bench/operations/<operation>.py`` (see :mod:`bench.drive`),
  and its parameters;
* its end-to-end metrics: those of ``end_to_end`` whose ``workloads``
  list names it, or that have no such list;
* its per-layer metrics, likewise, each read by
  ``bench/metrics/<metric>.py`` (or the file of the name's first part),
  a module with ``read(run)``.

A new cell, configuration, mix or metric is new files plus entries in
``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(path: str) -> ModuleType:
    """Import a file by path (metric names hold dots, so no import name)."""
    name = "bench_file_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str) -> ModuleType:
    """``metrics/<name>.py``, else the reader of the name's first part:
    ``device_idle.extract`` and ``device_idle.analyze`` share
    ``metrics/device_idle.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        path = os.path.join(BENCH_DIR, "metrics",
                            f"{name.split('.')[0]}.py")
    return load_module(path)


def operation(name: str) -> ModuleType:
    return load_module(os.path.join(BENCH_DIR, "operations", f"{name}.py"))


def roofline(kernel: str) -> ModuleType:
    return load_module(os.path.join(BENCH_DIR, "roofline", f"{kernel}.py"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, workload: str) -> Dict[str, object]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = _load_json(os.path.relpath(
        os.path.join(ROOT, entry["file"]), BENCH_DIR))
    traffic = _load_json("traffic", f"{cell['traffic']}.json")
    end_to_end: List[dict] = [m for m in bench["end_to_end"]
                              if _applies(m, workload)]
    per_layer: List[dict] = [m for m in bench["per_layer"]
                             if _applies(m, workload)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer}


def peaks(device_kind: str) -> dict:
    table = _load_json("peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json; add its peaks with their source")
    return table["devices"][device_kind]
