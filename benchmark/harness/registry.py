"""Finds everything by the name BENCHMARK.json gives it.

A cell names a configuration and a traffic mix; each is a data file. A
per-layer metric ``x`` or ``x.suffix`` is read by ``metrics/x.py``. The
driver comes from the traffic file's ``kind``. Nothing here lists names, so a
later PR adds files and entries and edits nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
DRIVERS = {"serve": "serve_engine", "train": "train_step"}


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(bench: dict, name: str) -> dict:
    """The cell with its configuration and traffic files read in."""
    for cell in bench["workloads"]:
        if cell["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[c['name'] for c in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load_json(os.path.join(ROOT, entry["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      cell["traffic"] + ".json"))
    if traffic["kind"] not in DRIVERS:
        raise KeyError(f"traffic kind {traffic['kind']!r} has no driver; "
                       f"known: {sorted(DRIVERS)}")
    return {**cell, "config_data": config, "traffic_data": traffic}


def _import_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(kind: str):
    return _import_file(os.path.join(BENCH_DIR, "drivers",
                                     DRIVERS[kind] + ".py"),
                        "benchmark_driver_" + kind)


def metric_reader(metric_name: str):
    """``read(run) -> float | None`` of the metric's own file; the part
    after the first dot only says which cells' end-to-end metric it moves."""
    base = metric_name.split(".", 1)[0]
    path = os.path.join(BENCH_DIR, "metrics", base + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"per-layer metric {metric_name!r} has no "
                                f"reader at {path}")
    return _import_file(path, "benchmark_metric_" + base).read


def metrics_for(bench: dict, group: str, cell_name: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: those that list it, and those that list no cells at all."""
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]
