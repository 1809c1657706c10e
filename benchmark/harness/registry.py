"""Finds everything by the name a data file gives it.

A cell of BENCHMARK.json names a configuration and a traffic mix; each is a
data file. The configuration's ``model`` is ``models/<model>.py``: the one
place that knows an architecture (its program, its plain reference, its
counts of work, the faults its tests plant). The traffic file's ``driver`` is
``drivers/<driver>.py``: the loop that offers that kind of load. A per-layer
metric ``x`` or ``x.suffix`` is read by ``metrics/x.py``. This file holds no
table of names and reads no key of a configuration beyond ``model``, so a
later PR adds files and entries and edits nothing; a name with no file is
refused with the list of the files there are.

``ROOT`` and ``BENCH_DIR`` are where BENCHMARK.json and the benchmark's
directories are looked for (a test points them at a copy).
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


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
    return {**cell, "config_data": config, "traffic_data": traffic}


def load_part(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of the benchmark, whatever files a
    later PR has put there. A model's file finds its reference and its
    counts this way too. Loaded once a path: its jitted functions keep
    their compiled programs from one cell to the next."""
    folder = os.path.join(BENCH_DIR, kind)
    path = os.path.join(folder, name + ".py")
    if not os.path.exists(path):
        there = sorted(f[:-3] for f in os.listdir(folder)
                       if f.endswith(".py") and f != "__init__.py")
        raise FileNotFoundError(f"no {path}: benchmark/{kind}/ has {there}")
    key = f"benchmark_{kind}_{name}"
    module = sys.modules.get(key)
    if module is None or module.__file__ != path:
        spec = importlib.util.spec_from_file_location(key, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[key] = module
    return module


def load_driver(cell: dict):
    return load_part("drivers", cell["traffic_data"]["driver"])


def load_model(cell: dict):
    return load_part("models", cell["config_data"]["model"])


def metric_reader(metric_name: str):
    """``read(run) -> float | None`` of the metric's own file; the part
    after the first dot only says which cells' end-to-end metric it moves."""
    base = metric_name.split(".", 1)[0]
    return load_part("metrics", base).read


def metrics_for(bench: dict, group: str, cell_name: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: those that list it, and those that list no cells at all."""
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]
