"""A configuration of another architecture is new files and new entries.

The test copies the benchmark, lays the files of ``second_model/`` over the
copy (a model's file with a reference and counts of its own, a configuration
with its ``rehearse`` block, a traffic mix) and adds a cell to the copy's
BENCHMARK.json, then points the registry at the copy: the new cell
rehearses through ``run_cell``, its planted fault is caught, the four real
cells still rehearse, and no file the copy started with has changed. A second
test holds the harness to naming no model.
"""

import hashlib
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import registry  # noqa: E402
from test_cells_rehearse import CELLS  # noqa: E402
from test_correct_controls import run  # noqa: E402

SECOND = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "second_model")
NEW_CELL = "serve-otherkeys-chat"


def digests(root: str) -> dict:
    out = {}
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The copy with the second model added, the registry pointed at it;
    yields (root of the copy, digests before, BENCHMARK.json before)."""
    root = str(tmp_path_factory.mktemp("files_only"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = digests(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    old = json.loads(json.dumps(bench))

    with open(os.path.join(SECOND, "BENCHMARK.add.json")) as f:
        add = json.load(f)
    for folder, _, files in os.walk(os.path.join(SECOND, "benchmark")):
        for name in files:
            src = os.path.join(folder, name)
            dst = os.path.join(root, os.path.relpath(src, SECOND))
            assert not os.path.exists(dst), "only new files"
            shutil.copy(src, dst)
    bench["configs"] += add["configs"]
    bench["workloads"] += add["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in add["joins"]:
            m["workloads"].append(NEW_CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)

    saved = registry.ROOT, registry.BENCH_DIR
    registry.ROOT, registry.BENCH_DIR = root, os.path.join(root, "benchmark")
    yield root, before, old
    registry.ROOT, registry.BENCH_DIR = saved


def rehearse(name: str, fault=None) -> dict:
    """Through the copy: the registry points there."""
    return run(name, fault, seed=2**31 + 11, bench=registry.load_benchmark())


def test_the_second_model_added_files_and_entries_alone(copy):
    root, before, old = copy
    after = digests(root)
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"BENCHMARK.json"}
    added = set(after) - set(before)
    assert added == {os.path.join("benchmark", *parts) for parts in (
        ("models", "otherkeys.py"), ("reference", "otherkeys_ref.py"),
        ("counts", "otherkeys.py"), ("configs", "otherkeys-serve.json"),
        ("traffic", "otherkeys-chat.json"))}
    new = registry.load_benchmark()
    groups = ("configs", "workloads", "end_to_end", "per_layer")
    for group in groups:
        kept = new[group][:len(old[group])]
        for entry in kept:  # a metric's list of cells may gain the new one
            if NEW_CELL in entry.get("workloads", []):
                entry["workloads"].remove(NEW_CELL)
        assert kept == old[group], group
    assert {k: v for k, v in new.items() if k not in groups} == {
        k: v for k, v in old.items() if k not in groups}


def test_the_new_cell_rehearses_on_its_own_model(copy):
    cell = registry.load_cell(registry.load_benchmark(), NEW_CELL)
    model = registry.load_model(cell)
    assert model.__file__.startswith(copy[0])
    dims = model.dims_of(cell["config_data"])
    assert dims["d_ff"] == 1024 and dims["head_dim"] * dims["n_heads"] == \
        dims["d_model"]
    line = rehearse(NEW_CELL)
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"token_gap_mean_ms", "setup_s"}
    # its readers find its own counts through the model
    run = {"summary": {"dims": dims, "t0": 0.0, "t_end": 2.0, "seconds": 2.0,
                       "requests": [{"t_first": 1.0, "prompt_len": 3}],
                       "steps": [[1.5, [[3, 1], [7, 2]]], [9.0, [[4, 1]]]]},
           "model": model, "device": {"count": 1},
           "peaks": {"bf16_flops": 1e6}}
    counts = registry.load_part("counts", "otherkeys")
    want = counts.prefill_flops(dims, 3) + counts.step_flops(
        dims, [[3, 1], [7, 2]])
    assert registry.metric_reader("serve_mfu_pct.chat")(run) == \
        pytest.approx(100.0 * want / (1e6 * 2.0))


def test_the_new_cells_planted_fault_is_not_correct(copy):
    line = rehearse(NEW_CELL, fault="alter_a_token")
    gap, limit = line["compared"]["widest_logit_gap"]
    assert not line["correct"] and gap > limit


@pytest.mark.parametrize("name", CELLS)
def test_the_real_cells_still_rehearse_beside_it(name, copy):
    line = rehearse(name)
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_a_name_with_no_file_lists_the_files_there_are(copy):
    with pytest.raises(FileNotFoundError, match="otherkeys"):
        registry.load_part("models", "no_such_model")
    with pytest.raises(FileNotFoundError, match="serve_engine"):
        registry.load_driver({"traffic_data": {"driver": "serve"}})


NAMES_A_MODEL = re.compile(
    r"flagship|init_lm_params|DecodeEngine|transformer_lm|"
    r"make_(single_device|composed)_train_step|"
    r"benchmark[./](models|reference|counts)\b")


def test_the_harness_names_no_model():
    """``run.py``, ``harness/``, ``drivers/``, ``metrics/`` and ``trace/``
    reach a model through the registry alone: none imports or calls a
    model's module, reference or counts, or the program's model code, by
    name (a docstring may say what an engine is). The registry keeps no
    table of names."""
    bench = os.path.join(ROOT, "benchmark")
    files = [os.path.join(bench, "run.py")]
    for part in ("harness", "drivers", "metrics", "trace"):
        files += [os.path.join(bench, part, f)
                  for f in sorted(os.listdir(os.path.join(bench, part)))
                  if f.endswith(".py")]
    assert len(files) > 40
    found = []
    for path in files:
        with open(path) as f:
            code = strip_docstrings(f.read())
        found += [f"{os.path.relpath(path, ROOT)}: {line.strip()}"
                  for line in code.splitlines() if NAMES_A_MODEL.search(line)]
    assert not found, found
    with open(os.path.join(bench, "harness", "registry.py")) as f:
        assert "DRIVERS" not in f.read()


def strip_docstrings(source: str) -> str:
    """``source`` without its docstrings and comments: what runs."""
    import ast

    tree = ast.parse(source)
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef)) \
                and ast.get_docstring(node) is not None:
            node.body = node.body[1:] or [ast.Pass()]
    return ast.unparse(tree)
