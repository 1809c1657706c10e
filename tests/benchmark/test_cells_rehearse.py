"""Every cell of BENCHMARK.json through run.py's own code path, at a tiny
size on the CPU: a rehearsal of control flow and counts, never of a device
metric (``run.py`` itself refuses to run without a chip)."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import serve_engine  # noqa: E402
from benchmark.harness import peaks, registry  # noqa: E402

BENCH = registry.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


def overlay(base: dict, cut: dict) -> None:
    """``cut`` laid over ``base``: a group into the group, a value in the
    value's place."""
    for key, value in cut.items():
        if isinstance(value, dict) and isinstance(base.get(key), dict):
            overlay(base[key], value)
        else:
            base[key] = value


def tiny_cell(name: str, bench: dict = BENCH) -> dict:
    """The cell as BENCHMARK.json names it, its files read by name, cut to
    what a CPU holds by the ``rehearse`` block that its configuration file
    and its traffic file each carry: this function knows no key of either."""
    cell = copy.deepcopy(registry.load_cell(bench, name))
    for data in (cell["config_data"], cell["traffic_data"]):
        overlay(data, data.pop("rehearse"))
    return cell


def cpu_devices(n: int) -> list:
    import jax

    return jax.devices("cpu")[:n]


@pytest.fixture(scope="module")
def results():
    return {}


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses_and_is_correct(name, results):
    cell = tiny_cell(name)
    out = bench_run.run_cell(cell, BENCH, seed=2**31 + 5, seconds=0.5,
                             trace=False, devices=cpu_devices(cell["chips"]))
    line = json.loads(out["line"])
    assert line["correct"], line["compared"]
    assert list(line)[-1] == "compared"
    assert line["attempted"] > 0 and line["failed"] == 0
    wanted = {m["name"] for m in registry.metrics_for(BENCH, "end_to_end",
                                                      name)}
    assert set(line["metrics"]) == wanted and "setup_s" in wanted
    assert all(v["value"] > 0 for v in line["metrics"].values())
    results[name] = line


def test_a_traced_serve_summary_names_the_scopes_of_its_programs():
    """What the serve driver hands the trace reduction in a traced run: the
    decode program's and the reached prefill programs' own scopes, by
    program and instruction."""
    from benchmark.harness import window

    name = next(n for n in CELLS if registry.load_cell(BENCH, n)[
        "traffic_data"]["kind"] == "serve")
    cell = tiny_cell(name)
    driver = registry.load_driver(cell).Driver(cell, 3, cpu_devices(1))
    driver.setup()
    rec = driver.window(0.2, window.TracedPart(False, name))
    assert driver.summary(rec)["scopes"] is None  # not traced: none kept
    rec["traced"] = (rec["t0"], rec["t_end"])
    scopes = driver.summary(rec)["scopes"]
    driver.release()
    programs = {key.split("/")[0] for key in scopes}
    assert programs == {"jit_step", "jit_prefill"}
    wanted = set(driver.model.program_scopes()) - {"lm_loss", "lm_update"}
    for program in programs:
        named = {part for key, scope in scopes.items()
                 if key.startswith(program + "/")
                 for part in scope.split("/") if part.startswith("lm_")}
        assert named >= wanted - {"lm_cache_write"}, program


@pytest.mark.parametrize("name", CELLS)
def test_every_metric_of_the_cell_has_its_reader(name):
    per_layer = registry.metrics_for(BENCH, "per_layer", name)
    assert per_layer, "a cell reports at least one per-layer metric"
    assert any("mfu" in m["name"] for m in per_layer)
    for m in per_layer:
        assert callable(registry.metric_reader(m["name"]))
        assert any(e["name"] == m["moves"] and name in e.get(
            "workloads", [name]) for e in BENCH["end_to_end"])


@pytest.mark.parametrize("traffic", sorted(
    {c["traffic"] for c in BENCH["workloads"]}))
def test_two_seeds_offer_the_same_work(traffic):
    with open(os.path.join(registry.BENCH_DIR, "traffic",
                           traffic + ".json")) as f:
        spec = json.load(f)
    if spec["kind"] != "serve":
        # a train job has one shape: what the seed draws is the rows
        assert spec["seq_len"] * spec["batch_sequences"] >= 4096
        assert spec["pool"] >= 3, "the first three steps need three batches"
        return
    hists = []
    for seed in (1, 2**31 + 7):
        t = serve_engine.Traffic(spec, seed, vocab=100, grid=64)
        work = [t.take(j) for j in range(64)]
        hists.append((sorted(len(p) for p, _ in work),
                      sorted(a for _, a in work)))
    assert hists[0] == hists[1]
    lens = np.array(hists[0][0])
    assert lens.min() >= spec["prompt_len"]["min"]
    assert lens.max() <= spec["prompt_len"]["max"]
    a = serve_engine.open_arrivals(3, 50, 10.0)
    assert len(a) == 50 and (np.diff(a) >= 0).all() and a.max() < 10.0


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_run_py_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark",
                                                     "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
