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
TINY = {"hidden_size": 32, "intermediate_size": 16, "num_attention_heads": 2,
        "vocab_size": 64}


def tiny_cell(name: str) -> dict:
    """The cell as BENCHMARK.json names it, its files read by name, with the
    widths and lengths cut so that a CPU holds it."""
    cell = copy.deepcopy(registry.load_cell(BENCH, name))
    config, traffic = cell["config_data"], cell["traffic_data"]
    config.update(TINY)
    config["num_key_value_heads"] = 2
    for key in ("num_experts", "num_local_experts"):
        if key in config:
            config[key] = 4
    config["num_experts_per_tok"] = 2
    if traffic["kind"] == "serve":
        config["serve"].update(n_slots=4, max_len=64, min_bucket=8,
                               serve_dtype="f32")
        config["precision"]["weights"] = "float32"
        config["correct"] = {"widest_logit_gap": 1e-3, "mean_logit_gap": 1e-4}
        traffic["prompt_len"].update(min=4, max=40, median=12)
        traffic["answer_len"].update(min=4, max=8, median=6)
        traffic.update(rate_per_s=40.0, clients=6, grid=16, check_requests=12)
    else:
        traffic.update(seq_len=64, batch_sequences=4, pool=4)
        config["correct"] = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                             "change_norm_gap": 1e-3}
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
    spec = json.load(open(os.path.join(registry.BENCH_DIR, "traffic",
                                       traffic + ".json")))
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
