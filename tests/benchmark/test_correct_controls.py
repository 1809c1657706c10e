"""The comparison that decides ``correct`` has to fail what it should: the
control (the reference in the nearest precision below the configuration's,
put in the program's place) and each fault planted under the timed path.
Tiny sizes on the CPU; the readings at the cells' own sizes are in PERF.md.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import registry, window  # noqa: E402
from test_cells_rehearse import BENCH, cpu_devices, tiny_cell  # noqa: E402

CELLS = {c["name"]: registry.load_cell(BENCH, c["name"])
         for c in BENCH["workloads"]}
SERVE = [n for n, c in CELLS.items() if c["traffic_data"]["kind"] == "serve"]
TRAIN = [n for n in CELLS if n not in SERVE]
# the faults each cell can have, planted by its model's own file: they
# reach into the program's attributes, which another model names otherwise
FAULTS = [(n, f) for n in TRAIN
          for f in registry.load_model(CELLS[n]).faults(CELLS[n])]


def run(name, fault=None, seed=7, bench=BENCH):
    """One rehearsal of the cell through ``run_cell``, with the named fault
    of its model planted under the timed path; the result line's object."""
    cell = tiny_cell(name, bench)
    tamper = registry.load_model(cell).faults(cell)[fault] if fault else None
    out = bench_run.run_cell(cell, bench, seed=seed, seconds=0.5, trace=False,
                             devices=cpu_devices(cell["chips"]),
                             tamper=tamper)
    return json.loads(out["line"])


def driven(name, seed=7):
    """A driver after its window, released: ready for ``check``."""
    cell = tiny_cell(name)
    driver = registry.load_driver(cell).Driver(
        cell, seed, cpu_devices(cell["chips"]))
    driver.setup()
    rec = driver.window(0.5, window.TracedPart(False, name))
    sampled = driver.sample(rec)
    driver.release()
    return driver, rec, sampled


# ------------------------------------------------------------------ serve ----

@pytest.mark.parametrize("name", SERVE)
def test_an_altered_token_is_not_correct(name):
    line = run(name, fault="alter_a_token")
    gap, limit = line["compared"]["widest_logit_gap"]
    assert not line["correct"] and gap > limit


@pytest.mark.parametrize("name", SERVE)
def test_serve_control_in_lower_precision_is_not_correct(name):
    driver, rec, sampled = driven(name)
    ok, compared = driver.check(rec, sampled)
    assert ok, compared
    # the cells' own control: the weights through float8_e4m3fn
    ok, compared = driver.check(rec, sampled, control_via="float8_e4m3fn")
    gap, limit = compared["mean_logit_gap"]
    assert not ok and gap > limit


def test_a_request_that_never_finishes_is_not_correct():
    driver, rec, sampled = driven(SERVE[0])
    rec["requests"][0].done.clear()
    if rec["loop"] == "closed":
        pytest.skip("a closed loop's open requests are cut, not failed")
    ok, compared = driver.check(rec, sampled)
    assert not ok and compared["requests_never_finished"][0] == 1.0


# ------------------------------------------------------------------ train ----

@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f}" for n, f in FAULTS])
def test_a_broken_step_is_not_correct(name, fault):
    line = run(name, fault=fault)
    assert not line["correct"], line["compared"]
    over = [k for k, (v, lim) in line["compared"].items() if v > lim]
    assert over
    if fault == "state_unchanged":
        # a leaf that has not moved reads 1 by the worst-leaf measure
        assert line["compared"]["change_norm_gap"][0] == pytest.approx(1.0)


@pytest.mark.parametrize("name", TRAIN)
def test_train_control_in_bfloat16_is_not_correct(name):
    driver, rec, sampled = driven(name)
    ok, compared = driver.check(rec, sampled)
    assert ok, compared
    ok, compared = driver.check(rec, sampled, control_via="bfloat16")
    assert not ok, compared


def test_worst_leaf_gap_takes_the_median_leaf_for_small_ones():
    from benchmark.drivers.train_step import compare_steps, worst_leaf_gap

    want = {"a": 10.0, "b": 1.0, "c": 1e-6}
    assert worst_leaf_gap({"a": 11.0, "b": 1.0, "c": 2e-6}, want) == \
        pytest.approx(0.1)
    got = {"losses": [1.0], "grad_norms": dict(want),
           "change_norms": {"a": 10.0, "b": 1.0, "c": 5.0}}
    ref = {"losses": [1.0], "grad_norms": want, "change_norms": want}
    # c's gradient is nought against the median leaf: round-off moves it
    assert compare_steps(got, ref)["change_norm_gap"] == 0.0
    assert np.isclose(compare_steps(got, ref)["loss_gap"], 0.0)
