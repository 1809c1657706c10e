"""``moe_routed_share_of_busy_pct`` (ISSUE 30): the reader on the trace
recorded on the chip, with a compiled text that names the routed expert
layer's parts and with one that does not; and its entry in
``BENCHMARK.json``."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import registry  # noqa: E402
from benchmark.trace import reduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(tr.__file__),
                        "recorded_v5e_serve.json")
PREFILL_TOP = "jit_prefill/multiply_reduce_fusion.2"  # the experts, PR 24


def recorded_run(scopes: dict) -> dict:
    with open(RECORDED) as f:
        return {"trace": tr.reduce_events(json.load(f), scopes)}


NAME = "moe_routed_share_of_busy_pct.sat"


def read(run):
    return registry.metric_reader(NAME)(run)


def test_the_share_is_the_scopes_device_time_over_busy_time():
    """The prefill's expert fusion named as the routed form's grouped
    matmuls, another operation as its sort; a third under ``lm_moe`` alone
    (the all-experts form) does not count."""
    run = recorded_run({
        PREFILL_TOP: "jit(prefill)/while/body/lm_moe/moe_routed_experts/"
                     "ragged_dot",
        "jit_prefill/fusion.1": "jit(prefill)/while/body/lm_moe/"
                                "moe_routed_sort/sort",
        "jit_step/fusion.1": "jit(step)/while/body/lm_moe/dot_general"})
    t = run["trace"]
    want = t["by_op"][PREFILL_TOP] + t["by_op"]["jit_prefill/fusion.1"]
    assert want > 0
    assert read(run) == pytest.approx(100.0 * want / t["busy_s"])
    assert 0.0 < read(run) < 100.0


def test_a_program_that_names_no_routed_part_reads_none():
    """The parent commit, or a cell whose calls stay below the chooser's
    rows an expert: scopes, but none of the routed form's."""
    run = recorded_run({PREFILL_TOP: "jit(prefill)/while/body/lm_moe/"
                                     "vmap()/dot_general"})
    assert read(run) is None
    assert read(recorded_run(None)) is None


def test_the_entry_names_its_cell_and_the_metric_it_moves():
    """One entry, for the cell whose prefill routes, and none for the train
    cells, whose step does not."""
    bench = registry.load_benchmark()
    last = bench["per_layer"][-1]  # appended: the others keep their place
    assert last == {"name": NAME, "unit": "%", "better": "lower",
                    "source": "device_trace", "layer": "model step",
                    "moves": "out_tokens_per_s",
                    "workloads": ["serve-longprompt-sat"]}
    assert [m["name"] for m in bench["per_layer"]
            if m["name"].startswith("moe_routed")] == [NAME]
