"""What the block-diffusion cell adds to the benchmark, at the rehearsal size
on the CPU: its counts by hand, its planted faults and its order control
caught, the stacked replay equal to the forward-by-forward one, and its new
readers on a rehearsed summary. The parametrised tests of the other files
pick the cell up from BENCHMARK.json."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import registry, window  # noqa: E402
from benchmark.harness.peaks import peaks_for, roofline_seconds  # noqa: E402
from test_cells_rehearse import BENCH, cpu_devices, tiny_cell  # noqa: E402
from test_correct_controls import driven, run  # noqa: E402

CELL = "serve-blockdiff-sat"
counts = registry.load_part("counts", "sdar")
ref = registry.load_part("reference", "sdar_ref")

DIMS = {"vocab": 10, "d_model": 4, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 3, "n_experts": 8, "d_ff": 5, "top_k": 2, "n_layers": 2,
        "block_length": 4, "denoising_steps": 2}


# ------------------------------------------------------------------ counts ----

def hand_layer(context):
    projections = 2 * 4 * 12 * 2 + 2 * 4 * 6 * 2  # q and o 4x12, k and v 4x6
    qk_norm = 4 * 3 * (4 + 2)
    rotary = 3 * 3 * (4 + 2)
    attention = 2 * (2 * context * 12)            # scores and values, 4 x 3
    router = 2 * 4 * 8
    experts = 2 * 3 * (2 * 4 * 5)                 # 2 chosen, three matrices
    return projections + qk_norm + rotary + attention + router + experts


def test_one_slot_forward_of_each_kind_by_hand():
    assert counts.layer_flops_position(DIMS, 12) == hand_layer(12) == 1294
    # a block at rows 8..11: each of 4 positions sees 12 rows, 2 layers
    assert counts.forward_flops(DIMS, 8, 4, "commit") == 4 * 2 * 1294
    assert counts.forward_flops(DIMS, 8, 4, "denoise") == \
        4 * (2 * 1294 + 2 * 4 * 10)
    slots = [[8, 4, 2, "denoise"], [0, 4, 0, "commit"]]
    assert counts.step_flops(DIMS, slots) == \
        4 * (2 * 1294 + 80) + 4 * 2 * hand_layer(4)
    # a prompt of 10: two whole blocks stored, seeing 4 and 8 rows, no head
    assert counts.prefill_flops(DIMS, 10) == \
        4 * 2 * hand_layer(4) + 4 * 2 * hand_layer(8)
    assert counts.prefill_flops(DIMS, 3) == 0


def test_required_work_is_the_schedules_not_the_programs():
    """More experts computed, or logits in a commit forward, count nothing;
    a forward that was not run is not counted either."""
    more = dict(DIMS, n_experts=64)
    assert counts.layer_flops_position(more, 5) \
        - counts.layer_flops_position(DIMS, 5) == 2 * 4 * (64 - 8)
    block = [[8, 4, 2, "denoise"], [8, 4, 2, "denoise"], [8, 4, 0, "commit"]]
    assert counts.step_flops(DIMS, block[:2]) < counts.step_flops(DIMS, block)


def test_step_bytes_and_a_roofline_by_hand():
    slots = [[8, 4, 2, "denoise"], [0, 4, 0, "commit"]]
    distinct = 8 * (1 - (1 - 2 / 8) ** 8)         # 8 positions, 2 of 8 each
    assert counts.expected_distinct_experts(DIMS, 8) == pytest.approx(distinct)
    layer = (4 * 3 * (2 * 4 + 2 * 2) + 4 * 8 + 2 * 4 + 2 * 3) * 2
    layer += distinct * 3 * 4 * 5 * 2
    row = 2 * 2 * 3 * 2                           # K and V of one position
    kv = (12 + 4) * row + (4 + 4) * row           # read to the block's end
    ends = 8 * 4 * 2 + (4 * 10 + 4) * 2           # embedding rows, the head
    want = 2 * (layer + kv) + ends
    assert counts.step_bytes(DIMS, slots) == pytest.approx(want)
    assert counts.step_bytes(DIMS, slots[1:]) < want - (4 * 10 + 4) * 2
    assert counts.step_bytes(DIMS, []) == 0.0
    peaks = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e2}
    assert roofline_seconds(counts.step_flops(DIMS, slots), want, peaks) == \
        pytest.approx(max(counts.step_flops(DIMS, slots) / 1e3, want / 1e2))


def test_the_cells_size_is_what_the_issue_counted():
    cell = registry.load_cell(BENCH, CELL)
    dims = registry.load_model(cell).dims_of(cell["config_data"])
    slots = [[256, 4, 2, "denoise"]] * 64
    # 7.9 GB of weights and 0.4 GB of K/V a step, 0.34 TFLOP required
    assert 7.8e9 < counts.step_bytes(dims, slots) < 8.5e9
    assert 0.3e12 < counts.step_flops(dims, slots) < 0.4e12
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cell["config_data"]["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    for key, value in cell["config_data"]["published"].items():
        if key not in entry["reduced"]:
            assert cell["config_data"][key] == value, key


# ------------------------------------------------------ faults and controls ----

@pytest.mark.parametrize("fault,by", [
    ("commit_skipped", "mean_logit_gap"),
    ("unmask_left_to_right", "widest_confidence_gap")])
def test_a_broken_schedule_is_not_correct(fault, by):
    line = run(CELL, fault=fault)
    gap, limit = line["compared"][by]
    assert not line["correct"] and gap > limit
    assert line["compared"]["schedule_faults"][0] == 0  # the records are whole


def test_a_record_that_breaks_the_schedule_is_counted():
    dims = dict(DIMS, mask_token_id=9)
    prompt = [1, 2, 3, 4, 5]
    good = [(1, "denoise", (False, True, True, True), (1, 3), (5, 7, 0, 8)),
            (1, "denoise", (False, False, True, False), (2,), (5, 7, 6, 8)),
            (1, "commit", (False,) * 4, (), (5, 7, 6, 8)),
            (2, "denoise", (True,) * 4, (0, 2), (1, 0, 2, 0)),
            (2, "denoise", (False, True, False, True), (1, 3), (1, 3, 2, 4))]
    assert ref.schedule_faults(prompt, good, 7, 7, dims) == 0
    assert ref.schedule_faults(prompt, good, 6, 7, dims) == 1  # short
    assert ref.schedule_faults(prompt, good[:2] + good[3:], 7, 7, dims) == 1
    greedy = [good[0][:3] + ((1, 2, 3), (5, 7, 6, 8))] + good[2:]
    assert ref.schedule_faults(prompt, greedy, 7, 7, dims) == 1
    clean, noisy = ref.noisy_states(prompt, good, dims)
    assert clean == [1, 2, 3, 4, 5, 7, 6, 8, 1, 3, 2, 4]
    assert [n[0] for n in noisy] == [4, 4, 8, 8]
    assert noisy[1][1] == [5, 7, 9, 8] and noisy[2][1] == [9] * 4


def test_stacked_replay_equals_forward_by_forward_replay():
    driver, rec, sampled = driven(CELL, seed=11)
    config, traffic = driver.config, driver.traffic
    dims = driver.model.dims_of(config)
    requests = [(r.prompt, [f[1:] for f in r.forwards]) for r in sampled[:4]]
    assert {len(p) % 4 for p, _ in requests} != {0}
    clean_width, noisy_width = driver.model.replay_widths(traffic, dims)
    for control in (None, "float8_e4m3fn"):
        stacked = ref.replay(11, dims, requests, "float32", control,
                             clean_width=clean_width, noisy_width=noisy_width)
        single = ref.replay(11, dims, requests, "float32", control,
                            stacked=False)
        for a, b in zip(stacked, single):
            assert len(a) == len(b) > 0
            for (ba, ta, *xa), (bb, tb, *xb) in zip(a, b):
                assert (ba, ta) == (bb, tb)
                for u, v in zip(xa, xb):
                    np.testing.assert_allclose(u, v, atol=2e-5, rtol=0)


# ----------------------------------------------------------------- readers ----

NEW = ["block_step_ms_mean", "block_step_roofline_pct",
       "idle_in_block_phase_pct", "forwards_per_token",
       "moe_share_of_busy_pct.sat", "attn_share_of_busy_pct.sat",
       "tick_admit_ms_mean.blockdiff", "tick_self_ms_mean.blockdiff",
       "idle_in_admit_phase_pct.blockdiff",
       "idle_outside_phases_pct.blockdiff"]


@pytest.fixture(scope="module")
def rehearsed():
    """The cell's summary as a traced run makes it, and a trace made by
    hand over it: the block step took 0.5 ms a run, the prefills 10% of
    the busy time."""
    cell = tiny_cell(CELL)
    driver = registry.load_driver(cell).Driver(cell, 5, cpu_devices(1))
    driver.setup()
    rec = driver.window(0.5, window.TracedPart(False, CELL))
    rec["traced"] = (rec["t0"], rec["t_end"])
    summary = driver.summary(rec)
    model = driver.model
    driver.release()
    steps = sum(1 for stamp, _ in summary["steps"]
                if rec["t0"] <= stamp <= rec["t_end"])
    span = 1e9 * (rec["t_end"] - rec["t0"])
    scoped = [("jit(block_step)/lm_attn/dot", "x", 0.0, 0.2 * span, 0),
              ("jit(block_step)/lm_moe/dot", "y", 0.2 * span, 0.5 * span, 0)]
    trace = {"window_s": rec["t_end"] - rec["t0"], "devices": 1,
             "busy_s": 0.5e-3 * steps / 0.9,
             "busy_s_fullest": 0.5e-3 * steps / 0.9, "scoped": scoped,
             "by_program": {"jit_block_step": 0.5e-3 * steps,
                            "jit_prefill": 0.5e-3 * steps / 9},
             "program_calls": {"jit_block_step": steps, "jit_prefill": 3}}
    return {"cell": cell, "summary": summary, "model": model, "trace": trace,
            "values": {}, "device": {"count": 1, "memory_peak_bytes": 1},
            "peaks": peaks_for("TPU v5 lite"),
            "setup": {"warmup_s": 1.0, "compile_requests": 2,
                      "cache_hits": 1, "compiles_in_window": 0}}


def test_every_metric_that_lists_the_cell_reads_a_number(rehearsed):
    listed = [m["name"] for m in registry.metrics_for(BENCH, "per_layer",
                                                      CELL)]
    assert set(NEW) <= set(listed)
    for name in listed:
        value = registry.metric_reader(name)(rehearsed)
        assert value is not None and np.isfinite(value), name
    read = {n: registry.metric_reader(n)(rehearsed) for n in NEW}
    assert read["block_step_ms_mean"] == pytest.approx(0.5)
    # every slot-forward and every position that left the mask, counted by
    # the engine: 3 forwards to 4 positions, more where a remainder opens
    assert 0.7 < read["forwards_per_token"] < 1.0
    assert read["moe_share_of_busy_pct.sat"] > \
        read["attn_share_of_busy_pct.sat"] > 0
    assert 0 < read["block_step_roofline_pct"] < 100
    # the three idle shares add up to the idle share at the grain of whole
    # programs in this cell too
    t = rehearsed["trace"]
    assert read["idle_in_block_phase_pct"] \
        + read["idle_in_admit_phase_pct.blockdiff"] \
        + read["idle_outside_phases_pct.blockdiff"] == pytest.approx(
            100.0 * (1.0 - sum(t["by_program"].values()) / t["window_s"]))


def test_new_readers_read_none_where_their_program_is_absent(rehearsed):
    bare = dict(rehearsed, trace=dict(
        rehearsed["trace"], by_program={"jit_step": 1.0, "jit_prefill": 0.1},
        program_calls={"jit_step": 7}))
    for name in NEW[:3]:
        assert registry.metric_reader(name)(bare) is None, name
    counters = {k: v for k, v in rehearsed["summary"]["counters"].items()
                if k not in ("block_forwards", "tokens_accepted")}
    flagship = dict(rehearsed, summary=dict(rehearsed["summary"],
                                            counters=counters))
    assert registry.metric_reader("forwards_per_token")(flagship) is None


def test_the_window_differences_the_two_new_counters(rehearsed):
    c = rehearsed["summary"]["counters"]
    records = [f for r in json.loads(json.dumps(
        rehearsed["summary"]["steps"])) for f in r[1]]
    # warm-up ran forwards too: the window's own are fewer than the total
    assert 0 < c["tokens_accepted"] and 0 < c["block_forwards"]
    assert c["block_forwards"] >= len(records)  # open requests ran some more
    assert c["decode_steps"] == len(rehearsed["summary"]["ticks"])
