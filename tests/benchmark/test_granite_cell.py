"""The hybrid cell's own pieces at a tiny size on the CPU: each fault its
model plants fails ``correct``, the state a retired request leaves is
compared with the reference's, its counts against a hand count, and its
three readers of the recurrence on a run built by hand."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import registry  # noqa: E402
from test_cells_rehearse import BENCH  # noqa: E402
from test_correct_controls import run  # noqa: E402

CELL = "serve-granite-chat-sat"
counts = registry.load_part("counts", "granite")
model = registry.load_part("models", "granite")


@pytest.mark.parametrize("fault", ["padding_in_state", "conv_state_dropped",
                                   "state_in_bfloat16", "float8_weights"])
def test_each_planted_fault_is_not_correct(fault):
    line = run(CELL, fault=fault, seed=2**31 + 3)
    assert not line["correct"], line["compared"]
    over = [k for k, (v, lim) in line["compared"].items() if v > lim]
    # a state rounded to bfloat16 moves no greedy token of the tiny model:
    # the state it leaves in the slot shows it
    assert over == ["state_gap"] if fault == "state_in_bfloat16" else over
    from deeplearning4j_tpu.models import ssm
    assert ssm._advance.__name__ == "_advance"


def test_a_retired_request_carries_the_state_it_left():
    """Each finished request holds its slot's state in every Mamba layer,
    read along the reference's probe, and it is the reference's state
    after the request's last token but one."""
    import numpy as np

    from test_correct_controls import driven

    driver, rec, sampled = driven(CELL)
    dims = driver.dims
    n_mamba = dims["kinds"].count("mamba")
    k = model.ref.state_probe(dims).shape[0]
    done = [r for r in rec["requests"] if r.done.is_set()]
    assert done and all(r.state_read.shape == (n_mamba, dims["ssm_heads"], k)
                        for r in done)
    ok, compared = driver.check(rec, sampled)
    assert ok and compared["state_gap"][0] < 1e-5, compared


def test_state_gaps_by_hand():
    import numpy as np

    want = np.zeros((1, 2, 2, 3))
    want[..., 0] = 2.0
    got = want.copy()
    got[0, 1, 0] = [2.0, 1.0, 0.0]     # off by 1 of 2
    got[0, 0, 1] = [0.0, 0.0, 0.0]     # all of it gone
    np.testing.assert_allclose(model.state_gaps(got, want),
                               [[[0.0, 1.0], [0.5, 0.0]]])


# one period of (mamba, attention), two of them; widths small enough to
# count by hand
DIMS = {"vocab": 10, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 2, "d_ff": 3, "n_layers": 4,
        "kinds": ("mamba", "attention", "mamba", "attention"),
        "ssm_heads": 2, "ssm_head_dim": 3, "ssm_state": 5, "ssm_groups": 1,
        "ssm_conv": 4}


def test_counts_by_hand():
    # mixer: in_proj 4 x (6 + 16 + 2), conv 4 x 16, recurrence, out 6 x 4
    scan = 5 * 2 * 3 * 5 + 2 * 2 * 3
    assert counts.scan_token_flops(DIMS) == scan == 162
    mamba = 2 * 4 * 24 + 2 * 4 * 16 + scan + 2 * 6 * 4
    assert counts.mamba_token_flops(DIMS) == mamba
    # attention at context 5: q 4 x 4, k and v 4 x 2, o 4 x 4, scores, values
    attention = 2 * 4 * 8 + 2 * 4 * 4 + 2 * 2 * 5 * 4
    assert counts.attention_token_flops(DIMS, 5) == attention
    mlp = 2 * 4 * 6 + 2 * 3 * 4
    assert counts.token_flops(DIMS, 5, logits=True) == \
        2 * mamba + 2 * attention + 4 * mlp + 2 * 4 * 10
    # state: 2 layers x (2 x 3 x 5 floats + 3 rows x 16 in bfloat16)
    assert counts.state_bytes_slot(DIMS) == 2 * (30 * 4 + 3 * 16 * 2)
    assert counts.state_step_bytes(DIMS, [3, 9]) == 2 * 2 * 2 * 30 * 4
    assert counts.state_step_flops(DIMS, [3, 9]) == 2 * 2 * scan
    assert counts.scan_flops(DIMS, 7) == 2 * 7 * scan
    assert counts.scan_bytes(DIMS, 7) == 2 * (7 * (12 + 2 + 10) * 4 + 120)
    assert counts.decode_step_bytes(DIMS, []) == 0.0
    weights = counts.weight_count(DIMS)
    one = counts.decode_step_bytes(DIMS, [0])
    assert one == weights * 2 + 2 * counts.state_bytes_slot(DIMS) + 4 * 2 \
        + 2 * 2 * (2 * 1 * 2 * 2)


def test_the_recurrence_readers_on_a_run_by_hand():
    """A traced part with two prompt passes and two decode steps, and the
    scopes' device time: the readers divide the counts' least time by it."""
    from benchmark.harness.peaks import roofline_seconds

    peaks = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e6}
    steps = [[1.5, [[3, 1], [7, 1]]], [1.6, [[4, 1]]], [9.0, [[5, 1]]]]
    requests = [{"t_first": 1.2, "prompt_len": 3},
                {"t_first": 1.7, "prompt_len": 6},
                {"t_first": 5.0, "prompt_len": 9}]
    scoped = [("jit_prefill/lm_ssm/ssm_scan/dot", "jit_prefill", 0, 2_000, 0),
              ("jit_step/lm_ssm/ssm_step/fusion", "jit_step", 3_000, 7_000, 0),
              ("jit_step/lm_mlp/dot", "jit_step", 7_000, 9_000, 0)]
    run_ = {"trace": {"devices": 1, "scoped": scoped, "busy_s": 9e-6},
            "summary": {"dims": DIMS, "traced": (1.0, 2.0), "steps": steps,
                        "requests": requests, "scopes": {"x": "lm_ssm"}},
            "model": model, "peaks": peaks}
    scan = sum(roofline_seconds(counts.scan_flops(DIMS, n),
                                counts.scan_bytes(DIMS, n), peaks)
               for n in (3, 6))
    step = sum(roofline_seconds(model.ssm_step_flops(DIMS, s),
                                model.ssm_step_bytes(DIMS, s), peaks)
               for _, s in steps[:2])
    read = registry.metric_reader
    assert read("ssm_scan_roofline_pct")(run_) == pytest.approx(
        100 * scan / 2e-6)
    assert read("ssm_step_roofline_pct")(run_) == pytest.approx(
        100 * step / 4e-6)
    assert read("ssm_share_of_busy_pct.granite")(run_) == pytest.approx(
        100 * 6e-6 / 9e-6)
    # a model that counts no recurrence, or a program with no such scope
    assert read("ssm_step_roofline_pct")(dict(run_, model=object())) is None
    run_["trace"]["scoped"] = scoped[2:]
    assert read("ssm_scan_roofline_pct")(run_) is None


def test_the_cell_reports_the_readers_it_names():
    names = {m["name"] for m in registry.metrics_for(BENCH, "per_layer",
                                                     CELL)}
    assert {"ssm_share_of_busy_pct.granite", "ssm_scan_roofline_pct",
            "ssm_step_roofline_pct", "serve_mfu_pct.granite",
            "prefill_pad_pct.granite", "host_ms_per_tick.granite",
            "idle_in_admit_phase_pct.granite",
            "idle_in_decode_phase_pct.granite",
            "idle_outside_phases_pct.granite"} <= names
