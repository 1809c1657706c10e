"""chip_smoke.py on the CPU: its device gate refuses, and its phases pass at
toy widths when driven as functions (the chip runs them at the flagship's)."""

import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(vocab=64, d_model=32, n_heads=2, n_experts=2, d_ff=64,
           n_layers=1, seq=16, batch=2)


def test_device_gate_refuses_the_cpu(capsys):
    assert chip_smoke.main() != 0
    said = capsys.readouterr()
    assert "'cpu'" in said.err
    assert said.out == ""  # no phase ran, no summary line


def test_verdict_line_holds_the_two_keys_and_no_others():
    line = chip_smoke.verdict_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.fixture(scope="module")
def trained():
    return chip_smoke.phase_train(TOY, steps=3)


def test_train_phase(trained):
    _, losses = trained
    assert len(losses) == 3 and losses[-1] < losses[0]


def test_train_phase_fails_when_the_loss_does_not_fall():
    step = lambda p, tk, tg: (p, 1.0)  # noqa: E731
    with pytest.raises(chip_smoke.SmokeFailure, match="did not fall"):
        chip_smoke.run_steps(step, {}, None, None, 3, "stuck")


def test_save_and_serve_phases(trained, tmp_path):
    params, _ = trained
    root = str(tmp_path / "ckpt")
    assert chip_smoke.phase_save(params, TOY, root, 3).startswith(root)
    # one prefill bucket: each compile here is a second off tier-1's budget
    serve = dict(slots=2, max_len=32, max_new_tokens=3, prompt_lens=(5, 7))
    got = chip_smoke.phase_serve(root, TOY, serve, str(tmp_path))
    assert got["requests"] == 2 and got["prefill_buckets_used"] == [8]


def test_blockdiff_phase_agrees_with_the_reference():
    got = chip_smoke.phase_blockdiff(chip_smoke.BLOCKDIFF,
                                     chip_smoke.TOL_BLOCKDIFF)
    assert got["requests"] == len(chip_smoke.BLOCKDIFF["prompt_lens"])
    # on the CPU the float32 program picks the reference's own tokens
    assert got["compared"]["widest_logit_gap"] < 1e-3
    assert got["compared"]["schedule_faults"] == 0
    with pytest.raises(chip_smoke.SmokeFailure, match="schedule_faults"):
        chip_smoke.phase_blockdiff(chip_smoke.BLOCKDIFF,
                                   dict(chip_smoke.TOL_BLOCKDIFF,
                                        schedule_faults=-1.0))


def test_kernel_phase_interprets_on_the_cpu():
    got = chip_smoke.phase_kernels(
        dict(dense=(16, 128, 128), lstm=((8, 128),), flash=None))
    assert {r["branch"] for r in got.values()} == {"interpret"}
    assert set(got) == {"fused_dense_f32", "fused_dense_bf16",
                        "lstm_gates_8x128"}


def test_kernel_phase_holds_the_routed_experts_to_the_dense_ones():
    """The routed stage at toy widths that the chooser routes: values in
    both types, gradients in float32."""
    got = chip_smoke._routed_against_dense(
        dict(d_model=16, d_ff=32, n_experts=8, top_k=2,
             calls=((512, "bfloat16", False), (512, "float32", True))), 2e-2)
    assert set(got) == {"routed_moe_512_bfloat16", "routed_moe_512_float32"}
    assert got["routed_moe_512_float32"]["grad_err"] < 1e-4
    with pytest.raises(chip_smoke.SmokeFailure, match="would not route"):
        chip_smoke._routed_against_dense(
            dict(d_model=16, d_ff=32, n_experts=8, top_k=2,
                 calls=((8, "float32", False),)), 2e-2)
    assert chip_smoke.KERNELS["routed"]["calls"] == (
        (2048, "bfloat16", False), (512, "float32", True))


def test_compile_cache_helper_places_and_respects():
    from deeplearning4j_tpu.utils.compile_cache import ensure_compile_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {name: getattr(jax.config, name) for name in names}
    try:
        jax.config.update(names[0], None)
        jax.config.update(names[1], 1.0)
        placed = os.path.join(REPO, ".jax_cache")
        assert ensure_compile_cache() == placed
        assert jax.config.jax_compilation_cache_dir == placed
        # a placed cache keeps the sub-second programs too
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        jax.config.update(names[0], "/elsewhere")
        jax.config.update(names[1], 1.0)
        assert ensure_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
