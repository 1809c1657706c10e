"""The block under a spec, and generation by diffusion over blocks, against
the benchmark's plain reference (``benchmark/reference/sdar_ref.py``) at a
small size: seeded weights, float32, the CPU.

- the program's prompt pass and cached step, logit for logit, with each
  part of the spec switched on alone over the flagship's block and with all
  of them together;
- the engine's generation against the reference's loop, token for token and
  record for record, with requests of different phase in the slots at once;
- the provisional rows a denoising forward leaves never reach a later
  forward; the steady loop compiles nothing; the fast paths that assume one
  causal token a step are refused; a checkpoint carries the spec.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import registry  # noqa: E402
from deeplearning4j_tpu.models import transformer_lm as lm  # noqa: E402
from deeplearning4j_tpu.serve.engine import DecodeEngine  # noqa: E402
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry  # noqa: E402

ref = registry.load_part("reference", "sdar_ref")

V, D, H, E, F, L, K = 64, 32, 4, 4, 16, 2, 2
MAXLEN, SEED, SCALE = 64, 2**31 + 3, 0.18
MASK_ID = V - 1

# the flagship's block in the reference's words, and the program's default
FLAGSHIP = {"vocab": V, "d_model": D, "n_heads": H, "n_kv_heads": H,
            "head_dim": D // H, "n_experts": E, "d_ff": F, "top_k": K,
            "n_layers": L, "norm": "layernorm", "eps": 1e-6,
            "rope_theta": None, "qk_norm": False, "ffn": "relu",
            "norm_topk_prob": True, "final_norm": False, "block_length": 1,
            "denoising_steps": 1, "mask_token_id": MASK_ID,
            "init_scale": SCALE}
# each part: (the spec's fields, the reference's switches)
PARTS = {
    "rmsnorm": (dict(norm="rmsnorm", norm_eps=1e-6, final_norm=True),
                dict(norm="rmsnorm", final_norm=True)),
    "rotary": (dict(rope_theta=1e6), dict(rope_theta=1e6)),
    "grouped_kv": (dict(n_kv_heads=2), dict(n_kv_heads=2)),
    "qk_norm": (dict(qk_norm=True, norm_eps=1e-6), dict(qk_norm=True)),
    "gated_experts": (dict(ffn="swiglu", bias=False), dict(ffn="swiglu")),
    "gates_as_the_config_says": (dict(norm_topk_prob=False),
                                 dict(norm_topk_prob=False)),
    "float32_router_and_logits": (dict(accum_f32=True), {}),
    "block_mask": (dict(attn_mask="block", block_length=4),
                   dict(block_length=4)),
}
ALL = ({k: v for fields, _ in PARTS.values() for k, v in fields.items()
        if k != "norm_topk_prob"},
       {k: v for _, switches in PARTS.values() for k, v in switches.items()
        if k != "norm_topk_prob"})
SDAR_SPEC = lm.BlockSpec(**ALL[0], generation="block_diffusion",
                         denoising_steps=2, mask_token_id=MASK_ID)
SDAR_DIMS = dict(FLAGSHIP, **ALL[1], denoising_steps=2)


def program_params(spec):
    return lm.init_lm_params(ref.seed_key(SEED), V, D, H, E, F, L, spec=spec,
                             init_scale=SCALE)


@pytest.fixture(scope="module")
def sdar():
    return program_params(SDAR_SPEC), ref.model_weights(SEED, SDAR_DIMS)


# ------------------------------------------------------- logit for logit ----

@pytest.mark.parametrize("part", list(PARTS) + ["all_together"])
def test_prefill_and_cached_step_match_the_reference(part):
    fields, switches = ALL if part == "all_together" else PARTS[part]
    spec, dims = lm.BlockSpec(**fields), dict(FLAGSHIP, **switches)
    params, weights = program_params(spec), ref.model_weights(SEED, dims)
    # the same draws (to the last place or two: one side scales them
    # inside a jitted program)
    np.testing.assert_allclose(params["blocks"]["wk"][1],
                               weights["layers"][1]["wk"], rtol=1e-6)
    np.testing.assert_allclose(params["dec_w"], weights["ends"]["head"],
                               rtol=1e-6)

    tokens = np.random.default_rng(1).integers(0, V, size=20)
    want = np.asarray(ref.forward_logits(weights, dims, tokens))
    logits, ks, vs = lm.lm_prefill(params, jnp.asarray(tokens[None]), H, K,
                                   spec=spec)
    np.testing.assert_allclose(logits[0], want, atol=2e-5, rtol=0)

    # the cached step: 16 rows stored, then a forward of the next 4 over
    # the cache, in a cache of three slots with the request in the second
    n_kv, hd = spec.kv_heads(H), spec.head_size(D, H)
    cache = lm.init_kv_cache(L, 3, n_kv, hd, MAXLEN)
    cache = {"k": cache["k"].at[:, 1, :, :16].set(ks[:, 0, :, :16]),
             "v": cache["v"].at[:, 1, :, :16].set(vs[:, 0, :, :16])}
    block = np.zeros((3, 4), np.int32)
    block[1] = tokens[16:]
    starts = jnp.array([0, 16, 0], jnp.int32)
    _, got = lm.lm_verify_step(params, cache, jnp.asarray(block), starts, H,
                               K, spec)
    np.testing.assert_allclose(got[1], want[16:], atol=2e-5, rtol=0)


@pytest.mark.parametrize("part", ["flagship"] + list(PARTS)
                         + ["all_together"])
def test_the_three_callers_of_the_one_block_agree(part):
    """Hidden states of the same tokens through the block's three callers:
    the training form (``_lm_hidden``, a dense core under the spec's mask,
    ``dense_moe`` by name, as the loss builders hand them in), the prompt
    pass, and the cached step fed the tokens in chunks of four. The first
    two run the same operations, and under the flagship's spec agree to the
    bit (the compiler may fuse another spec's differently, by a last
    place); the cached step reduces over the padded cache."""
    fields = {} if part == "flagship" else \
        (ALL if part == "all_together" else PARTS[part])[0]
    spec = lm.BlockSpec(**fields)
    params = program_params(spec)
    rows, length, width = 2, 24, 4
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        0, V, size=(rows, length)), jnp.int32)

    trained, moe_ins = lm._lm_hidden(
        params, tokens, H, lm._prefill_core(spec, "dense"),
        lambda rw, ex, x: lm.dense_moe(rw, ex, x, K, spec), spec)
    prefilled, (ks, vs) = lm._prefill_hidden(params, tokens, H, K, "dense",
                                             spec)
    assert moe_ins.shape == (L, rows * length, D)
    np.testing.assert_allclose(trained, prefilled, rtol=0,
                               atol=0 if part == "flagship" else 1e-5)

    cache = lm.init_kv_cache(L, rows, spec.kv_heads(H), spec.head_size(D, H),
                             MAXLEN)
    chunks = []
    for start in range(0, length, width):
        cache, h = lm._cached_layers(
            params, cache, params["embed"][tokens[:, start:start + width]],
            jnp.full((rows,), start, jnp.int32), H, K, spec=spec)
        chunks.append(h)
    np.testing.assert_allclose(jnp.concatenate(chunks, axis=1), prefilled,
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(cache["k"][:, :, :, :length], ks, atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(cache["v"][:, :, :, :length], vs, atol=1e-5,
                               rtol=0)


def test_block_step_with_masked_positions_matches_the_reference(sdar):
    params, weights = sdar
    tokens = np.random.default_rng(2).integers(0, V, size=12)
    masked = np.array([False, True, False, True])
    state = np.where(masked, MASK_ID, tokens[8:])
    want = np.asarray(ref.forward_logits(
        weights, SDAR_DIMS, np.concatenate([tokens[:8], state])))[8:]
    _, ks, vs = lm.lm_prefill(params, jnp.asarray(tokens[None, :8]), H, K,
                              spec=SDAR_SPEC)
    cache = lm.init_kv_cache(L, 1, 2, D // H, MAXLEN)
    cache = {"k": cache["k"].at[:, 0, :, :8].set(ks[:, 0]),
             "v": cache["v"].at[:, 0, :, :8].set(vs[:, 0])}
    cache, got = lm.lm_block_step(
        params, cache, jnp.asarray(tokens[None, 8:], jnp.int32),
        jnp.array([8], jnp.int32), jnp.asarray(masked[None]), H, K, SDAR_SPEC)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=0)
    # the choice made on the device is the reference's
    toks, left = lm.unmask_most_confident(
        got, jnp.asarray(tokens[None, 8:], jnp.int32),
        jnp.asarray(masked[None]), 1, jax.random.PRNGKey(0),
        jnp.zeros((1,)))
    ref_toks, _, took = ref.unmask(want, masked, 1)
    assert list(np.flatnonzero(masked & ~np.asarray(left[0]))) == took
    assert int(toks[0, took[0]]) == int(ref_toks[took[0]])


# ------------------------------------------------------------- the engine ----

def engine(params, **kw):
    kw.setdefault("n_slots", 3)
    return DecodeEngine(params, H, max_len=MAXLEN, top_k=K, serve_dtype="f32",
                        min_bucket=8, registry=MetricsRegistry(), tuned=False,
                        runprof=False, spec=SDAR_SPEC, **kw)


# prompts of every remainder mod 4 (one shorter than a block), lengths of
# answer that are and are not multiples of 4 (the driver's warm-up sends 2)
WORK = [(5, 7), (8, 2), (3, 9), (10, 8), (7, 4), (16, 1), (21, 8)]


def test_generation_matches_the_reference_token_and_record(sdar):
    params, weights = sdar
    eng = engine(params)
    rng = np.random.default_rng(3)
    reqs = []
    for i, (n, m) in enumerate(WORK):
        reqs.append(eng.submit(rng.integers(0, V, size=n).tolist(),
                               max_new_tokens=m))
        if i % 2:
            eng.step()  # three requests of different phase in the slots
    eng.run_until_idle()
    assert eng.stats()["occupancy_mean"] > 1.5
    for r in reqs:
        generated, records = ref.generate(weights, SDAR_DIMS, r.prompt,
                                          r.max_new_tokens)
        assert r.generated == generated, (len(r.prompt), r.max_new_tokens)
        assert [f[1:] for f in r.forwards] == records
        assert r.finish_reason == "max_new_tokens"
        assert len(r.t_tokens) == len(r.generated)
        assert ref.schedule_faults(r.prompt, records, len(generated),
                                   r.max_new_tokens, SDAR_DIMS) == 0
    record = eng.metrics_record()
    forwards = sum(len(r.forwards) for r in reqs)
    assert record["serve_block_forwards_total"] == forwards
    assert record["serve_block_tokens_accepted_total"] == sum(
        len(f[4]) for r in reqs for f in r.forwards)
    assert record["serve_block_steps_total"] == eng.decode_steps
    assert record["serve_decode_step_ms_count"] == eng.decode_steps


def test_a_prompt_may_hold_the_mask_tokens_id(sdar):
    params, weights = sdar
    prompt = [MASK_ID, 5, MASK_ID, 7, 9, MASK_ID]
    got = engine(params).generate(prompt, max_new_tokens=6)
    assert got == ref.generate(weights, SDAR_DIMS, prompt, 6)[0]


def test_eos_ends_a_request_inside_a_block(sdar):
    params, weights = sdar
    prompt = list(range(1, 8))
    plain = ref.generate(weights, SDAR_DIMS, prompt, 8)[0]
    eos = plain[2]
    eng = engine(params)
    r = eng.submit(prompt, max_new_tokens=8, eos_id=eos)
    eng.run_until_idle()
    assert r.finish_reason == "eos"
    assert r.generated == plain[:plain.index(eos)]
    assert r.generated == ref.generate(weights, SDAR_DIMS, prompt, 8, eos)[0]


def test_a_slot_whose_next_block_passes_max_len_retires(sdar):
    params, _ = sdar
    eng = DecodeEngine(params, H, n_slots=1, max_len=18, top_k=K,
                       serve_dtype="f32", min_bucket=8,
                       registry=MetricsRegistry(), tuned=False, runprof=False,
                       spec=SDAR_SPEC)
    r = eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=40)
    eng.run_until_idle()
    assert r.finish_reason == "max_len"
    assert len(r.generated) == 16 - 6 and r.forwards[-1][2] == "commit"
    with pytest.raises(ValueError, match="no whole block"):
        eng.submit(list(range(1, 17)), max_new_tokens=2)


def test_provisional_rows_never_reach_a_later_forward(sdar):
    """After every step the rows that its denoising forwards wrote are
    overwritten with a large value: the next forward of the same block has
    to write its own before it reads, and no other may look there."""
    params, weights = sdar
    eng = engine(params)
    step = eng._block_step

    def poisoning(p, cache, tokens, starts, masked, *rest):
        cache, toks, left = step(p, cache, tokens, starts, masked, *rest)
        for slot in np.flatnonzero(np.asarray(masked).any(axis=1)):
            rows = slice(int(starts[slot]), int(starts[slot]) + 4)
            cache = {name: leaf.at[:, slot, :, rows].set(1e3)
                     for name, leaf in cache.items()}
        return cache, toks, left

    eng._block_step = poisoning
    rng = np.random.default_rng(5)
    reqs = [eng.submit(rng.integers(0, V, size=n).tolist(), max_new_tokens=m)
            for n, m in WORK[:5]]
    eng.run_until_idle()
    for r in reqs:
        assert r.generated == ref.generate(weights, SDAR_DIMS, r.prompt,
                                           r.max_new_tokens)[0]


def test_steady_loop_compiles_nothing(sdar, retrace_budget):
    params, _ = sdar
    eng = engine(params)
    for n in (5, 12):  # buckets 8 and 16, and the block step
        eng.generate([1] * n, max_new_tokens=2)
    rng = np.random.default_rng(6)
    with retrace_budget(0, label="block-diffusion steady loop"):
        reqs = []
        for n, m in WORK[:6]:
            reqs.append(eng.submit(rng.integers(0, V, size=n).tolist(),
                                   max_new_tokens=m))
            eng.step()
        eng.run_until_idle()
    assert all(r.done.is_set() for r in reqs)


@pytest.mark.parametrize("kw,why", [
    (dict(speculative=True), "no next token"),
    (dict(prefill_chunk=8), "stores whole blocks"),
    (dict(prefix_cache=True), "opens a generated block"),
])
def test_fast_paths_of_one_token_a_step_are_refused(sdar, kw, why):
    with pytest.raises(ValueError, match=why):
        engine(sdar[0], **kw)


def test_the_tick_names_the_block_step_and_its_scopes(sdar):
    from deeplearning4j_tpu.telemetry import trace

    params, _ = sdar
    eng = engine(params)
    t0 = time.perf_counter()
    eng.generate([1, 2, 3, 4, 5], max_new_tokens=4)
    entries, _ = trace.phases_between(t0, time.perf_counter())
    decodes = [attrs for name, *_, attrs in entries if name == "tick.decode"]
    assert decodes and all(a["kind"] == "block" for a in decodes)
    # 3 positions left of the first block, then the 4 of the next: two
    # denoising forwards each, one commit between them
    assert sum(a["denoise"] for a in decodes) == 4
    assert sum(a["commit"] for a in decodes) == 1
    assert sum(a["accepted"] for a in decodes) == 3 + 4
    text = eng._block_step.lower(
        eng.params, eng._cache, eng._block_tokens, eng._positions,
        eng._block_masked, eng._temps, eng._key, 0).as_text(debug_info=True)
    assert "jit_block_step" in text or "block_step" in text
    for scope in ("lm_embed", "lm_attn", "lm_cache_write", "lm_moe",
                  "lm_sample") + lm.LM_SPEC_SCOPES:
        assert scope in text, scope


def test_each_block_tick_holds_one_dispatch_and_one_fence(sdar):
    """Inside every block tick's ``tick.decode``: the jitted call under
    ``tick.dispatch``, then the copy of its tokens and bitmap under
    ``tick.fence``, both named by the program the device trace counts."""
    from deeplearning4j_tpu.telemetry import trace

    eng = engine(sdar[0])
    t0 = time.perf_counter()
    eng.generate([1, 2, 3, 4, 5], max_new_tokens=4)
    entries, _ = trace.phases_between(t0, time.perf_counter())
    decodes = [e for e in entries if e[0] == "tick.decode"]
    assert decodes
    for _, tick, lo, hi, _ in decodes:
        inside = sorted((e[2], e[0], e[4]["program"]) for e in entries
                        if e[0] in ("tick.dispatch", "tick.fence")
                        and e[1] == tick and lo <= e[2] and e[3] <= hi)
        assert [x[1:] for x in inside] == [
            ("tick.dispatch", "jit_block_step"),
            ("tick.fence", "jit_block_step")]


# ------------------------------------------------------------ checkpoints ----

def test_a_checkpoint_carries_the_spec_into_the_engine(sdar, tmp_path):
    from deeplearning4j_tpu.scaleout.ckpt.checkpointer import Checkpointer

    params, weights = sdar
    root = str(tmp_path / "ckpt")
    Checkpointer(root).save(
        2, {"params": params},
        meta=lm.lm_checkpoint_meta(params, H, K, spec=SDAR_SPEC))
    eng = DecodeEngine.from_checkpoint(root, max_len=MAXLEN, serve_dtype=None,
                                       tuned=False)
    assert eng.block_spec == SDAR_SPEC and eng.block_mode and eng.top_k == K
    prompt = [3, 1, 4, 1, 5, 9]
    assert eng.generate(prompt, max_new_tokens=6) == ref.generate(
        weights, SDAR_DIMS, prompt, 6)[0]


def test_a_flagship_checkpoint_written_before_specs_still_loads(tmp_path):
    from deeplearning4j_tpu.scaleout.ckpt.checkpointer import Checkpointer

    params = lm.init_lm_params(jax.random.PRNGKey(0), V, D, H, E, F, L)
    # the meta block exactly as lm_checkpoint_meta wrote it before this PR
    old_meta = {"lm": {**lm.lm_dims(params), "n_heads": H, "top_k": K}}
    assert lm.lm_checkpoint_meta(params, H, K) == old_meta
    root = str(tmp_path / "ckpt")
    Checkpointer(root).save(1, {"params": params}, meta=old_meta)
    eng = DecodeEngine.from_checkpoint(root, max_len=MAXLEN, serve_dtype=None,
                                       tuned=False)
    assert eng.block_spec == lm.FLAGSHIP_SPEC and not eng.block_mode
    direct = DecodeEngine(params, H, max_len=MAXLEN, top_k=K,
                          serve_dtype=None, tuned=False)
    assert eng.generate([1, 2, 3], max_new_tokens=4) == \
        direct.generate([1, 2, 3], max_new_tokens=4)
