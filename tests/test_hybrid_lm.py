"""A layer pattern of Mamba-2 and attention layers (ISSUE 37) at a tiny
size, float32, seeded weights: the chunked scan against the recurrence, the
serving path against the plain reference (``benchmark/reference/
granite_ref.py``), the period loop against a layer-by-layer loop, slots
that neither leak nor keep state, and the engine options refused."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.harness import registry  # noqa: E402
from deeplearning4j_tpu.models import ssm  # noqa: E402
from deeplearning4j_tpu.models import transformer_lm as lm  # noqa: E402
from deeplearning4j_tpu.serve.engine import DecodeEngine  # noqa: E402
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry  # noqa: E402

ref = registry.load_part("reference", "granite_ref")
granite = registry.load_part("models", "granite")

# Granite's keys at a tiny size: two periods of (mamba, mamba, attention,
# mamba), 4 query heads over 2 K/V heads, 4 mixer heads in 2 groups
CONFIG = {
    "vocab_size": 48, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "shared_intermediate_size": 24,
    "num_hidden_layers": 8,
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 6,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 4,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.125, "logits_scaling": 8, "rms_norm_eps": 1e-5,
    "weights": {"embed_scale": 0.02},
}
DIMS = ref.dims_of(CONFIG)
SPEC = granite.block_spec(DIMS)
SEED = 2**31 + 9
# float32 programs on the CPU against the float32 reference at "highest":
# the chunked scan and the recurrence sum the same terms in another order,
# so they agree to float32 rounding over a few dozen rows
TOL = 2e-5
decode_step = jax.jit(lm.lm_decode_step, static_argnames=("n_heads", "spec"))


@pytest.fixture(scope="module")
def params():
    return lm.init_lm_params(ref.seed_key(SEED), DIMS["vocab"],
                             DIMS["d_model"], DIMS["n_heads"], 0,
                             DIMS["d_ff"], DIMS["n_layers"], spec=SPEC,
                             init_scale=DIMS["embed_scale"])


def engine(params, **kw):
    kw.setdefault("n_slots", 2)
    return DecodeEngine(params, DIMS["n_heads"], max_len=32, top_k=0,
                        serve_dtype=None, min_bucket=8, tuned=False,
                        runprof=False, spec=SPEC,
                        registry=kw.pop("registry", MetricsRegistry()), **kw)


def scan_inputs(seed, t, groups=1, n=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, p, s = 3, 4, 5
    x = jax.random.normal(ks[0], (n, t, groups, r, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (n, t, groups, r)) - 1.0)
    a = -jax.random.uniform(ks[2], (groups, r), minval=0.5, maxval=4.0)
    b = jax.random.normal(ks[3], (n, t, groups, s))
    c = jax.random.normal(ks[4], (n, t, groups, s))
    s0 = jax.random.normal(ks[5], (n, groups, r, p, s))
    return x, dt, a, b, c, s0


def sequential(x, dt, a, b, c, initial=None):
    """The recurrence one row at a time, ``ssd_chunked``'s arguments and
    results: the chunked form's parity oracle."""
    n = x.shape[0]
    g, r, p = x.shape[2:]
    s0 = (jnp.zeros((n, g, r, p, b.shape[-1])) if initial is None
          else initial)

    def one(s, row):
        xt, dtt, bt, ct = row
        s = s * jnp.exp(dtt * a)[..., None, None] \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, None, :]
        return s, jnp.einsum("zgrps,zgs->zgrp", s, ct,
                             precision=jax.lax.Precision.HIGHEST)

    final, ys = jax.lax.scan(one, s0, tuple(jnp.moveaxis(v, 1, 0)
                                            for v in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), final


def prefill_logits(params, tokens, lengths):
    """The prompt pass's logits at every row (the serving program takes
    the last real row's alone)."""
    h, _ = lm._hybrid_prefill_hidden(params, tokens, DIMS["n_heads"], None,
                                     SPEC, lengths)
    return lm._lm_head(params, h, SPEC)


@pytest.mark.parametrize("t,groups", [(4, 1), (11, 1), (16, 2), (23, 2)])
def test_chunked_scan_equals_the_recurrence(t, groups):
    """Lengths a whole number of chunks and not, one group and two, from a
    zero state and from a given one."""
    x, dt, a, b, c, s0 = scan_inputs(t, t, groups)
    for initial in (None, s0):
        y, final = ssm.ssd_chunked(x, dt, a, b, c, 4, initial)
        y_ref, final_ref = sequential(x, dt, a, b, c, initial)
        np.testing.assert_allclose(y, y_ref, rtol=TOL, atol=TOL)
        np.testing.assert_allclose(final, final_ref, rtol=TOL, atol=TOL)


def test_a_padded_bucket_keeps_the_state_of_the_last_real_row(params):
    """The prompt pass over 11 tokens padded to 16 hands over the state and
    the conv rows the unpadded pass ends with, and the same outputs on
    the real rows."""
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["mamba"])
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 16, DIMS["d_model"]))
    out, (conv, state) = ssm.mixer_prefill(p["mixer"], u, SPEC, 11)
    out_ref, (conv_ref, state_ref) = ssm.mixer_prefill(p["mixer"], u[:, :11],
                                                       SPEC, 11)
    np.testing.assert_allclose(state, state_ref, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(conv, conv_ref, rtol=0, atol=0)
    np.testing.assert_allclose(out[:, :11], out_ref, rtol=TOL, atol=TOL)
    # a prompt shorter than the conv's window: zeros before it
    _, (conv2, _) = ssm.mixer_prefill(p["mixer"], u, SPEC, 2)
    assert float(jnp.abs(conv2[0, 0]).max()) == 0.0
    assert float(jnp.abs(conv2[0, 1:]).min()) > 0.0


def test_prefill_then_decode_matches_the_reference(params):
    """The serving programs' logits, prompt pass then the cached step a
    token at a time, against the reference's full forward."""
    tokens = np.array(jax.random.randint(jax.random.PRNGKey(3), (13,), 0,
                                         DIMS["vocab"]))
    n = 8
    want = np.asarray(ref.logits(SEED, DIMS, tokens[None], via="float32"))[0]
    cache = lm.init_hybrid_cache(SPEC, DIMS["n_layers"], 2,
                                 DIMS["n_kv_heads"], DIMS["head_dim"], 32)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :n] = tokens[:n]
    logits = prefill_logits(params, padded, n)
    np.testing.assert_allclose(logits[0, :n], want[:n], rtol=TOL, atol=TOL)
    prefill = lm.make_prefill_step(DIMS["n_heads"], 0, spec=SPEC)
    cache, _ = prefill(params, cache, padded, n - 1, 1, np.float32(0),
                       jax.random.PRNGKey(0), 0)
    for pos in range(n, len(tokens)):
        toks = np.array([0, tokens[pos]], np.int32)
        cache, out = decode_step(params, cache, toks,
                                 np.array([0, pos], np.int32),
                                 n_heads=DIMS["n_heads"], spec=SPEC)
        np.testing.assert_allclose(out[1], want[pos], rtol=TOL, atol=TOL)


def test_the_engine_serves_the_references_greedy_tokens(params):
    e = engine(params)
    prompts = [[5, 9, 2, 7, 7, 1, 30], [11, 3], list(range(20))]
    reqs = [e.submit(p, max_new_tokens=6) for p in prompts]
    e.run_until_idle()
    for r in reqs:
        row = np.array(r.prompt + r.generated)[None]
        gaps, _, _ = ref.serve_logit_gaps(SEED, DIMS, row, [row.shape[1]],
                                          [len(r.prompt)],
                                          weights_via="float32")
        assert len(gaps[0]) == 6 and float(np.max(gaps[0])) < 1e-4


def _period_loop_by_hand(params, tokens):
    """Every layer in ``layer_types`` order, each kind's stack indexed by
    hand, through ``_block``: what the period scan must equal."""
    h = lm._embed(params, tokens, SPEC)
    seen = {"mamba": 0, "attention": 0}
    core = lm._prefill_core(SPEC, None)
    for kind in DIMS["kinds"]:
        p = jax.tree_util.tree_map(lambda a: a[seen[kind]],
                                   params["blocks"][kind])
        seen[kind] += 1
        attend = (lambda mp, u: ssm.mixer_prefill(mp, u, SPEC,
                                                  tokens.shape[1])) \
            if kind == "mamba" else (lambda q, k, v: (core(q, k, v), None))
        h, _, _ = lm._block(p, h, DIMS["n_heads"], SPEC, attend, None,
                            kind=kind)
    return h


def test_the_period_loop_equals_a_layer_by_layer_loop(params):
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0,
                                DIMS["vocab"])
    h, kept = lm._hybrid_prefill_hidden(params, tokens, DIMS["n_heads"],
                                        None, SPEC, tokens.shape[1])
    np.testing.assert_allclose(h, _period_loop_by_hand(params, tokens),
                               rtol=TOL, atol=TOL)
    assert kept["attention"][0].shape[0] == 2     # k of two attention layers
    assert kept["mamba"][1].shape[:2] == (6, 2)   # state of six, two rows


def _run(e, prompts, n_new=6):
    reqs = [e.submit(list(p), max_new_tokens=n_new) for p in prompts]
    e.run_until_idle()
    return reqs


def _slot_state(e, slot, rows):
    """What a slot holds after its request: conv and recurrent state, and
    the K/V rows it wrote."""
    c = e._cache
    return [c["conv"][:, slot], c["ssm"][:, slot], c["k"][:, slot, :, :rows],
            c["v"][:, slot, :, :rows]]


PROMPT = [4, 8, 15, 16, 23, 42 % DIMS["vocab"]]
# the prompt's rows and every fed-back token's but the last
ROWS = len(PROMPT) + 5


@pytest.mark.parametrize("prompt", [PROMPT, [9]], ids=["six", "one"])
def test_a_readmitted_slot_reads_nothing_of_its_last_request(params, prompt):
    """A request admitted into the slot a longer request used ends with the
    tokens, state and rows it has in a fresh engine; a one-token prompt too,
    which a K/V-only model leaves to the decode tick."""
    fresh = engine(params, n_slots=1)
    (want,) = _run(fresh, [prompt])
    used = engine(params, n_slots=1)
    _run(used, [list(range(3, 24))])
    (got,) = _run(used, [prompt])
    assert got.generated == want.generated
    rows = len(prompt) + 5
    for a, b in zip(_slot_state(used, 0, rows), _slot_state(fresh, 0, rows)):
        np.testing.assert_allclose(a, b, rtol=TOL, atol=TOL)


def test_two_slots_do_not_leak_into_each_other(params):
    """Beside a neighbour that starts and ends with it, a request ends with
    the tokens, state and rows it has alone."""
    alone = engine(params, n_slots=2)
    (want,) = _run(alone, [PROMPT])
    both = engine(params, n_slots=2)
    got, other = _run(both, [PROMPT, [7, 7, 7, 1, 2]])
    assert got.slot is None and got.generated == want.generated
    assert other.generated != got.generated
    for a, b in zip(_slot_state(both, 0, ROWS), _slot_state(alone, 0, ROWS)):
        np.testing.assert_allclose(a, b, rtol=TOL, atol=TOL)


def test_counters_and_the_prefill_phase_name_the_state(params):
    from deeplearning4j_tpu.telemetry import trace

    reg = MetricsRegistry()
    e = engine(params, registry=reg)
    lo = time.perf_counter()
    _run(e, ([1, 2], [3, 4, 5], [6]), n_new=2)
    entries, _ = trace.phases_between(lo, time.perf_counter())
    prefills = [attrs for name, _, _, _, attrs in entries
                if name == "tick.prefill"]
    assert len(prefills) == 3
    assert all(a["ssm_layers"] == 6 for a in prefills)
    assert reg.counter("serve_ssm_state_writes_total").value == 3
    held = sum(e._cache[k].nbytes for k in ("conv", "ssm"))
    assert reg.gauge("serve_ssm_state_bytes").value == held
    assert e._cache["ssm"].dtype == jnp.float32


@pytest.mark.parametrize("option,reason", [
    ({"speculative": {"k": 2, "draft_layers": 1}}, "rolling it back"),
    ({"prefix_cache": True}, "recurrent state at the"),
    ({"prefill_chunk": 4}, "previous chunk left"),
])
def test_fast_paths_that_assume_kv_alone_are_refused(params, option, reason):
    with pytest.raises(ValueError, match=reason):
        engine(params, **option)


def test_block_diffusion_under_a_pattern_is_refused(params):
    spec = SPEC._replace(generation="block_diffusion", attn_mask="block",
                         block_length=4, denoising_steps=2, mask_token_id=1)
    with pytest.raises(ValueError, match="cannot take back"):
        DecodeEngine(params, DIMS["n_heads"], max_len=32, top_k=0,
                     serve_dtype=None, tuned=False, runprof=False, spec=spec)


def test_a_pattern_that_the_depth_does_not_repeat_is_refused():
    with pytest.raises(ValueError, match="whole periods"):
        lm.init_lm_params(jax.random.PRNGKey(0), 16, 32, 4, 0, 8, 6,
                          spec=SPEC)
