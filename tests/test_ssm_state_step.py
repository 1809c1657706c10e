"""The Mamba-2 cached step's state kernel (``ssm._state_step``) on the CPU
through the Pallas interpreter, against the XLA form it replaces: the read
of the layer's state, ``_advance``, the product with C at ``highest`` and
the write back in place."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.harness import registry  # noqa: E402
from deeplearning4j_tpu.models import ssm  # noqa: E402
from deeplearning4j_tpu.models import transformer_lm as lm  # noqa: E402
from deeplearning4j_tpu.ops import pallas_kernels  # noqa: E402

# a float32 state: the kernel sums over N in another order than the einsum
TOL = 1e-5
LAYERS = 3


def reference(cache, layer, decay, dtx, b, c):
    """The XLA form: the layer's state read whole, advanced, its product
    with C, and written back."""
    _, s, h, P, N = cache.shape
    g = b.shape[1]
    state = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    state = state.reshape(s, g, h // g, P, N)
    state = ssm._advance(state, decay.reshape(s, g, -1)[..., None, None],
                         dtx.reshape(s, g, -1, P)[..., None]
                         * b[:, :, None, None, :])
    y = jnp.einsum("sgrpn,sgn->sgrp", state, c,
                   precision=jax.lax.Precision.HIGHEST)
    return (ssm._write_layer(cache, state.reshape(s, h, P, N), layer),
            y.reshape(s, h, P))


def inputs(slots, heads, groups, P=8, N=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    cache = jax.random.normal(ks[0], (LAYERS, slots, heads, P, N))
    decay = jax.random.uniform(ks[1], (slots, heads), minval=0.5, maxval=1.0)
    dtx = jax.random.normal(ks[2], (slots, heads, P))
    b = jax.random.normal(ks[3], (slots, groups, N))
    c = jax.random.normal(ks[4], (slots, groups, N))
    return cache, decay, dtx, b, c


kernel = jax.jit(ssm._state_step)

# (slots, heads, groups, P, N): one block of 8 heads; several slots, two
# blocks of 64 a slot; two groups of two blocks; four heads; six heads a
# group over 12 by 64 (the cell's rehearsal state); the hybrid tests' widths
SHAPES = [(1, 8, 1, 8, 128), (3, 128, 1, 8, 128), (2, 256, 2, 8, 128),
          (2, 4, 1, 8, 128), (2, 12, 2, 12, 64), (1, 6, 3, 4, 6)]


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1],
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize("slots,heads,groups,P,N", SHAPES)
def test_the_kernel_is_the_xla_form(layer, slots, heads, groups, P, N):
    cache, decay, dtx, b, c = inputs(slots, heads, groups, P, N, seed=layer)
    at = jnp.int32(layer)
    got_cache, got_y = kernel(cache, at, decay, dtx, b, c)
    want_cache, want_y = reference(cache, at, decay, dtx, b, c)
    assert got_cache.dtype == jnp.float32
    np.testing.assert_allclose(got_cache[layer], want_cache[layer], rtol=TOL,
                               atol=TOL)
    np.testing.assert_allclose(got_y, want_y, rtol=TOL, atol=TOL)
    # every other layer of the leaf as it was, bit for bit
    others = [i for i in range(LAYERS) if i != layer]
    assert np.array_equal(np.asarray(got_cache)[others],
                          np.asarray(cache)[others])


def test_the_kernel_advances_through_the_modules_advance(monkeypatch):
    """A rounding put in ``ssm._advance`` reaches the kernel's state, as
    the benchmark's bfloat16-state fault plants itself."""
    cache, decay, dtx, b, c = inputs(2, 16, 1)
    # a new function each time, so that jit traces the module's _advance anew
    step = lambda: jax.jit(lambda *a: ssm._state_step(*a))(  # noqa: E731
        cache, jnp.int32(1), decay, dtx, b, c)
    exact, _ = step()
    monkeypatch.setattr(ssm, "_advance", lambda s, d, u: jax.lax.
                        reduce_precision(s * d + u, exponent_bits=8,
                                         mantissa_bits=7))
    rounded, _ = step()
    assert not np.array_equal(np.asarray(rounded[1]), np.asarray(exact[1]))
    np.testing.assert_allclose(rounded[1], exact[1], rtol=1e-2, atol=1e-2)


granite = registry.load_part("models", "granite")
ref = registry.load_part("reference", "granite_ref")
# Granite's keys at a tiny size, 128 lanes of N as in Granite's state
CONFIG = {
    "vocab_size": 48, "hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "shared_intermediate_size": 24,
    "num_hidden_layers": 4, "layer_types": ["mamba", "mamba", "attention",
                                            "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 128,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 4,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.125, "logits_scaling": 8, "rms_norm_eps": 1e-5,
    "weights": {"embed_scale": 0.02},
}


def test_the_engine_serves_the_references_tokens_through_the_kernel():
    """Prompt pass, then decode steps whose state kernel runs interpreted:
    each served token's logit against the float32 reference's."""
    from deeplearning4j_tpu.serve.engine import DecodeEngine

    dims = ref.dims_of(CONFIG)
    spec = granite.block_spec(dims)
    seed = 2**31 + 11
    params = lm.init_lm_params(ref.seed_key(seed), dims["vocab"],
                               dims["d_model"], dims["n_heads"], 0,
                               dims["d_ff"], dims["n_layers"], spec=spec,
                               init_scale=dims["embed_scale"])
    e = DecodeEngine(params, dims["n_heads"], max_len=32, top_k=0,
                     n_slots=2, serve_dtype=None, min_bucket=8, tuned=False,
                     runprof=False, spec=spec)
    reqs = [e.submit(p, max_new_tokens=5) for p in ([5, 9, 2, 7], [11, 3])]
    e.run_until_idle()
    for r in reqs:
        row = np.array(r.prompt + r.generated)[None]
        gaps, _, _ = ref.serve_logit_gaps(seed, dims, row, [row.shape[1]],
                                          [len(r.prompt)],
                                          weights_via="float32")
        assert len(gaps[0]) == 5 and float(np.max(gaps[0])) < 1e-4


def _decode_text(monkeypatch, state) -> str:
    """The tiny decode step with a state of ``state`` lanes lowered for the
    TPU, its kernel through Mosaic (lowered, never run), with locations."""
    dims = ref.dims_of(dict(CONFIG, mamba_d_state=state))
    spec = granite.block_spec(dims)
    params = jax.eval_shape(lambda k: lm.init_lm_params(
        k, dims["vocab"], dims["d_model"], dims["n_heads"], 0, dims["d_ff"],
        dims["n_layers"], spec=spec), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_hybrid_cache(
        spec, dims["n_layers"], 2, dims["n_kv_heads"], dims["head_dim"], 16))
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    step = lm.make_decode_step(dims["n_heads"], 0, spec=spec)
    slots = np.zeros((2,), np.int32)
    return step.trace(params, cache, slots, slots, np.zeros((2,), np.float32),
                      jax.random.PRNGKey(0), 0).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


# Granite's 128 lanes, and the cell's rehearsal width of 64, which Mosaic
# takes as a whole dimension of the block
@pytest.mark.parametrize("state", [128, 64])
def test_the_decode_step_reads_the_state_in_the_kernel_alone(monkeypatch,
                                                            state):
    text = _decode_text(monkeypatch, state)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))

    def scopes(op):
        return [names.get(m, "") for line in text.splitlines()
                if re.search(rf"\b{op}\b", line)
                for m in re.findall(r"loc\((#loc\d+)\)", line)]

    calls = scopes("tpu_custom_call")
    assert calls and all("lm_ssm/ssm_step" in s for s in calls), calls
    assert not [s for s in scopes("stablehlo.dot_general") if "ssm_step" in s]
