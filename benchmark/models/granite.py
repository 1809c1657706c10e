"""Everything the benchmark knows of Granite-4.0-H served whole: the hybrid
layer pattern of ``models/transformer_lm.py`` (Mamba-2 layers of
``models/ssm.py`` beside grouped-query attention, a dense gated FFN a
layer) under a ``BlockSpec``, served by ``serve/engine.py``'s DecodeEngine
on its one-token tick with a recurrent state a slot.

A configuration file names this file under ``model``. It offers what
``PERF.md`` section 3 lists for a model that only serves: ``dims_of``,
``seed_key``, ``block_spec``, ``build_serve``, ``serve_counters``,
``serve_steps``, ``serve_programs``, ``serve_compare``, ``prefill_flops``,
``step_flops``, ``step_bytes``, ``program_scopes`` and ``faults``; and for
the readers of the recurrence alone ``ssm_scan_flops``, ``ssm_scan_bytes``,
``ssm_step_flops`` and ``ssm_step_bytes``. Its reference
(``reference/granite_ref.py``) and its counts (``counts/granite.py``) are
files of their own, found through the registry as this one is.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import registry

ref = registry.load_part("reference", "granite_ref")
counts = registry.load_part("counts", "granite")

seed_key = ref.seed_key
dims_of = ref.dims_of  # the harness itself reads ``vocab`` alone


# -------------------------------------------------------------------- serve ----

def block_spec(dims: dict):
    """The program's spec of this model."""
    from deeplearning4j_tpu.models.transformer_lm import BlockSpec

    return BlockSpec(
        norm="rmsnorm", norm_eps=dims["eps"], final_norm=True,
        n_kv_heads=dims["n_kv_heads"], head_dim=dims["head_dim"],
        ffn="dense_swiglu", bias=False, accum_f32=True,
        layer_pattern=dims["pattern"], ssm_heads=dims["ssm_heads"],
        ssm_head_dim=dims["ssm_head_dim"], ssm_state=dims["ssm_state"],
        ssm_groups=dims["ssm_groups"], ssm_conv=dims["ssm_conv"],
        ssm_chunk=dims["ssm_chunk"],
        embedding_multiplier=dims["embedding_multiplier"],
        residual_multiplier=dims["residual_multiplier"],
        attention_multiplier=dims["attention_multiplier"],
        logits_scaling=dims["logits_scaling"], tied_head=True)


def build_serve(config: dict, seed: int, metrics):
    """The engine on the normal path: weights made on the device in one
    jitted call from the seed (the draws the reference repeats), in the
    type they are served in."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer_lm import init_lm_params
    from deeplearning4j_tpu.serve.engine import DecodeEngine

    d, s = dims_of(config), config["serve"]
    spec = block_spec(d)
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[s["serve_dtype"]]

    def make(key):
        p = init_lm_params(key, d["vocab"], d["d_model"], d["n_heads"], 0,
                           d["d_ff"], d["n_layers"], spec=spec,
                           init_scale=d["embed_scale"])
        return jax.tree_util.tree_map(lambda w: w.astype(dtype), p)

    params = jax.jit(make)(seed_key(seed))
    engine = DecodeEngine(
        params, d["n_heads"], n_slots=s["n_slots"], max_len=s["max_len"],
        top_k=0, serve_dtype=s["serve_dtype"], min_bucket=s["min_bucket"],
        registry=metrics, tuned=False, runprof=False,
        seed=int(seed) & 0x7FFFFFFF, spec=spec)
    _keep_final_states(engine, jnp.asarray(ref.state_probe(d)))
    return engine


def _keep_final_states(engine, probe) -> None:
    """As a request retires, its slot's recurrent state in every Mamba
    layer read along the reference's ``state_probe`` lands on the request
    as ``state_read`` (L_mamba, H, k), a device array left unfetched: one
    dispatch a retirement, 8 numbers a head, before the slot is seeded
    again. The state is the one after the request's last token but one
    (the last is sampled, never fed)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def read(states, slot, probe):
        state = jax.lax.dynamic_index_in_dim(states, slot, 1, keepdims=False)
        return jnp.einsum("lhpn,kpn->lhk", state, probe,
                          precision=jax.lax.Precision.HIGHEST)

    finish = engine._finish

    def finish_and_read(req, reason, now):
        if req.slot is not None:
            req.state_read = read(engine._cache["ssm"], np.int32(req.slot),
                                  probe)
        finish(req, reason, now)

    engine._finish = finish_and_read


def serve_counters(engine, metrics) -> dict:
    """The engine's running totals under the names the readers know, and
    the slots whose state an admission seeded."""
    return {
        "decode_steps": engine.decode_steps,
        "occupancy_sum": engine._occupancy_sum,
        "prefill_dispatches": metrics.counter(
            "serve_prefill_dispatches_total").value,
        "decode_ms_sum": metrics.histogram(
            "serve_decode_step_ms").snapshot()["sum"],
        "prefill_ms_sum": metrics.histogram(
            "serve_prefill_ms").snapshot()["sum"],
        "ssm_state_writes": metrics.counter(
            "serve_ssm_state_writes_total").value,
    }


def serve_steps(requests: list) -> list:
    """``[[stamp, slots], ...]``: every decode step that gave a token to one
    of ``requests``, a slot as ``[position written, tokens accepted]``,
    rebuilt from the token stamps (a step's tokens share its one stamp)."""
    steps = {}
    for r in requests:
        for j, stamp in enumerate(r.t_tokens[1:]):
            steps.setdefault(stamp, []).append([len(r.prompt) + j, 1])
    return [[stamp, slots] for stamp, slots in steps.items()]


def serve_programs(engine, buckets: list) -> list:
    """The compiled text of the decode program and of the prefill program
    at each of ``buckets``: found in the compile cache, the same programs
    the window ran."""
    e = engine
    texts = [e._decode.lower(e.params, e._cache, e._tokens, e._positions,
                             e._temps, e._key, e._step_idx)
             .compile().as_text()]
    for b in buckets:
        texts.append(e._prefill.lower(
            e.params, e._cache, np.zeros((1, b), np.int32), b - 1, 0,
            np.float32(0.0), e._key, e._step_idx).compile().as_text())
    return texts


def serve_compare(config: dict, traffic: dict, seed: int, sampled: list,
                  control_via=None) -> dict:
    """{name: [number, limit]} over the sampled requests as the engine
    left them, teacher-forced through the float32 reference (the
    recurrence a token at a time, the whole causal softmax):

    - how far a served token's logit lies below the reference's best: the
      widest such gap (a wrong token, or a state gone astray, reads a large
      part of the logits' spread) and the mean (the noise of the
      arithmetic, which a lower precision or a state that drifts
      multiplies);
    - ``state_gap``: how far the recurrent state a request left in its
      slot lies from the reference's after the same tokens, as a share of
      the reference's (``state_gaps``), mean over the sampled requests and
      the heads of the first Mamba layer that forget slowest: there the
      activations carry no error from the layers before, and a rounding
      of the state lives longest.

    With ``control_via`` the tokens judged and the states compared are
    those of the reference on weights of that type. No finished request to
    follow reads as a gap no limit admits."""
    limits = config["correct"]
    gaps, states = (_logit_gaps(config, traffic, seed, sampled, control_via)
                    if sampled else ([1e30], None))
    state_gap = 1e30
    if states is not None:
        slow = _slowest_heads(seed, dims_of(config))
        state_gap = float(np.mean(states[:, 0, slow]))
    return {"widest_logit_gap": [float(np.max(gaps)),
                                 float(limits["widest_logit_gap"])],
            "mean_logit_gap": [float(np.mean(gaps)),
                               float(limits["mean_logit_gap"])],
            "state_gap": [state_gap, float(limits["state_gap"])]}


def _logit_gaps(config, traffic, seed, sampled, control_via) -> tuple:
    """(every served position's logit gap, ``state_gaps`` (R, L_mamba, H)
    or None where a request kept no state read). One shape whatever the
    seed drew (``check_requests`` rows as wide as the mix's longest request
    can be), so that the reference compiles once a cell."""
    rows = [list(r.prompt) + list(r.generated) for r in sampled]
    n_rows = max(int(traffic["check_requests"]), len(rows))
    longest = int(traffic["prompt_len"]["max"]) \
        + int(traffic["answer_len"]["max"])
    width = min(-(-longest // 256) * 256, int(config["serve"]["max_len"]))
    tokens = np.zeros((n_rows, width), np.int32)
    lengths = np.ones(n_rows, np.int64)  # a spare row judges nothing
    prompts = np.ones(n_rows, np.int64)
    for i, (x, r) in enumerate(zip(rows, sampled)):
        tokens[i, :len(x)] = x
        lengths[i], prompts[i] = len(x), len(r.prompt)
    gaps, want, judged = ref.serve_logit_gaps(
        seed, dims_of(config), tokens, lengths, prompts,
        weights_via=config["precision"]["weights"], control_via=control_via,
        span=int(traffic["answer_len"]["max"]), keep_at=lengths - 2)
    reads = [getattr(r, "state_read", None) for r in sampled]
    states = None
    if all(v is not None for v in reads):
        got = judged if control_via else np.stack(
            [np.asarray(v) for v in reads])
        states = state_gaps(got[:len(sampled)], want[:len(sampled)])
    return np.concatenate(gaps), states


def state_gaps(got, want) -> np.ndarray:
    """(R, L, H): how far each head's state read ``got`` lies from
    ``want``, both (R, L, H, k), as a share of ``want``'s."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) \
        / np.maximum(np.linalg.norm(want, axis=-1), 1e-30)


def _slowest_heads(seed: int, dims: dict) -> np.ndarray:
    """The quarter of the first Mamba layer's heads whose state decays
    slowest, by ``softplus(dt_bias) * exp(A_log)`` of the seed's weights."""
    first = dims["kinds"].index("mamba")
    rate = np.asarray(ref.decay_rates(seed, dims, first))
    return np.argsort(rate, kind="stable")[:max(1, len(rate) // 4)]


# ------------------------------------------------------------ required work ----

prefill_flops = counts.prefill_flops


def step_flops(dims: dict, slots: list) -> float:
    """Required operations of one decode step from its own record
    (``serve_steps``): one token a live slot, at the position it wrote."""
    return counts.decode_flops(dims, [p for p, _ in slots])


def step_bytes(dims: dict, slots: list) -> float:
    return counts.decode_step_bytes(dims, [p for p, _ in slots])


ssm_scan_flops = counts.scan_flops
ssm_scan_bytes = counts.scan_bytes


def ssm_step_flops(dims: dict, slots: list) -> float:
    return counts.state_step_flops(dims, [p for p, _ in slots])


def ssm_step_bytes(dims: dict, slots: list) -> float:
    return counts.state_step_bytes(dims, [p for p, _ in slots])


def program_scopes():
    """The scopes this model's programs name in their compiled text."""
    from deeplearning4j_tpu.models.transformer_lm import LM_HYBRID_SCOPES

    return ("lm_embed", "lm_attn", "lm_cache_write", "lm_sample") \
        + LM_HYBRID_SCOPES


# ------------------------------------------------------------------- faults ----

def faults(cell: dict) -> dict:
    """{fault: ``tamper(driver)``}: planted under the timed path, in the
    engine's programs or weights once the driver has built them."""
    return {"alter_a_token": _alter_a_token,
            "padding_in_state": _padding_in_state,
            "conv_state_dropped": _conv_state_dropped,
            "state_in_bfloat16": _state_in_bfloat16,
            "float8_weights": _float8_weights}


def _alter_a_token(driver) -> None:
    """Every third decode step hands back the next id for every slot."""
    decode, calls = driver.engine._decode, [0]
    vocab = driver.dims["vocab"]

    def broken(*args):
        cache, toks = decode(*args)
        calls[0] += 1
        if calls[0] % 3 == 0:
            toks = (toks + 1) % vocab
        return cache, toks

    driver.engine._decode = broken


def _padding_in_state(driver) -> None:
    """The prompt pass's scan runs through its bucket's padding: dt and x
    are not zeroed past the prompt's length, so the state the slot keeps
    has taken in every padded row. A prefill program of its own, traced
    with the mixer's choice of real rows taken to be every row."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import ssm
    from deeplearning4j_tpu.models.transformer_lm import make_prefill_step
    from deeplearning4j_tpu.serve.quant import dequantize_tree

    e = driver.engine
    fresh = make_prefill_step(e.n_heads, e.top_k,
                              params_transform=dequantize_tree,
                              spec=e.block_spec)
    real_rows = ssm._real_rows

    def broken(*args):
        ssm._real_rows = lambda lengths, t: jnp.ones(
            (lengths.shape[0], t), bool)
        try:
            return fresh(*args)
        finally:
            ssm._real_rows = real_rows

    e._prefill = broken


def _conv_state_dropped(driver) -> None:
    """Admission writes the slot's K/V and recurrent state but leaves the
    conv state the slot held before (a retired request's, or zeros)."""
    e = driver.engine
    prefill = e._prefill

    def broken(params, cache, tokens, last_idx, slot, *rest):
        before = cache["conv"][:, slot]
        cache, tok = prefill(params, cache, tokens, last_idx, slot, *rest)
        return dict(cache, conv=cache["conv"].at[:, slot].set(before)), tok

    e._prefill = broken


def _state_in_bfloat16(driver) -> None:
    """The recurrent state kept and updated in bfloat16, as an engine
    without a float32 state cache would: the prompt pass's state rounded
    through it when stored, and every decode step's update computed in it
    (a decode program of its own, traced with the recurrence's one-row
    update rounding its state, decay and input)."""
    from functools import partial

    import jax

    from deeplearning4j_tpu.models import ssm
    from deeplearning4j_tpu.models.transformer_lm import make_decode_step
    from deeplearning4j_tpu.serve.quant import dequantize_tree

    e = driver.engine
    fresh = make_decode_step(e.n_heads, e.top_k,
                             params_transform=dequantize_tree,
                             spec=e.block_spec)
    prefill, advance = e._prefill, ssm._advance
    # rounded with reduce_precision: a round trip through astype is one the
    # compiler may drop where it allows excess precision (the v5e's did)
    bf16 = partial(jax.lax.reduce_precision, exponent_bits=8,
                   mantissa_bits=7)

    def in_bf16(state, decay, update):
        return bf16(bf16(bf16(state) * bf16(decay)) + bf16(update))

    def decode(*args):
        ssm._advance = in_bf16
        try:
            return fresh(*args)
        finally:
            ssm._advance = advance

    # in place: a second float32 state does not fit beside the first
    through_bf16 = jax.jit(bf16, donate_argnums=0)

    def stored(*args):
        cache, tok = prefill(*args)
        return dict(cache, ssm=through_bf16(cache["ssm"])), tok

    e._decode, e._prefill = decode, stored


def _float8_weights(driver) -> None:
    """The served weights rounded to float8_e4m3fn's 3 mantissa and 4
    exponent bits (``reduce_precision``, as ``_state_in_bfloat16`` says
    why), in place: a second copy of the weights does not fit beside the
    first."""
    import jax

    e = driver.engine
    e.params = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda w: jax.lax.reduce_precision(w, exponent_bits=4,
                                           mantissa_bits=3), p),
        donate_argnums=0)(e.params)
