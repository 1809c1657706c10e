"""Everything the benchmark knows of the SDAR block served by block
diffusion: ``models/transformer_lm.py``'s block functions under a
``BlockSpec``, generated by ``serve/engine.py``'s DecodeEngine in its
block-diffusion tick.

A configuration file names this file under ``model``. It offers what
``PERF.md`` section 3 lists for a model that only serves: ``dims_of``,
``seed_key``, ``build_serve``, ``serve_counters``, ``serve_steps``,
``serve_programs``, ``serve_compare``, ``prefill_flops``, ``step_flops``,
``step_bytes``, ``program_scopes`` and ``faults``. Its reference
(``reference/sdar_ref.py``) and its counts (``counts/sdar.py``) are files of
their own, found through the registry as this one is.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import registry

ref = registry.load_part("reference", "sdar_ref")
counts = registry.load_part("counts", "sdar")

seed_key = ref.seed_key
dims_of = ref.dims_of  # the harness itself reads ``vocab`` alone


# -------------------------------------------------------------------- serve ----

def block_spec(dims: dict):
    """The program's spec of this block: every switch on."""
    from deeplearning4j_tpu.models.transformer_lm import BlockSpec

    return BlockSpec(
        norm="rmsnorm", norm_eps=dims["eps"], final_norm=True,
        rope_theta=dims["rope_theta"], qk_norm=True,
        n_kv_heads=dims["n_kv_heads"], head_dim=dims["head_dim"],
        ffn="swiglu", norm_topk_prob=dims["norm_topk_prob"], bias=False,
        accum_f32=True, attn_mask="block",
        block_length=dims["block_length"], generation="block_diffusion",
        denoising_steps=dims["denoising_steps"],
        mask_token_id=dims["mask_token_id"])


def build_serve(config: dict, seed: int, metrics):
    """The engine on the normal path: weights made on the device in one
    jitted call from the seed (the draws the reference repeats), in the type
    they are served in. ``metrics`` is the ``MetricsRegistry`` the engine
    counts into."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer_lm import init_lm_params
    from deeplearning4j_tpu.serve.engine import DecodeEngine

    d, s = dims_of(config), config["serve"]
    spec = block_spec(d)
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[s["serve_dtype"]]

    def make(key):
        p = init_lm_params(key, d["vocab"], d["d_model"], d["n_heads"],
                           d["n_experts"], d["d_ff"], d["n_layers"],
                           spec=spec, init_scale=d["init_scale"])
        return jax.tree_util.tree_map(lambda w: w.astype(dtype), p)

    params = jax.jit(make)(seed_key(seed))
    return DecodeEngine(
        params, d["n_heads"], n_slots=s["n_slots"], max_len=s["max_len"],
        top_k=d["top_k"], serve_dtype=s["serve_dtype"],
        min_bucket=s["min_bucket"], registry=metrics, tuned=False,
        runprof=False, seed=int(seed) & 0x7FFFFFFF, spec=spec)


def serve_counters(engine, metrics) -> dict:
    """The engine's running totals: block steps and live slots under the
    names the readers know, and beside them the slot-forwards and the
    positions that left the mask."""
    def count(name, labels=None):
        return metrics.counter(name, labels).value

    return {
        "decode_steps": engine.decode_steps,
        "occupancy_sum": engine._occupancy_sum,
        "prefill_dispatches": count("serve_prefill_dispatches_total"),
        "decode_ms_sum": metrics.histogram(
            "serve_decode_step_ms").snapshot()["sum"],
        "prefill_ms_sum": metrics.histogram(
            "serve_prefill_ms").snapshot()["sum"],
        "block_forwards": sum(
            count("serve_block_forwards_total", {"kind": kind})
            for kind in ("denoise", "commit")),
        "tokens_accepted": count("serve_block_tokens_accepted_total"),
    }


def serve_steps(requests: list) -> list:
    """``[[stamp, slots], ...]``: every block step that ran a forward for
    one of ``requests``, from the records the engine appends to a request
    (``ServeRequest.forwards``), a slot as ``[first row, positions
    forwarded, tokens accepted, kind]``. The forwards of one step share its
    one stamp."""
    steps = {}
    for r in requests:
        for stamp, block, kind, _, took, tokens in r.forwards:
            steps.setdefault(stamp, []).append(
                [block * len(tokens), len(tokens), len(took), kind])
    return [[stamp, slots] for stamp, slots in steps.items()]


def serve_programs(engine, buckets: list) -> list:
    """The compiled text of ``jit_block_step`` and of ``jit_prefill`` at
    each of ``buckets``: found in the compile cache, the same programs the
    window ran."""
    e = engine
    texts = [e._block_step.lower(
        e.params, e._cache, e._block_tokens, e._positions, e._block_masked,
        e._temps, e._key, e._step_idx).compile().as_text()]
    for b in buckets:
        texts.append(e._prefill.lower(
            e.params, e._cache, np.zeros((1, b), np.int32), b - 1, 0,
            np.float32(0.0), e._key, e._step_idx).compile().as_text())
    return texts


def replay_widths(traffic: dict, dims: dict) -> tuple:
    """One shape whatever the seed drew, so that the reference compiles
    once a cell: room for the mix's longest request's clean blocks and for
    every denoising forward of its longest answer."""
    width = dims["block_length"]
    per_block = -(-width // max(1, width // dims["denoising_steps"]))
    longest = int(traffic["prompt_len"]["max"]) \
        + int(traffic["answer_len"]["max"])
    blocks = int(traffic["answer_len"]["max"]) // width + 2
    return (longest // width + 2) * width, blocks * per_block * width


def serve_compare(config: dict, traffic: dict, seed: int, sampled: list,
                  control_via=None) -> dict:
    """{name: [number, limit]} over the sampled requests as the engine left
    them, every denoising forward replayed in the float32 reference from its
    recorded state (``ServeRequest.forwards``):

    - ``widest_logit_gap``, ``mean_logit_gap``: how far an accepted token's
      logit lies under the reference's best at that position in that state
      (a wrong token reads several units; the mean is the noise of the
      arithmetic, which a lower precision multiplies). With ``control_via``
      the tokens judged are those the reference on weights of that type
      puts first;
    - ``widest_confidence_gap``: in the reference's log-probabilities, how
      far a position the program unmasked lies under the best masked
      position it left (unmasking in another order than by confidence
      reads the spread of confidences across a block);
    - ``schedule_faults``: records that break the configuration's schedule
      (``reference/sdar_ref.py`` ``schedule_faults``), limit 0.

    No finished request to follow reads as a gap no limit admits."""
    limits = config["correct"]
    dims = dims_of(config)
    logit_gaps, conf_gaps, faults = [1e30], [1e30], 0
    if sampled:
        # a record without its stamp is what the reference's loop makes
        requests = [(r.prompt, [f[1:] for f in r.forwards]) for r in sampled]
        clean_width, noisy_width = replay_widths(traffic, dims)
        rows = ref.replay(seed, dims, requests,
                          config["precision"]["weights"], control_via,
                          clean_width=clean_width, noisy_width=noisy_width)
        logit_gaps, conf_gaps = [], [0.0]
        for per_request in rows:
            for before, took, best, at, conf in per_request:
                left = [i for i, m in enumerate(before)
                        if m and i not in took]
                logit_gaps += [float(best[i] - at[i]) for i in took]
                if left and took:
                    conf_gaps.append(float(max(conf[i] for i in left)
                                           - min(conf[i] for i in took)))
        faults = sum(ref.schedule_faults(
            prompt, records, len(r.generated), r.max_new_tokens, dims,
            r.eos_id) for r, (prompt, records) in zip(sampled, requests))
    return {"widest_logit_gap": [float(np.max(logit_gaps)),
                                 float(limits["widest_logit_gap"])],
            "mean_logit_gap": [float(np.mean(logit_gaps)),
                               float(limits["mean_logit_gap"])],
            "widest_confidence_gap": [float(np.max(conf_gaps)),
                                      float(limits["widest_confidence_gap"])],
            "schedule_faults": [float(faults),
                                float(limits["schedule_faults"])]}


# ------------------------------------------------------------ required work ----

prefill_flops = counts.prefill_flops
step_flops = counts.step_flops
step_bytes = counts.step_bytes


def program_scopes():
    """The scopes this model's programs name in their compiled text."""
    from deeplearning4j_tpu.models.transformer_lm import (
        LM_SCOPES,
        LM_SPEC_SCOPES,
    )

    return LM_SCOPES + LM_SPEC_SCOPES


# ------------------------------------------------------------------- faults ----

def faults(cell: dict) -> dict:
    """{fault: ``tamper(driver)``}: planted under the timed path, in the
    engine's ``jit_block_step`` (``driver.engine._block_step``)."""
    return {"alter_a_token": _alter_a_token,
            "commit_skipped": _commit_skipped,
            "unmask_left_to_right": _left_to_right}


def _alter_a_token(driver) -> None:
    """Every third block step hands back the next id at every position it
    unmasked."""
    step, calls = driver.engine._block_step, [0]
    vocab = driver.dims["vocab"]

    def broken(params, cache, tokens, starts, masked, *rest):
        cache, toks, left = step(params, cache, tokens, starts, masked, *rest)
        calls[0] += 1
        if calls[0] % 3 == 0:
            took = np.asarray(masked) & ~np.asarray(left)
            toks = np.where(took, (np.asarray(toks) + 1) % vocab, toks)
        return cache, toks, left

    driver.engine._block_step = broken


def _commit_skipped(driver) -> None:
    """Every third commit forward dropped: its rows go to the page's spare
    end, so the block keeps the provisional rows its last denoising forward
    left and later blocks read those."""
    engine, commits = driver.engine, [0]
    step = engine._block_step
    spare = engine.max_len - engine.block_spec.block_length

    def broken(params, cache, tokens, starts, masked, *rest):
        starts = np.array(starts)
        for slot in np.flatnonzero(~np.asarray(masked).any(axis=1)):
            if engine._slots[slot] is not None:
                commits[0] += 1
                if commits[0] % 3 == 0:
                    starts[slot] = spare
        return step(params, cache, tokens, starts, masked, *rest)

    engine._block_step = broken


def _left_to_right(driver) -> None:
    """The control of the schedule's order: the step's own forward, but the
    leftmost ``B // D`` masked positions leave the mask, not the most
    confident. Everything else of the schedule holds, so only
    ``widest_confidence_gap`` can tell."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer_lm import lm_block_step

    engine = driver.engine
    spec = engine.block_spec
    n = max(1, spec.block_length // spec.denoising_steps)

    def step(params, cache, tokens, starts, masked, temps, key, step_idx):
        cache, logits = lm_block_step(params, cache, tokens, starts, masked,
                                      engine.n_heads, engine.top_k, spec)
        take = masked & (jnp.cumsum(masked, axis=1) <= n)
        toks = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
        return cache, jnp.where(take, toks, tokens), masked & ~take

    engine._block_step = jax.jit(step, donate_argnums=(1,))
