"""Benchmarks: MNIST MLP + LeNet + wide-conv + char-LSTM + Word2Vec
(BASELINE configs #1/#2/#4 plus MXU-fill diagnostics) + the composed
transformer-LM flagship (lm_composed: multi-block, blockwise flash core via
the DL4J_TPU_ATTN_IMPL seam, with forced-dense and forced-CPU twins).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

- value: steady-state bf16 training samples/sec/chip for the MLP on the
  default platform (the real TPU chip under the driver).
- vs_baseline: ratio vs the same fp32 training step measured in a CPU
  subprocess — the stand-in for the reference's nd4j-native CPU backend
  (the reference publishes no numbers, BASELINE.md; its jblas CPU path is
  the comparison point named in BASELINE.json's north star, target >=5x).
- detail: per-precision throughput and MFU for each model, plus word2vec
  words/sec on TPU and CPU.

Precision honesty (round 4): on TPU v5e XLA's DEFAULT matmul precision
executes float32-input matmuls as a SINGLE bf16 MXU pass — measured on this
chip with tools/probe_matmul_precision.py (4096^3 matmul): bf16 185.7 TF/s,
fp32-DEFAULT 153.5 TF/s, fp32-HIGH 59.5 (bf16x3), fp32-HIGHEST 29.7 (bf16x6).
So the former "fp32" stage was never true fp32 — that is why round 3 saw
bf16 <= "fp32". Stages are now labeled by what actually runs:

  *_bf16      bf16 operands, 1 MXU pass          MFU vs 197 TF/s
  *_fp32      fp32 operands, DEFAULT precision   MFU vs 197 TF/s
              (1 bf16 MXU pass; extra HBM traffic only)
  *_fp32_true fp32 operands, HIGHEST precision   MFU vs 197/6 TF/s
              (bf16x6 passes ~ true fp32 accuracy)

Each precision's MFU is computed against ITS OWN achievable peak (fixes the
round-3 bench dividing everything by the bf16 peak).

Round-3 structure (fixes the round-2 rc=124 timeout): every stage runs in
its OWN subprocess with a hard timeout under a global deadline
(BENCH_BUDGET_SEC; default = sum of per-stage caps + 60 so no stage is
budget-starved by default), so one wedged compile can never forfeit the
whole bench. Stage results are flushed incrementally to bench_partial.json;
the summary line is printed even when later stages are skipped (marked
"skipped_budget") and the CPU baseline failure is loud (error text lands in
detail + stderr), never a silent 0.0.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import subprocess
import sys
import time

BATCH = 512
WARMUP_CHUNKS = 2
# steps fused into ONE scan program per dispatch: 20-step chunks were
# dispatch-bound (round-2 instability); 200 steps amortize the dispatch.
# Dispatch cost on a local chip: not measured.
CHUNK = 200
HID1, HID2 = 500, 300

REPO = os.path.dirname(os.path.abspath(__file__))
PARTIAL_PATH = os.path.join(REPO, "bench_partial.json")

# TPU v5e (v5 lite) peak bf16 matmul throughput per chip. fp32-DEFAULT runs
# the same single-bf16-pass MXU path (see module docstring measurements);
# HIGHEST precision is 6 chained bf16 passes, so its achievable peak is /6.
PEAK_BF16_FLOPS = 197e12
PRECISION_PEAKS = {
    "bf16": PEAK_BF16_FLOPS,
    "fp32": PEAK_BF16_FLOPS,          # 1 bf16 MXU pass (DEFAULT precision)
    "fp32_true": PEAK_BF16_FLOPS / 6,  # bf16x6 (HIGHEST precision)
}

# Analytic model FLOPs per training sample (fwd matmul/conv FLOPs x3 for
# fwd + both backward matmuls; elementwise ops are bandwidth, not FLOP,
# bound and excluded — standard MFU accounting).
#
# ISSUE 9: the tables are PARAMETRIC formulas (``MODEL_FLOPS``), not baked
# constants — tests/test_xprofile.py cross-checks every formula against the
# XLA ``cost_analysis()`` FLOPs of the exact compiled train step (via
# telemetry/xprofile.py) at CPU-sized shapes, so a model edit that changes
# the FLOP content without updating the formula fails tier-1 instead of
# silently rotting the MFU numbers. ``TRAIN_FLOPS`` below evaluates the
# same formulas at the registered bench shapes.

LSTM_VOCAB = 128
LSTM_SEQ = 64
# WIDE char-LSTM (round 5): hidden 512 = 4 MXU tiles per gate — shows what
# the scan+pallas path does when shapes fill the unit (the 128-hidden stage
# is exactly one tile, VERDICT r04 weak #3). The *_nokernels twin runs the
# IDENTICAL stage with the pallas fused-gate + fused-dense kernels forced
# off, so the kernels' contribution is a measured delta, not a claim.
LSTM_WIDE_HID = 512
ATTN_VOCAB, ATTN_D, ATTN_SEQ = 128, 256, 64
# LONG-context causal LM (round-5 flagship): T=2048, d_model=512, 4 heads
# (head_dim 128 = one MXU lane tile). Same analytic form as the short stage.
ATTN_LONG_VOCAB, ATTN_LONG_D, ATTN_LONG_SEQ, ATTN_LONG_HEADS = 128, 512, 2048, 4
# COMPOSED-flagship LM (round 6): the multi-block transformer LM
# (models/transformer_lm.py) trained END TO END on one chip, attention core
# selected through the DL4J_TPU_ATTN_IMPL env seam.
LMC_VOCAB, LMC_D, LMC_HEADS, LMC_EXPERTS, LMC_DFF = 2048, 512, 4, 4, 1024
LMC_LAYERS, LMC_SEQ, LMC_BATCH = 2, 2048, 4


def mlp_fwd_flops(hid1: int = HID1, hid2: int = HID2) -> int:
    return 2 * (784 * hid1 + hid1 * hid2 + hid2 * 10)


def lenet_fwd_flops() -> int:
    """conv1 24^2x6x(5^2x1), conv2 8^2x16x(5^2x6), dense 256x120, 120x84,
    84x10 (fixed architecture — models/zoo.lenet takes no shape knobs)."""
    return 2 * (24 * 24 * 6 * 25 + 8 * 8 * 16 * 150
                + 256 * 120 + 120 * 84 + 84 * 10)


def conv_wide_fwd_flops() -> int:
    """conv_wide (models/zoo.py): conv1 28^2x128x(5^2x32), conv2
    10^2x128x(5^2x128), dense 3200x256, 256x10 — contractions 800/3200
    wide, 128 output channels (fixed architecture)."""
    return 2 * (28 * 28 * 128 * (25 * 32) + 10 * 10 * 128 * (25 * 128)
                + 3200 * 256 + 256 * 10)


def lstm_fwd_flops(hidden: int = LSTM_VOCAB, seq: int = LSTM_SEQ) -> int:
    """char-LSTM (hidden = vocab): per timestep the fused-gate matmul
    (1 + vocab + hidden) x 4*hidden plus the decoder hidden x vocab."""
    return seq * 2 * ((1 + hidden + hidden) * 4 * hidden + hidden * hidden)


def attn_fwd_flops(vocab: int = ATTN_VOCAB, d: int = ATTN_D,
                   seq: int = ATTN_SEQ) -> int:
    """causal attention char-LM (models/zoo.py char_attention_lm): per
    sample the embedding + qkv/out projections + decoder (matmul term) and
    the T^2 d score/value einsums (attention term).

    NOTE on accounting: the 4·T²·d attention term counts the FULL score
    rectangle; the blockwise core actually executes only the causal half
    (static block skip), and its flash-style backward recomputes block
    scores (7 attention matmuls vs the 4 the ×3 train factor assumes) —
    the two conventions roughly cancel, and this matches the r04 attn
    stage. ``attn_long`` evaluates the SAME formula at its shapes."""
    return 2 * seq * (2 * vocab * d + 4 * d * d) + 4 * seq * seq * d


def lmc_fwd_flops(vocab: int = LMC_VOCAB, d: int = LMC_D,
                  experts: int = LMC_EXPERTS, dff: int = LMC_DFF,
                  layers: int = LMC_LAYERS, seq: int = LMC_SEQ) -> int:
    """Composed-flagship LM FLOPs per sample: per layer the q/k/v/o
    projections, the FULL T² score rectangle (same convention as
    ``attn_fwd_flops`` — the blockwise core executes only the causal half
    but its backward recomputes block scores, the two roughly cancel),
    the router matmul, and dense_moe which runs ALL E experts on every
    token (that is what executes on one chip — the expert-parallel
    capacity path needs the mesh); plus the vocab decoder."""
    return layers * (
        2 * seq * 4 * d * d
        + 4 * seq * seq * d
        + 2 * seq * d * experts
        + experts * 2 * seq * 2 * d * dff
    ) + 2 * seq * d * vocab


def lmc_xla_flops_expectation(vocab: int, d: int, experts: int, dff: int,
                              seq: int, batch: int) -> int:
    """What XLA ``cost_analysis()`` should report for the compiled
    composed-LM TRAIN step: the layer stack runs as a ``lax.scan`` whose
    body XLA's cost model counts ONCE regardless of trip count (the
    convention documented in telemetry/xprofile.py and pinned in
    tests/test_xprofile.py), so the expectation is 3× the SINGLE-layer
    forward formula — independent of n_layers — times the batch. The MFU
    tables (``TRAIN_FLOPS``) still use the true per-sample count; the
    profile blobs record both numbers so the ratio is interpretable."""
    return 3 * lmc_fwd_flops(vocab, d, experts, dff, 1, seq) * batch


# model → parametric fwd-FLOPs formula (the cross-check surface; stage
# "conv_wide_*" → model "conv", lstm_wide/attn_long share their family's
# formula at different shapes)
MODEL_FLOPS = {
    "mlp": mlp_fwd_flops,
    "lenet": lenet_fwd_flops,
    "conv": conv_wide_fwd_flops,
    "lstm": lstm_fwd_flops,
    "lstm_wide": lstm_fwd_flops,
    "attn": attn_fwd_flops,
    "attn_long": attn_fwd_flops,
    "lm_composed": lmc_fwd_flops,
}
TRAIN_FLOPS = {
    "mlp": 3 * mlp_fwd_flops(),
    "lenet": 3 * lenet_fwd_flops(),
    "conv": 3 * conv_wide_fwd_flops(),
    "lstm": 3 * lstm_fwd_flops(),
    "lstm_wide": 3 * lstm_fwd_flops(LSTM_WIDE_HID),
    "attn": 3 * attn_fwd_flops(),
    "attn_long": 3 * attn_fwd_flops(ATTN_LONG_VOCAB, ATTN_LONG_D,
                                    ATTN_LONG_SEQ),
    "lm_composed": 3 * lmc_fwd_flops(),
}

# Per-model batch/chunk: the wide conv's im2col buffers and the LSTM's
# one-hot sequences are far bigger per sample than the MLP's 784 floats.
MODEL_BATCH = {"mlp": BATCH, "lenet": BATCH, "conv": 64, "lstm": 256,
               "lstm_wide": 64, "attn": 256, "attn_long": 4}
MODEL_CHUNK = {"mlp": CHUNK, "lenet": CHUNK, "conv": 32, "lstm": 16,
               "lstm_wide": 8, "attn": 16, "attn_long": 4}


def _time_of(fn) -> float:
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def _conf(model: str):
    from deeplearning4j_tpu.models.zoo import (
        char_attention_lm,
        char_lstm,
        conv_wide,
        lenet,
        mnist_mlp,
    )

    if model == "mlp":
        return mnist_mlp(HID1, HID2)
    if model == "lenet":
        return lenet()
    if model == "conv":
        return conv_wide()
    if model == "lstm":
        return char_lstm(vocab=LSTM_VOCAB)
    if model == "lstm_wide":
        return char_lstm(vocab=LSTM_WIDE_HID)
    if model == "attn":
        return char_attention_lm(vocab=ATTN_VOCAB, d_model=ATTN_D,
                                 n_heads=8, num_iterations=1)
    if model == "attn_long":
        return char_attention_lm(vocab=ATTN_LONG_VOCAB, d_model=ATTN_LONG_D,
                                 n_heads=ATTN_LONG_HEADS, num_iterations=1)
    raise ValueError(model)


def _make_data(model: str, chunk: int, batch: int):
    """(xs, ys) shaped (chunk, batch, ...) for one scan dispatch."""
    import jax
    import jax.numpy as jnp

    if model in ("mlp", "lenet"):
        from deeplearning4j_tpu.datasets.fetchers import synthetic_mnist

        xs_np, ys_np = synthetic_mnist(batch * chunk)
        xs = jnp.asarray(xs_np).reshape(chunk, batch, -1)
        ys = jax.nn.one_hot(jnp.asarray(ys_np), 10, dtype=jnp.float32).reshape(
            chunk, batch, -1
        )
        return xs, ys
    if model == "conv":
        xs = jax.random.normal(
            jax.random.PRNGKey(2), (chunk, batch, 32, 32, 32), jnp.float32
        )
        ys = jax.nn.one_hot(
            jax.random.randint(jax.random.PRNGKey(3), (chunk, batch), 0, 10),
            10, dtype=jnp.float32,
        )
        return xs, ys
    if model in ("lstm", "lstm_wide"):
        vocab = LSTM_VOCAB if model == "lstm" else LSTM_WIDE_HID
        toks = jax.random.randint(
            jax.random.PRNGKey(2), (chunk, batch, LSTM_SEQ + 1), 0, vocab
        )
        xs = jax.nn.one_hot(toks[..., :-1], vocab, dtype=jnp.float32)
        ys = jax.nn.one_hot(toks[..., 1:], vocab, dtype=jnp.float32)
        return xs, ys
    if model in ("attn", "attn_long"):
        seq = ATTN_SEQ if model == "attn" else ATTN_LONG_SEQ
        vocab = ATTN_VOCAB if model == "attn" else ATTN_LONG_VOCAB
        toks = jax.random.randint(
            jax.random.PRNGKey(2), (chunk, batch, seq + 1), 0, vocab
        )
        xs = jax.nn.one_hot(toks[..., :-1], vocab, dtype=jnp.float32)
        ys = jax.nn.one_hot(toks[..., 1:], vocab, dtype=jnp.float32)
        return xs, ys
    raise ValueError(model)


def measure(model: str = "mlp", precision: str = "fp32",
            steps: int | None = None, batch: int | None = None,
            chunk: int | None = None) -> float:
    """Steady-state training samples/sec with the step loop kept ON DEVICE:
    `chunk` steps run as one lax.scan program per dispatch.

    Timing discipline (round-3 fix): every timed run ends in a
    device->host fetch of the last score, which is a true sync on any
    backend, and no fresh host->device transfer happens inside the loop.
    Rounds 1/2 timed enqueue rates (hence the absurd 17M-samples/s swings).
    Protocol here: all arguments staged on device first, run length DOUBLED
    until one timed run holds >=1.2 s of work (dwarfing the fetch's jitter),
    then rate = work / (median run wall - measured fetch latency) over 3
    runs. The fetch latency of a local chip: not measured.

    ``precision``: "bf16" (mixed-precision policy), "fp32" (DEFAULT matmul
    precision — a single bf16 MXU pass, see module docstring), or
    "fp32_true" (HIGHEST — bf16x6 passes, true-fp32 accuracy; the caller
    must set jax_default_matmul_precision='highest' BEFORE tracing, which
    run_stage does in the stage subprocess).
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import functional as F
    from deeplearning4j_tpu.ops.dtypes import BF16_COMPUTE

    repeats = 3
    batch = batch if batch is not None else MODEL_BATCH[model]
    chunk = chunk if chunk is not None else MODEL_CHUNK[model]

    conf = _conf(model)
    policy = BF16_COMPUTE if precision == "bf16" else None
    params = F.init_params(conf, jax.random.PRNGKey(0))
    states = F.init_train_state(conf, params)
    epoch = F.make_train_epoch(conf, chunk, donate=True, policy=policy)

    x, y = _make_data(model, chunk, batch)
    key = jax.random.PRNGKey(1)

    # every argument device-resident BEFORE timing: a fresh host->device
    # transfer (e.g. a per-dispatch jnp.asarray(i)) would bill per dispatch,
    # not per step
    iter0 = jnp.asarray(0)
    float(jnp.sum(x) + jnp.sum(y) + iter0)  # force + sync the transfers

    def run(k):
        nonlocal params, states
        t0 = time.perf_counter()
        for _ in range(k):
            params, states, scores = epoch(params, states, iter0, x, y, key)
        last = float(scores[-1])  # true sync: device->host fetch
        assert math.isfinite(last), "non-finite training score"
        return time.perf_counter() - t0

    for _ in range(WARMUP_CHUNKS):
        run(1)

    fetch_lat = statistics.median(
        _time_of(lambda: float(jnp.sum(iter0 + 1))) for _ in range(5)
    )

    # size the run by DOUBLING until its measured wall clears the target —
    # a single short probe is itself jitter-dominated, so never trust one
    # small sample to extrapolate
    target = 0.3 if _fast() else 1.2  # seconds of work per timed run
    k = max(steps // chunk, 1) if steps is not None else 4
    t = run(k)
    while t < target + fetch_lat and k < 256:
        k *= 2
        t = run(k)
    times = [t] + [run(k) for _ in range(repeats - 1)]
    t_med = statistics.median(times)
    # the doubling above guarantees t_med >> fetch_lat, so the subtraction
    # can never clamp into a fabricated rate
    return k * chunk * batch / max(t_med - fetch_lat, 0.2 * t_med)


def measure_moe() -> float:
    """A/B of the two MoE dispatch impls (parallel/moe.py) on a dp×ep mesh
    at G ∈ {1, 4} experts per device: one MoE layer (router + grouped
    expert FFNs, top-2, Switch aux) trained by a jitted SGD step, tokens/s
    per config plus an analytic per-device comm-volume estimate in the
    stage detail — the replicated path pays a dense (n_row, d) psum
    allreduce regardless of expert occupancy, the alltoall path pays the
    2×(E·C·d) capacity exchange. Headline value: alltoall tokens/s at G=4.

    Same timing discipline as ``measure``: device-staged args, measured
    fetch latency, run length doubled until a timed run dwarfs the fetch
    jitter, median of 3."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from deeplearning4j_tpu.parallel.moe import (
        expected_dropped,
        load_balance_loss,
        moe_apply,
        route_shards,
    )

    repeats = 3
    if _fast():
        d, dff, n_tokens = 32, 64, 512
    else:
        d, dff, n_tokens = 512, 1024, 16384

    devs = jax.devices()
    n_use = min(len(devs), 8)
    ep = 2 if n_use >= 2 else 1
    dp = max(n_use // ep, 1)
    # top-2 is the flagship setting; a single-device run (ep=1, so the G=1
    # config has exactly one expert) can only route top-1
    top_k = 2 if ep >= 2 else 1
    mesh = Mesh(np.array(devs[: dp * ep]).reshape(dp, ep),
                ("data", "expert"))
    n_row = n_tokens // dp

    def expert_fn(p, t):
        return jax.nn.relu(t @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n_tokens, d))
    tgt = jnp.tanh(jax.random.normal(jax.random.fold_in(key, 2),
                                     (n_tokens, d)))
    zero = jnp.asarray(0)
    float(jnp.sum(x) + jnp.sum(tgt) + zero)  # force + sync the transfers

    fetch_lat = statistics.median(
        _time_of(lambda: float(jnp.sum(zero + 1))) for _ in range(5)
    )
    target = 0.3 if _fast() else 1.2

    def bench_config(group: int, impl: str) -> dict:
        n_experts = group * ep
        # equal-E, equal capacity-FACTOR A/B (GShard factor 1.25): capacity
        # binds per (expert, sub-shard), so each impl gets the factor over
        # ITS routing unit — the whole token row for replicated, one
        # device's n_row/ep slice for alltoall. Same admitted global route
        # budget either way; the buffers just live where the tokens do.
        sub = n_row if impl == "replicated" else n_row // ep
        capacity = max(-(-int(1.25 * top_k * sub) // n_experts), 1)
        ks = jax.random.split(jax.random.fold_in(key, 10 + group), 2)
        router_w = jax.random.normal(ks[0], (d, n_experts)) / (d ** 0.5)
        ek = jax.random.split(ks[1], 4)
        experts = {
            "w1": jax.random.normal(ek[0], (n_experts, d, dff)) / (d ** 0.5),
            "b1": jnp.zeros((n_experts, dff)),
            "w2": jax.random.normal(ek[1], (n_experts, dff, d)) / (dff ** 0.5),
            "b2": jnp.zeros((n_experts, d)),
        }
        from deeplearning4j_tpu.parallel.sharding import shard_leading_axis

        experts = shard_leading_axis(experts, mesh, "expert")

        # the hot loop only rebinds the (router, experts) state, so the old
        # buffers donate into the update
        @partial(jax.jit, donate_argnums=(0,))
        def moe_step(state, xs, ys):
            rw, ps = state

            def loss_fn(rw, ps):
                out = moe_apply(rw, ps, xs, mesh, expert_fn, capacity,
                                top_k=top_k, token_axes=("data",), impl=impl)
                task = jnp.mean((out - ys) ** 2)
                return task + 1e-2 * load_balance_loss(rw, xs)

            loss, (gr, ge) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(rw, ps)
            new = (rw - 0.1 * gr,
                   jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, ps, ge))
            return new, loss

        # drop stats on the INITIAL router (donation below retires the
        # original buffers; the init-time routing is the comparable stat)
        n_shards = route_shards(mesh, ("data",), "expert", n_tokens, impl)
        drop = expected_dropped(router_w, x, capacity, top_k,
                                n_shards=n_shards)

        state = (router_w, experts)
        for _ in range(2):  # compile + committed-sharding warmup
            state, loss = moe_step(state, x, tgt)
        float(loss)

        def run(k):
            nonlocal state
            t0 = time.perf_counter()
            for _ in range(k):
                state, loss = moe_step(state, x, tgt)
            last = float(loss)  # true sync: device->host fetch
            assert math.isfinite(last), "non-finite moe loss"
            return time.perf_counter() - t0

        k, t = 1, run(1)
        while t < target + fetch_lat and k < 256:
            k *= 2
            t = run(k)
        t_med = statistics.median([t] + [run(k) for _ in range(repeats - 1)])
        rate = k * n_tokens / max(t_med - fetch_lat, 0.2 * t_med)

        # analytic per-device FORWARD comm volume (backward transposes
        # mirror it); f32 = 4 bytes, ring-allreduce convention for psum
        if impl == "replicated":
            comm = 2 * (ep - 1) / ep * n_row * d * 4
        else:
            comm = 2 * (ep - 1) / ep * n_experts * capacity * d * 4
        return {
            "n_experts": n_experts,
            "capacity": capacity,
            "tokens_per_sec": round(rate, 1),
            "est_fwd_comm_bytes_per_dev": int(comm),
            "dropped_frac": round(drop / (n_tokens * top_k), 4),
        }

    detail = {
        "mesh": {"data": dp, "expert": ep},
        "d_model": d, "d_ff": dff, "tokens_per_step": n_tokens,
        "top_k": top_k,
        "comm_model": (
            "est_fwd_comm_bytes_per_dev: replicated = ring-allreduce of the "
            "dense (n_row, d) combine, 2(p-1)/p·n_row·d·4; alltoall = "
            "dispatch+return capacity exchange, 2(p-1)/p·E·C·d·4 — forward "
            "only, the backward transposes mirror the same volumes"
        ),
    }
    for group in (1, 4):
        for impl in ("alltoall", "replicated"):
            detail[f"{impl}_g{group}"] = bench_config(group, impl)
    for group in (1, 4):
        a2a = detail[f"alltoall_g{group}"]["tokens_per_sec"]
        rep = detail[f"replicated_g{group}"]["tokens_per_sec"]
        if rep:
            detail[f"alltoall_vs_replicated_g{group}"] = round(a2a / rep, 2)
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return detail["alltoall_g4"]["tokens_per_sec"]


def measure_word2vec(n_sentences: int = 2000, sent_len: int = 100,
                     vocab: int = 5000, layer_size: int = 100,
                     batch_size: int = 8192, mesh=None) -> float:
    """End-to-end Word2Vec skip-gram words/sec (BASELINE config #4): host
    tokenization + vectorized pair generation + device SGNS steps. Counted in
    corpus words per second, the reference's unit (Word2Vec.java:303-342).

    Two scales: the r01-r04 toy stage (V=5k, D=100, 200k words — small
    enough that post-round-5 the epoch is dispatch-latency-bound on BOTH
    platforms) and the `_large` stage (V=50k, D=256, 2M words) where
    compute dominates and the chip's advantage is visible.

    ``mesh``: a data-parallel mesh routes training through
    ``make_sharded_sgns_step`` (pair batches sharded over the data axis,
    in-graph psum over ICI) — the `word2vec_sharded` stage, the next lever
    the r05 bench note called out after the single-chip row-op work."""
    import numpy as np

    from deeplearning4j_tpu.models.word2vec import Word2Vec
    from deeplearning4j_tpu.text.sentence_iterator import (
        CollectionSentenceIterator,
    )

    rng = np.random.default_rng(0)
    # zipf-ish corpus so the unigram table and subsampling do real work
    words = np.array([f"w{i}" for i in range(vocab)])
    probs = 1.0 / np.arange(1, vocab + 1)
    probs /= probs.sum()
    ids = rng.choice(vocab, (n_sentences, sent_len), p=probs)
    sents = [" ".join(row) for row in words[ids]]
    vec = Word2Vec(
        sentence_iterator=CollectionSentenceIterator(sents),
        layer_size=layer_size, window=5, negative=5, iterations=1,
        sample=1e-3, batch_size=batch_size, seed=1, mesh=mesh,
    )
    vec.build_vocab()
    vec.fit()  # warmup: compiles the scan program (~25 s, one-time)
    t0 = time.perf_counter()
    vec.fit()
    # fence on the device-resident tables: fit() leaves the embeddings on
    # device (lazy host sync), so the clock must cover the actual training,
    # not its enqueue
    vec.block_until_ready()
    dt = time.perf_counter() - t0
    rate = n_sentences * sent_len / dt
    split = getattr(vec, "last_fit_timings", None)
    if split:
        print("W2V_SPLIT " + json.dumps(split), flush=True)
    return rate


TELEMETRY_INTERVAL = 10  # steps per device->host metrics fetch


def measure_lm_composed(steps: int | None = None,
                        batch: int | None = None,
                        telemetry: bool = True) -> float:
    """End-to-end training samples/sec of the COMPOSED-flagship LM: the
    multi-block (n_layers=2) transformer LM with causal MHA + top-2 MoE
    FFN, trained by models/transformer_lm.make_single_device_train_step.

    The attention core comes from the DL4J_TPU_ATTN_IMPL env seam —
    run_stage exports it BEFORE tracing ("blockwise" for the main stage and
    the forced-CPU baseline, "dense" for the _densecore A/B twin), so the
    A/B needs no code edits. Same timing discipline as ``measure``: warmup,
    measured fetch latency, run length doubled until a timed run dwarfs the
    fetch jitter, median of 3.

    ``telemetry``: after the headline rate, A/B the metrics-threaded step
    (telemetry/) against the plain one — interleaved min-of-N runs at the
    same k, metrics fetched every TELEMETRY_INTERVAL steps — then run a
    short logged window through TrainTelemetry and report the step-log
    summary + measured overhead in the stage detail (the <5% budget is
    asserted by tests/test_bench_smoke.py)."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer_lm import (
        init_lm_params,
        make_single_device_train_step,
        selected_attn_impl,
    )

    repeats = 3
    if _fast():
        vocab, d, heads, experts, dff = 256, 64, 2, 2, 128
        seq = 256
    else:
        vocab, d, heads, experts, dff = (LMC_VOCAB, LMC_D, LMC_HEADS,
                                         LMC_EXPERTS, LMC_DFF)
        seq = LMC_SEQ
    batch = batch if batch is not None else (2 if _fast() else LMC_BATCH)

    params = init_lm_params(jax.random.PRNGKey(0), vocab, d, heads, experts,
                            dff, n_layers=LMC_LAYERS)
    # the hot loop only ever rebinds params, so the step can donate the old
    # param buffers into the update (halves peak param HBM; the telemetry
    # A/B below builds its own non-donating steps and copies). profile=
    # (ISSUE 9) captures the compiled step's StepProfile at first call —
    # compile-time-only, the timed loop runs the same executable — so each
    # BENCH round embeds the cost/memory/collective blob profile_report.py
    # and bench_report.py diff across rounds.
    step = make_single_device_train_step(heads, donate=True,
                                         profile="lm_composed")
    toks = jax.random.randint(jax.random.PRNGKey(2), (batch, seq + 1), 0,
                              vocab)
    tk, tg = toks[:, :-1], toks[:, 1:]
    zero = jnp.asarray(0)
    float(jnp.sum(tk) + jnp.sum(tg) + zero)  # force + sync the transfers

    def run(k):
        nonlocal params
        t0 = time.perf_counter()
        for _ in range(k):
            params, loss = step(params, tk, tg)
        last = float(loss)  # true sync: device->host fetch
        assert math.isfinite(last), "non-finite lm_composed loss"
        return time.perf_counter() - t0

    for _ in range(2):
        run(1)  # compile + warmup

    fetch_lat = statistics.median(
        _time_of(lambda: float(jnp.sum(zero + 1))) for _ in range(5)
    )
    target = 0.3 if _fast() else 1.2
    k = max(steps, 1) if steps is not None else 1
    t = run(k)
    while t < target + fetch_lat and k < 256:
        k *= 2
        t = run(k)
    times = [t] + [run(k) for _ in range(repeats - 1)]
    t_med = statistics.median(times)
    rate = k * batch / max(t_med - fetch_lat, 0.2 * t_med)
    detail = {
        "tokens_per_sec": round(rate * seq, 1),
        "seq_len": seq, "n_layers": LMC_LAYERS,
        "attn_impl": os.environ.get("DL4J_TPU_ATTN_IMPL", "auto"),
    }
    prof = getattr(step, "step_profile", None)
    if prof is not None:
        from deeplearning4j_tpu.telemetry.xprofile import attribute

        detail["profile"] = prof.to_dict()
        analytic = 3 * lmc_fwd_flops(vocab, d, experts, dff, LMC_LAYERS,
                                     seq) * batch
        if prof.flops:
            detail["profile"]["analytic_train_flops"] = analytic
            # XLA counts the layer scan's body once (xprofile docstring),
            # so the like-for-like ratio is vs the scan-adjusted number
            detail["profile"]["xla_vs_analytic_flops"] = round(
                prof.flops / lmc_xla_flops_expectation(
                    vocab, d, experts, dff, seq, batch), 4)
        att = attribute(prof, batch / rate)
        detail["profile_attribution"] = {
            "measured_mfu": round(att["measured_mfu"], 4),
            "hbm_utilization": round(att["hbm_utilization"], 4),
            "comm_fraction": round(att["comm_fraction"], 6),
            "arithmetic_intensity": (round(att["arithmetic_intensity"], 2)
                                     if att["arithmetic_intensity"]
                                     else None),
            "ridge_intensity": round(att["ridge_intensity"], 2),
            "bound": att["bound"],
        }
    if telemetry:
        detail["telemetry"] = _lm_composed_telemetry(
            heads, params, tk, tg, k, batch, seq,
            selected_attn_impl(seq), tempfile, repeats)
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return rate


def _lm_composed_telemetry(heads, params, tk, tg, k, batch, seq,
                           attn_impl, tempfile, repeats) -> dict:
    """Telemetry-on vs telemetry-off A/B + a logged window (see
    measure_lm_composed). Returns the stage-detail telemetry block.

    A/B fairness: BOTH loops fetch at the same cadence — the telemetry-off
    twin pulls the loss scalar every TELEMETRY_INTERVAL steps (any real
    training loop logs its loss; an end-only-sync baseline would bill the
    logging sync, which telemetry-off runs pay too, to telemetry), the
    telemetry-on loop pulls the full metrics window. Overhead = median of
    per-pair on/off ratios over interleaved runs at the same k — pairing
    cancels drift; the median rides out a one-off scheduler hiccup that a
    min-based estimate inherits from whichever side it hits."""
    import jax

    from deeplearning4j_tpu.models.transformer_lm import (
        make_single_device_train_step,
    )
    from deeplearning4j_tpu.telemetry import (
        TrainTelemetry,
        read_step_log,
        summarize_step_log,
    )

    mstep = make_single_device_train_step(heads, with_metrics=True)
    step = make_single_device_train_step(heads)
    mparams = jax.tree_util.tree_map(lambda a: a, params)
    oparams = jax.tree_util.tree_map(lambda a: a, params)
    interval = TELEMETRY_INTERVAL

    def run_off(kk):
        nonlocal oparams
        t0 = time.perf_counter()
        for i in range(kk):
            oparams, loss = step(oparams, tk, tg)
            if (i + 1) % interval == 0:
                float(loss)  # the loss-logging sync every loop pays
        float(loss)
        return time.perf_counter() - t0

    def run_on(kk):
        nonlocal mparams
        buf = []
        t0 = time.perf_counter()
        for _ in range(kk):
            mparams, loss, m = mstep(mparams, tk, tg)
            buf.append(m)
            if len(buf) >= interval:  # the one sync per window
                jax.device_get(buf)
                buf.clear()
        if buf:
            jax.device_get(buf)
        float(loss)
        return time.perf_counter() - t0

    for _ in range(2):
        run_on(1)  # compile + warmup the metrics step
        run_off(1)
    ratios = []
    for _ in range(max(repeats, 5)):
        t_off = run_off(k)
        t_on = run_on(k)
        ratios.append(t_on / t_off)
    overhead_pct = (statistics.median(ratios) - 1.0) * 100.0

    # short logged window through the full host pipeline (session -> JSONL
    # -> summary) so the bench's telemetry claim is end-to-end, not synthetic
    log_path = os.path.join(tempfile.mkdtemp(prefix="lmc_telemetry_"),
                            "steps.jsonl")
    session = TrainTelemetry(
        step_log_path=log_path, interval=interval,
        tokens_per_step=batch * seq,
        static={"stage": "lm_composed", "attn_impl": attn_impl})
    log_steps = interval + 2  # spans a fetch boundary
    for i in range(log_steps):
        mparams, loss, m = mstep(mparams, tk, tg)
        session.record(i, m)
    session.close()
    summary = summarize_step_log(read_step_log(log_path))
    return {
        "interval": interval,
        "overhead_pct": round(overhead_pct, 2),
        "steps_logged": summary.get("steps", 0),
        "step_log_summary": summary,
    }


def measure_guardrails() -> float:
    """ISSUE 8 overhead budget + recovery demo. Two halves:

    (a) Guarded vs unguarded composed-flagship step A/B on one device —
    the in-graph guard (finiteness reductions + skip select,
    optimize/guardrails.py) must cost <5% vs the identical unguarded step.
    Same paired discipline as the PR 2 metrics budget: both loops fetch at
    the same cadence (the guarded loop pulls its guard block every
    TELEMETRY_INTERVAL steps, the plain loop pulls the loss scalar),
    interleaved runs at the same k, overhead = median of per-pair ratios.

    (b) Injected-NaN recovery demo on the guarded elastic reference model:
    a poisoned batch is skipped in-graph (params carried, finite), the
    faulting step is dumped as a replay bundle, and tools/step_replay.py
    re-executes it — asserting the non-finite result REPRODUCES. The demo
    results land in the stage detail (test_bench_smoke pins them).

    Headline = overhead percent (lower is better)."""
    import contextlib
    import io
    import tempfile

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer_lm import (
        init_lm_params,
        make_single_device_train_step,
    )
    from tools import step_replay

    repeats = 3
    if _fast():
        vocab, d, heads, experts, dff = 256, 64, 2, 2, 128
        seq, batch = 256, 2
    else:
        vocab, d, heads, experts, dff = (LMC_VOCAB, LMC_D, LMC_HEADS,
                                         LMC_EXPERTS, LMC_DFF)
        seq, batch = LMC_SEQ, LMC_BATCH

    params = init_lm_params(jax.random.PRNGKey(0), vocab, d, heads, experts,
                            dff, n_layers=LMC_LAYERS)
    step = make_single_device_train_step(heads, donate=True)
    gstep = make_single_device_train_step(heads, donate=True, guard=True)
    toks = jax.random.randint(jax.random.PRNGKey(2), (batch, seq + 1), 0,
                              vocab)
    tk, tg = toks[:, :-1], toks[:, 1:]
    zero = jnp.asarray(0)
    float(jnp.sum(tk) + jnp.sum(tg) + zero)  # force + sync the transfers
    # REAL copies: both steps donate their params, so the two loops must
    # not alias the init tree (a donated-away buffer would be deleted
    # under the other loop)
    oparams = jax.tree_util.tree_map(jnp.array, params)
    gparams = jax.tree_util.tree_map(jnp.array, params)
    interval = TELEMETRY_INTERVAL

    def run_off(kk):
        nonlocal oparams
        t0 = time.perf_counter()
        for i in range(kk):
            oparams, loss = step(oparams, tk, tg)
            if (i + 1) % interval == 0:
                float(loss)  # the loss-logging sync every loop pays
        float(loss)
        return time.perf_counter() - t0

    def run_on(kk):
        nonlocal gparams
        buf = []
        t0 = time.perf_counter()
        for _ in range(kk):
            gparams, loss, gm = gstep(gparams, tk, tg)
            buf.append(gm)
            if len(buf) >= interval:  # the watchdog-cadence sync
                jax.device_get(buf)
                buf.clear()
        if buf:
            jax.device_get(buf)
        float(loss)
        return time.perf_counter() - t0

    for _ in range(2):
        run_off(1)
        run_on(1)  # compile + warmup both programs

    fetch_lat = statistics.median(
        _time_of(lambda: float(jnp.sum(zero + 1))) for _ in range(5)
    )
    target = 0.3 if _fast() else 1.2
    k, t = 1, run_off(1)
    while t < target + fetch_lat and k < 256:
        k *= 2
        t = run_off(k)
    ratios = []
    for _ in range(max(repeats, 5)):
        t_off = run_off(k)
        t_on = run_on(k)
        ratios.append(t_on / t_off)
    overhead_pct = (statistics.median(ratios) - 1.0) * 100.0

    # ---- (b) injected-NaN recovery + replay forensics ----
    from deeplearning4j_tpu.optimize.guardrails import (
        dump_replay_bundle,
        tree_all_finite,
    )
    from deeplearning4j_tpu.scaleout.elastic import SyntheticRegressionModel

    model_kw = dict(d_in=8, d_hidden=16, batch=16, lr=0.05, mesh_devices=1)
    nan_step = 3
    model = SyntheticRegressionModel(guard=True, nan_at_step=nan_step,
                                     **model_kw)
    p = model.init_params()
    p, _ = model.run_steps(p, 0, nan_step, worker_seed=0)  # clean prefix
    pre = p  # run_steps returns a fresh host tree; this reference is stable
    x, y = model._batch_for(0, nan_step)
    p, _ = model.run_steps(p, nan_step, 1, worker_seed=0)  # the NaN step
    skipped = model.skipped_steps
    params_carried = all(
        a.tobytes() == b.tobytes()
        for a, b in zip(jax.tree_util.tree_leaves(pre),
                        jax.tree_util.tree_leaves(p)))
    p, post_loss = model.run_steps(p, nan_step + 1, 4, worker_seed=0)

    bundle_dir = tempfile.mkdtemp(prefix="guardrails_bench_")
    bundle = dump_replay_bundle(
        bundle_dir, nan_step, {"params": pre, "batch": {"x": x, "y": y}},
        {"demo": "bench guardrails stage"})
    # in-process: this stage holds the chip, and a child that imports JAX
    # could not take it
    replay_out = io.StringIO()
    with contextlib.redirect_stdout(replay_out):
        replay_rc = step_replay.main(
            [bundle, "--factory",
             "deeplearning4j_tpu.scaleout.elastic:synthetic_replay",
             "--kwargs-json", json.dumps(model_kw),
             "--expect-nonfinite", "--json"])
    if replay_rc != 0:
        raise RuntimeError(f"tools/step_replay.py exited {replay_rc}: "
                           + replay_out.getvalue()[-500:])
    replay_rep = json.loads(replay_out.getvalue())

    detail = {
        "interval": interval,
        "overhead_pct": round(overhead_pct, 2),
        "guarded_vs_unguarded_ratio": round(statistics.median(ratios), 4),
        "recovery": {
            "skipped_steps": skipped,
            "params_carried_bitwise": bool(params_carried),
            "params_finite_after_skip": bool(tree_all_finite(p)),
            "post_recovery_loss": round(float(post_loss), 6),
            "replay_rc": replay_rc,
            "replay_reproduced": bool(replay_rep.get("reproduced")),
            "poisoned_leaves": [e["path"] for e in
                                replay_rep.get("forensics", [])
                                if e.get("nonfinite")],
        },
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return overhead_pct


def measure_profile() -> float:
    """ISSUE 9 acceptance: profiling is COMPILE-TIME-ONLY. A/B of the
    composed-flagship single-device step with the ``profile=`` seam on
    (telemetry/xprofile.py ProfiledStep: AOT lower→compile once, then the
    same executable every call) vs the identical plain jitted step — same
    paired-median discipline as the telemetry/guardrails budgets, both
    loops fetching the loss at the same cadence. Headline = overhead
    percent (<5% budget, asserted in test_bench_smoke).

    The stage detail also carries the captured StepProfile (XLA FLOPs /
    bytes / memory / collective inventory), the analytic-vs-XLA FLOPs
    cross-check against ``lmc_fwd_flops`` at the stage shapes, the fused
    measured-MFU/roofline attribution, and a memory-watermark sampler
    pass over the timed window (empty watermarks on backends without
    memory_stats — explicitly, never fabricated)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer_lm import (
        init_lm_params,
        make_single_device_train_step,
    )
    from deeplearning4j_tpu.telemetry.xprofile import (
        MemoryWatermarkSampler,
        attribute,
    )

    repeats = 3
    if _fast():
        vocab, d, heads, experts, dff = 256, 64, 2, 2, 128
        seq, batch = 256, 2
    else:
        vocab, d, heads, experts, dff = (LMC_VOCAB, LMC_D, LMC_HEADS,
                                         LMC_EXPERTS, LMC_DFF)
        seq, batch = LMC_SEQ, LMC_BATCH

    params = init_lm_params(jax.random.PRNGKey(0), vocab, d, heads, experts,
                            dff, n_layers=LMC_LAYERS)
    step = make_single_device_train_step(heads, donate=True)
    pstep = make_single_device_train_step(heads, donate=True, profile=True)
    toks = jax.random.randint(jax.random.PRNGKey(2), (batch, seq + 1), 0,
                              vocab)
    tk, tg = toks[:, :-1], toks[:, 1:]
    zero = jnp.asarray(0)
    float(jnp.sum(tk) + jnp.sum(tg) + zero)  # force + sync the transfers
    # REAL copies: both steps donate, so the loops must not alias the init
    oparams = jax.tree_util.tree_map(jnp.array, params)
    pparams = jax.tree_util.tree_map(jnp.array, params)
    interval = TELEMETRY_INTERVAL

    def run_off(kk):
        nonlocal oparams
        t0 = time.perf_counter()
        for i in range(kk):
            oparams, loss = step(oparams, tk, tg)
            if (i + 1) % interval == 0:
                float(loss)  # the loss-logging sync every loop pays
        float(loss)
        return time.perf_counter() - t0

    def run_on(kk):
        nonlocal pparams
        t0 = time.perf_counter()
        for i in range(kk):
            pparams, loss = pstep(pparams, tk, tg)
            if (i + 1) % interval == 0:
                float(loss)
        float(loss)
        return time.perf_counter() - t0

    for _ in range(2):
        run_off(1)
        run_on(1)  # compile + AOT-profile warmup

    fetch_lat = statistics.median(
        _time_of(lambda: float(jnp.sum(zero + 1))) for _ in range(5)
    )
    target = 0.3 if _fast() else 1.2
    k, t = 1, run_off(1)
    while t < target + fetch_lat and k < 256:
        k *= 2
        t = run_off(k)
    ratios = []
    t_offs = []
    sampler = MemoryWatermarkSampler(interval_s=0.1)
    with sampler:
        for _ in range(max(repeats, 5)):
            t_off = run_off(k)
            t_on = run_on(k)
            t_offs.append(t_off)
            ratios.append(t_on / t_off)
    overhead_pct = (statistics.median(ratios) - 1.0) * 100.0

    prof = pstep.step_profile
    step_s = statistics.median(t_offs) / k
    analytic = 3 * lmc_fwd_flops(vocab, d, experts, dff, LMC_LAYERS,
                                 seq) * batch
    # XLA counts the layer scan's body once (xprofile docstring), so the
    # like-for-like cross-check divides by the scan-adjusted expectation
    expectation = lmc_xla_flops_expectation(vocab, d, experts, dff, seq,
                                            batch)
    att = attribute(prof, step_s)
    detail = {
        "interval": interval,
        "overhead_pct": round(overhead_pct, 2),
        "profiled_vs_plain_ratio": round(statistics.median(ratios), 4),
        "signature_fallbacks": pstep.signature_fallbacks,
        "profile": prof.to_dict(),
        "analytic_train_flops": analytic,
        "xla_vs_analytic_flops": (round(prof.flops / expectation, 4)
                                  if prof.flops else None),
        "attribution": {
            "step_seconds": round(att["step_seconds"], 6),
            "measured_mfu": round(att["measured_mfu"], 4),
            "hbm_utilization": round(att["hbm_utilization"], 4),
            "comm_fraction": round(att["comm_fraction"], 6),
            "bound": att["bound"],
        },
        "memory_watermarks": {
            "samples": sampler.samples,
            "devices": sampler.watermarks(),
        },
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return overhead_pct


def measure_optimizer() -> float:
    """ISSUE 13: the in-graph optimizer A/B on the composed dp×ep
    flagship — SGD vs Adam(replicated update) vs Adam(ZeRO
    update-sharded) at identical math (optimize/updaters.py). For each
    config: steps/s (same fenced timing discipline as the moe stage) plus
    the compile-time StepProfile, so the memory claim is
    profiler-provable, not hand-waved: the headline is the
    replicated/sharded ``peak_bytes`` ratio (>1 = the ZeRO update is
    smaller), the per-replica at-rest moment bytes are measured off the
    actual device buffers, and the sharded blob lands as the stage's
    ``profile`` detail so ``tools/bench_report.py`` tracks
    ``optimizer_profile_peak_bytes`` LOWER-IS-BETTER across rounds.
    A 3-step sharded-vs-replicated parity check (max |Δparam|) rides in
    the detail — the A/B is only meaningful at identical math."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import Mesh

    from deeplearning4j_tpu.models.transformer_lm import (
        init_lm_opt_state,
        init_lm_params,
        make_composed_train_step,
        shard_lm_batch,
        shard_lm_params,
    )
    from deeplearning4j_tpu.optimize.updaters import OptimizerConfig
    from deeplearning4j_tpu.telemetry.xprofile import profile_compiled

    repeats = 3
    if _fast():
        vocab, d, heads, dff = 256, 64, 2, 128
        seq, batch = 128, 8
    else:
        vocab, d, heads, dff = LMC_VOCAB, LMC_D, LMC_HEADS, LMC_DFF
        seq, batch = 512, 8

    devs = jax.devices()
    n_use = min(len(devs), 8)
    ep = 2 if n_use >= 2 else 1
    dp = max(n_use // ep, 1)
    mesh = Mesh(np.array(devs[: dp * ep]).reshape(dp, ep),
                ("data", "expert"))
    n_experts = 2 * ep
    # ample capacity (the full token row) — the A/B compares optimizers,
    # not drop semantics
    capacity = max((batch // dp) * seq, 4)

    params = init_lm_params(jax.random.PRNGKey(0), vocab, d, heads,
                            n_experts, dff, n_layers=LMC_LAYERS)
    toks = jax.random.randint(jax.random.PRNGKey(2), (batch, seq + 1), 0,
                              vocab)
    tk, tg = shard_lm_batch(toks[:, :-1], toks[:, 1:], mesh)
    zero = jnp.asarray(0)
    float(jnp.sum(tk) + jnp.sum(tg) + zero)  # force + sync the transfers
    fetch_lat = statistics.median(
        _time_of(lambda: float(jnp.sum(zero + 1))) for _ in range(5)
    )
    target = 0.3 if _fast() else 1.2

    configs = {
        "sgd": None,
        "adam_replicated": OptimizerConfig(
            name="adam", lr=1e-3, update_sharding="replicated"),
        "adam_sharded": OptimizerConfig(
            name="adam", lr=1e-3, update_sharding="sharded"),
        "lamb_sharded": OptimizerConfig(
            name="lamb", lr=1e-3, update_sharding="sharded"),
    }

    def per_replica_state_bytes(state) -> int:
        dev0 = jax.devices()[0]
        total = 0
        for leaf in jax.tree_util.tree_leaves(
                {"m": state["m"], "v": state["v"]}):
            total += sum(sh.data.nbytes for sh in leaf.addressable_shards
                         if sh.device == dev0)
        return total

    def bench_config(name, opt) -> dict:
        step = make_composed_train_step(mesh, heads, capacity,
                                        optimizer=opt, donate=True)
        # REAL copy before placing: device_put may alias the host tree's
        # buffers, and the donating step would delete them for every
        # config that follows
        p = shard_lm_params(jax.tree_util.tree_map(jnp.array, params), mesh)
        state = None if opt is None else init_lm_opt_state(opt, p, mesh)
        prof_args = (p, tk, tg) if opt is None else (p, state, tk, tg)
        # profile BEFORE the timed loop (donation retires the init args);
        # profile_compiled is one AOT compile, no execution
        prof = profile_compiled(step, *prof_args, label=f"optimizer_{name}")
        out = {"profile_peak_bytes": prof.peak_bytes,
               "profile_flops": prof.flops,
               "collectives": {k: v["count"]
                               for k, v in prof.collectives.items()}}
        if state is not None:
            out["moment_bytes_per_replica"] = per_replica_state_bytes(state)

        carry = [p, state]

        def one_step():
            if carry[1] is None:
                carry[0], loss = step(carry[0], tk, tg)
            else:
                carry[0], carry[1], loss = step(carry[0], carry[1], tk, tg)
            return loss

        for _ in range(2):  # compile + committed-sharding warmup
            loss = one_step()
        float(loss)

        def run(k):
            t0 = time.perf_counter()
            for _ in range(k):
                loss = one_step()
            last = float(loss)  # true sync: device->host fetch
            assert math.isfinite(last), f"non-finite {name} loss"
            return time.perf_counter() - t0

        k, t = 1, run(1)
        while t < target + fetch_lat and k < 256:
            k *= 2
            t = run(k)
        t_med = statistics.median([t] + [run(k) for _ in range(repeats - 1)])
        out["steps_per_sec"] = round(k / max(t_med - fetch_lat,
                                             0.2 * t_med), 2)
        return out

    detail = {
        "mesh": {"data": dp, "expert": ep},
        "model": {"vocab": vocab, "d_model": d, "d_ff": dff, "seq": seq,
                  "batch": batch, "n_experts": n_experts,
                  "n_layers": LMC_LAYERS},
    }
    profiles = {}
    for name, opt in configs.items():
        cfg_out = bench_config(name, opt)
        detail[name] = cfg_out
        profiles[name] = cfg_out

    # the sharded blob is THE tracked footprint row
    # (optimizer_profile_peak_bytes, LOWER-IS-BETTER in bench_report)
    sh_step = make_composed_train_step(mesh, heads, capacity,
                                       optimizer=configs["adam_sharded"])
    p0 = shard_lm_params(params, mesh)
    st0 = init_lm_opt_state(configs["adam_sharded"], p0, mesh)
    detail["profile"] = profile_compiled(
        sh_step, p0, st0, tk, tg, label="optimizer_adam_sharded").to_dict()

    # parity at identical math: 3 steps each mode from the same init
    rep_step = make_composed_train_step(mesh, heads, capacity,
                                        optimizer=configs["adam_replicated"])
    pr = shard_lm_params(params, mesh)
    sr = init_lm_opt_state(configs["adam_replicated"], pr, mesh)
    ps, ss = p0, st0
    for _ in range(3):
        pr, sr, lr_ = rep_step(pr, sr, tk, tg)
        ps, ss, ls_ = sh_step(ps, ss, tk, tg)
    parity = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(pr)),
                        jax.tree_util.tree_leaves(jax.device_get(ps))))
    detail["adam_sharded_vs_replicated_parity_max_abs_diff"] = parity
    detail["adam_loss_delta"] = abs(float(lr_) - float(ls_))

    rep_peak = profiles["adam_replicated"]["profile_peak_bytes"]
    sh_peak = profiles["adam_sharded"]["profile_peak_bytes"]
    ratio = (rep_peak / sh_peak) if (rep_peak and sh_peak) else 0.0
    detail["peak_bytes_replicated"] = rep_peak
    detail["peak_bytes_sharded"] = sh_peak
    detail["moment_bytes_ratio"] = round(
        profiles["adam_replicated"].get("moment_bytes_per_replica", 0)
        / max(profiles["adam_sharded"].get("moment_bytes_per_replica", 1),
              1), 2)
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return ratio


def measure_comm_overlap() -> float:
    """ISSUE 14: the three comm/compute-overlap A/Bs, each a pure-schedule
    twin of a pinned-parity pair —

    (1) flat vs hierarchical 2D MoE all_to_all on dp×ep (the expert axis
        factorized into the (outer, inner) grid of arXiv:2112.01075;
        identical routed values, grouped wire schedule),
    (2) strict vs double-buffered-overlap pipeline ticks on dp×pp
        (ppermute issued for the previous tick's output while this
        tick's stage computes; bit-identical loss+params),
    (3) rotate-after-attend vs prefetch ring attention on dp×sp (the
        K/V rotation issued before the flash tiles consume the current
        block; bit-identical).

    Headline = strict/overlapped pipeline step-time ratio (>1 = overlap
    faster). Every config carries its compiled StepProfile; the measured
    comm fraction (xprofile.attribute at the v5e ICI model) gates which
    configs COUNT — on a comm-starved backend (CPU, tiny shapes, where
    the collectives are memcpys) the ratios are recorded but flagged
    informational rather than claimed as wins. The 2D a2a step's profile
    blob embeds as the stage profile and its wire bytes land on the
    LOWER-IS-BETTER ``comm_overlap_collective_wire_bytes`` bench_report
    row, so comm growth trips --fail-on-regression."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from deeplearning4j_tpu.telemetry.xprofile import (
        attribute,
        profile_compiled,
    )

    repeats = 3
    fast = _fast()
    target = 0.25 if fast else 1.0
    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError("comm_overlap needs 8 devices (dp×ep 2×4)")

    zero = jnp.asarray(0)
    fetch_lat = statistics.median(
        _time_of(lambda: float(jnp.sum(zero + 1))) for _ in range(5))

    def time_step(step, state, *args):
        """Warm 2, double k until a run dwarfs fetch latency, median of
        3 → (ms/step, final state). The step donates+rebinds state."""
        for _ in range(2):
            state, loss = step(state, *args)
        float(loss)

        def run(k):
            nonlocal state
            t0 = time.perf_counter()
            for _ in range(k):
                state, loss = step(state, *args)
            last = float(loss)  # true sync: device->host fetch
            assert math.isfinite(last), "non-finite comm_overlap loss"
            return time.perf_counter() - t0

        k, t = 1, run(1)
        while t < target + fetch_lat and k < 128:
            k *= 2
            t = run(k)
        t_med = statistics.median([t] + [run(k) for _ in range(repeats - 1)])
        return max(t_med - fetch_lat, 0.2 * t_med) / k * 1000.0, state

    detail: dict = {"fast": fast}

    # ---- (1) flat vs 2D MoE all_to_all on dp×ep --------------------------
    from deeplearning4j_tpu.parallel.moe import (
        factor_expert_axis,
        load_balance_loss,
        moe_apply,
    )
    from deeplearning4j_tpu.parallel.sharding import shard_leading_axis

    dp, ep = 2, 4
    mesh = Mesh(np.array(devs[: dp * ep]).reshape(dp, ep),
                ("data", "expert"))
    d, dff = (32, 64) if fast else (256, 512)
    n_tokens = 512 if fast else 8192
    group = 2
    n_experts = group * ep
    sub = (n_tokens // dp) // ep
    capacity = max(-(-int(1.25 * 2 * sub) // n_experts), 1)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n_tokens, d))
    tgt = jnp.tanh(jax.random.normal(jax.random.fold_in(key, 2),
                                     (n_tokens, d)))
    router_w = jax.random.normal(jax.random.fold_in(key, 3),
                                 (d, n_experts)) / (d ** 0.5)
    ek = jax.random.split(jax.random.fold_in(key, 4), 2)
    experts = shard_leading_axis({
        "w1": jax.random.normal(ek[0], (n_experts, d, dff)) / (d ** 0.5),
        "b1": jnp.zeros((n_experts, dff)),
        "w2": jax.random.normal(ek[1], (n_experts, dff, d)) / (dff ** 0.5),
        "b2": jnp.zeros((n_experts, d)),
    }, mesh, "expert")
    float(jnp.sum(x) + jnp.sum(tgt))  # force + sync the transfers

    def expert_fn(p, t):
        return jax.nn.relu(t @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    def make_moe_step(impl):
        @partial(jax.jit, donate_argnums=(0,))
        def moe_step(state, xs, ys):
            rw, ps = state

            def loss_fn(rw, ps):
                out = moe_apply(rw, ps, xs, mesh, expert_fn, capacity,
                                top_k=2, token_axes=("data",), impl=impl)
                task = jnp.mean((out - ys) ** 2)
                return task + 1e-2 * load_balance_loss(rw, xs)

            loss, (gr, ge) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(rw, ps)
            return (rw - 0.1 * gr, jax.tree_util.tree_map(
                lambda p, g: p - 0.1 * g, ps, ge)), loss

        return moe_step

    a2a = {"mesh": {"data": dp, "expert": ep},
           "grid": list(factor_expert_axis(ep)),
           "n_experts": n_experts, "capacity": capacity,
           "d_model": d, "tokens_per_step": n_tokens}
    profiles = {}
    for impl in ("alltoall", "alltoall_2d"):
        step = make_moe_step(impl)
        state0 = (jnp.array(router_w),
                  jax.tree_util.tree_map(jnp.array, experts))
        prof = profile_compiled(step, state0, x, tgt,
                                label=f"comm_overlap_{impl}")
        ms, _ = time_step(step, state0, x, tgt)
        ops = prof.collectives.get("all-to-all", {})
        att = attribute(prof, ms / 1000.0)
        profiles[impl] = prof
        a2a[impl] = {
            "step_ms": round(ms, 3),
            "a2a_count": ops.get("count", 0),
            "a2a_group_sizes": ops.get("group_sizes", []),
            "a2a_wire_bytes": ops.get("wire_bytes", 0.0),
            "collective_wire_bytes": prof.collective_wire_bytes,
            "comm_fraction": round(att["comm_fraction"], 6),
        }
    # parity at identical init: one step each, losses within 1e-5
    l_f = float(make_moe_step("alltoall")(
        (jnp.array(router_w), jax.tree_util.tree_map(jnp.array, experts)),
        x, tgt)[1])
    l_2 = float(make_moe_step("alltoall_2d")(
        (jnp.array(router_w), jax.tree_util.tree_map(jnp.array, experts)),
        x, tgt)[1])
    a2a["parity_loss_abs_diff"] = abs(l_f - l_2)
    a2a["2d_vs_flat"] = round(a2a["alltoall"]["step_ms"]
                              / max(a2a["alltoall_2d"]["step_ms"], 1e-9), 3)
    detail["a2a"] = a2a

    # ---- (2) strict vs overlapped pipeline ticks on dp×pp ----------------
    from deeplearning4j_tpu.parallel.pipeline import (
        PIPE_AXIS,
        make_pipeline_train_step,
        shard_stage_params,
        stack_stage_params,
    )

    pmesh = Mesh(np.array(devs[:8]).reshape(2, 4), ("data", PIPE_AXIS))
    pd = 64 if fast else 256
    n_micro, mb = 8, 8
    ks = jax.random.split(jax.random.fold_in(key, 5), 4)
    per_stage = [{"w": jax.random.normal(k, (pd, pd)) / (pd ** 0.5),
                  "b": jnp.zeros((pd,))} for k in ks]
    stacked = shard_stage_params(stack_stage_params(per_stage), pmesh)
    px = jax.random.normal(jax.random.fold_in(key, 6), (n_micro, mb, pd))
    ptgt = jnp.tanh(jax.random.normal(jax.random.fold_in(key, 7),
                                      (n_micro, mb, pd)))
    stage_fn = lambda p, x: jnp.tanh(x @ p["w"] + p["b"])  # noqa: E731
    loss_fn = lambda y, t: jnp.mean((y - t) ** 2)  # noqa: E731

    pp = {"mesh": {"data": 2, "pipe": 4}, "d": pd,
          "n_micro": n_micro, "microbatch": mb}
    pp_params = {}
    for mode, overlap in (("strict", False), ("overlap", True)):
        step = make_pipeline_train_step(stage_fn, loss_fn, pmesh, lr=0.1,
                                        batch_axis="data", overlap=overlap)
        state0 = jax.tree_util.tree_map(jnp.array, stacked)
        prof = profile_compiled(step, state0, px, ptgt,
                                label=f"comm_overlap_pp_{mode}")
        ms, state = time_step(step, state0, px, ptgt)
        att = attribute(prof, ms / 1000.0)
        pp_params[mode] = state
        pp[mode] = {
            "step_ms": round(ms, 3),
            "collective_permute_count": prof.collectives.get(
                "collective-permute", {}).get("count", 0),
            "comm_fraction": round(att["comm_fraction"], 6),
        }
    # bit-parity of the timed endpoints: identical step counts either side
    # would be timing-dependent, so re-run 2 fixed steps from scratch
    s_s = make_pipeline_train_step(stage_fn, loss_fn, pmesh, lr=0.1,
                                   batch_axis="data")
    s_o = make_pipeline_train_step(stage_fn, loss_fn, pmesh, lr=0.1,
                                   batch_axis="data", overlap=True)
    ps_, po_ = (jax.tree_util.tree_map(jnp.array, stacked) for _ in "ab")
    for _ in range(2):
        ps_, l_s = s_s(ps_, px, ptgt)
        po_, l_o = s_o(po_, px, ptgt)
    pp["bit_identical"] = bool(float(l_s) == float(l_o) and all(
        bool(jnp.array_equal(a, b))
        for a, b in zip(jax.tree_util.tree_leaves(ps_),
                        jax.tree_util.tree_leaves(po_))))
    pp["overlap_vs_strict"] = round(
        pp["strict"]["step_ms"] / max(pp["overlap"]["step_ms"], 1e-9), 3)
    detail["pipeline"] = pp

    # ---- (3) rotate-after vs prefetch ring on dp×sp ----------------------
    from deeplearning4j_tpu.parallel.ring_attention import ring_attention

    rmesh = Mesh(np.array(devs[:8]).reshape(2, 4), ("data", "sp"))
    rb, rh, rt, rd = (2, 4, 256, 16) if fast else (2, 8, 2048, 64)
    rk = jax.random.split(jax.random.fold_in(key, 8), 3)
    q0, k0, v0 = (jax.random.normal(kk, (rb, rh, rt, rd)) * 0.5
                  for kk in rk)

    def make_ring_step(prefetch):
        @partial(jax.jit, donate_argnums=(0,))
        def ring_step(q, k, v):
            def loss(q):
                out = ring_attention(q, k, v, rmesh, "sp", causal=True,
                                     batch_axis="data", attn_impl="dense",
                                     prefetch=prefetch)
                return jnp.sum(out * out)

            l, g = jax.value_and_grad(loss)(q)
            return q - 1e-3 * g, l

        return ring_step

    ring = {"mesh": {"data": 2, "sp": 4},
            "shape": [rb, rh, rt, rd]}
    for mode, prefetch in (("rotate_after", False), ("prefetch", True)):
        step = make_ring_step(prefetch)
        prof = profile_compiled(step, jnp.array(q0), k0, v0,
                                label=f"ring_{mode}")
        ms, _ = time_step(step, jnp.array(q0), k0, v0)
        att = attribute(prof, ms / 1000.0)
        ring[mode] = {
            "step_ms": round(ms, 3),
            "collective_permute_count": prof.collectives.get(
                "collective-permute", {}).get("count", 0),
            "comm_fraction": round(att["comm_fraction"], 6),
        }
    o_ra = make_ring_step(False)(jnp.array(q0), k0, v0)
    o_pf = make_ring_step(True)(jnp.array(q0), k0, v0)
    ring["bit_identical"] = bool(
        float(o_ra[1]) == float(o_pf[1])
        and jnp.array_equal(o_ra[0], o_pf[0]))
    ring["prefetch_vs_rotate_after"] = round(
        ring["rotate_after"]["step_ms"]
        / max(ring["prefetch"]["step_ms"], 1e-9), 3)
    detail["ring"] = ring

    # comm-fraction gating: which A/Bs COUNT as overlap evidence (the
    # schedule can only win where comm is a visible step-time share)
    floor = 0.01
    detail["comm_fraction_floor"] = floor
    detail["counted_configs"] = sorted(
        name for name, frac in (
            ("a2a", a2a["alltoall"]["comm_fraction"]),
            ("pipeline", pp["strict"]["comm_fraction"]),
            ("ring", ring["rotate_after"]["comm_fraction"]),
        ) if frac >= floor)
    detail["headline_counted"] = "pipeline" in detail["counted_configs"]

    # the tracked blob: the 2D a2a step (its wire bytes are the
    # LOWER-IS-BETTER comm-growth tripwire)
    detail["profile"] = profiles["alltoall_2d"].to_dict()
    detail["collective_wire_bytes"] = profiles[
        "alltoall_2d"].collective_wire_bytes
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return pp["overlap_vs_strict"]


def mfu(model: str, samples_per_sec: float, precision: str) -> float:
    return (samples_per_sec * TRAIN_FLOPS[model]
            / PRECISION_PEAKS.get(precision, PEAK_BF16_FLOPS))


def measure_ckpt() -> float:
    """Sharded checkpoint save/restore wall time and bytes for the
    composed-LM params at dp×ep (scaleout/ckpt): warm save + restore,
    median of 3 each, through the real Checkpointer (manifest commit,
    retention, telemetry counters included — this is the path a training
    run pays). Returns save MB/s; restore timing, bytes, and chunk count
    land in the stage detail."""
    import tempfile

    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import (
        init_lm_params,
        lm_param_shardings,
        shard_lm_params,
    )
    from deeplearning4j_tpu.scaleout.ckpt import Checkpointer
    from deeplearning4j_tpu.scaleout.ckpt.manifest import read_manifest
    from jax.sharding import Mesh

    if _fast():
        vocab, d, heads, experts, dff, layers = 256, 64, 2, 2, 128, 2
    else:
        vocab, d, heads, experts, dff, layers = (
            LMC_VOCAB, LMC_D, LMC_HEADS, LMC_EXPERTS, LMC_DFF, LMC_LAYERS)

    devs = jax.devices()
    ep = experts if (len(devs) >= experts
                     and len(devs) % experts == 0) else 1
    dp = max(len(devs) // ep, 1)
    mesh = Mesh(np.array(devs[: dp * ep]).reshape(dp, ep),
                ("data", "expert"))

    params = init_lm_params(jax.random.PRNGKey(0), vocab, d, heads, experts,
                            dff, n_layers=layers)
    sharded = shard_lm_params(params, mesh)
    state = {"params": sharded}
    jax.block_until_ready(sharded)  # nothing enqueued before the clocks

    root = tempfile.mkdtemp(prefix="ckpt_bench_")
    ck = Checkpointer(root, keep_last=2)
    ck.save(0, state, mesh=mesh)  # warmup: dir creation, allocator, caches

    def one_save(step):
        t0 = time.perf_counter()
        step_dir = ck.save(step, state, mesh=mesh)
        # graftlint: allow[untimed-dispatch] ck.save fetches every shard via np.asarray and fsyncs the files — host-synchronous IO, nothing enqueued
        return time.perf_counter() - t0, step_dir

    saves = [one_save(i + 1) for i in range(3)]
    save_s = statistics.median(t for t, _ in saves)
    step_dir = saves[-1][1]
    manifest = read_manifest(step_dir)
    n_bytes = manifest.total_bytes
    n_chunks = sum(len(e.chunks) for e in manifest.leaves)

    template = {"params": params}
    shardings = {"params": lm_param_shardings(params, mesh)}

    def one_restore():
        t0 = time.perf_counter()
        restored, _step, _meta = ck.restore(template, shardings)
        jax.block_until_ready(restored)  # fence the device placement
        return time.perf_counter() - t0

    restore_s = statistics.median(one_restore() for _ in range(3))
    mb = n_bytes / 1e6
    detail = {
        "save_ms": round(save_s * 1e3, 2),
        "restore_ms": round(restore_s * 1e3, 2),
        "mb": round(mb, 2),
        "chunks": n_chunks,
        "shard_files": len(manifest.files),
        "mesh": {"data": dp, "expert": ep},
        "save_mb_per_sec": round(mb / save_s, 1),
        "restore_mb_per_sec": round(mb / restore_s, 1),
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return mb / save_s


def measure_ckpt_async() -> float:
    """Step-time jitter at save steps (ISSUE 6): the SAME composed-LM
    training loop checkpointed two ways — blocking ``Checkpointer.save``
    on the training thread vs ``AsyncCheckpointer`` (non-blocking
    device→host copy + background writer). Reported per mode: median
    plain-step ms, median save-step ms, and their difference (the jitter a
    save step adds). Headline = blocking/background save-step overhead
    ratio (>1 means the background writer keeps the training thread
    freer)."""
    import tempfile

    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import (
        init_lm_params,
        make_composed_train_step,
        shard_lm_batch,
        shard_lm_params,
    )
    from deeplearning4j_tpu.scaleout.ckpt import AsyncCheckpointer, Checkpointer
    from jax.sharding import Mesh

    if _fast():
        vocab, d, heads, experts, dff, layers = 256, 64, 2, 2, 128, 2
        batch, seq, steps, save_every = 4, 64, 9, 3
    else:
        vocab, d, heads, experts, dff, layers = (
            LMC_VOCAB, LMC_D, LMC_HEADS, LMC_EXPERTS, LMC_DFF, LMC_LAYERS)
        batch, seq, steps, save_every = LMC_BATCH, LMC_SEQ, 24, 6

    devs = jax.devices()
    ep = experts if (len(devs) >= experts and len(devs) % experts == 0) else 1
    dp = max(len(devs) // ep, 1)
    mesh = Mesh(np.array(devs[: dp * ep]).reshape(dp, ep),
                ("data", "expert"))
    capacity = max((batch // dp) * seq // max(experts // ep, 1), 8)

    def run_mode(background: bool) -> dict:
        params = shard_lm_params(
            init_lm_params(jax.random.PRNGKey(0), vocab, d, heads, experts,
                           dff, n_layers=layers), mesh)
        # non-donating on purpose: an async snapshot must be able to hold
        # the saved buffers while the next step runs
        step = make_composed_train_step(mesh, heads, capacity)
        toks = np.random.default_rng(0).integers(
            0, vocab, (batch, seq + 1))
        tk, tg = shard_lm_batch(toks[:, :-1], toks[:, 1:], mesh)
        params, loss = step(params, tk, tg)  # warmup compile
        jax.block_until_ready(loss)
        root = tempfile.mkdtemp(prefix="ckpt_async_bench_")
        inner = Checkpointer(root, keep_last=2)
        ck = AsyncCheckpointer(inner) if background else inner
        ck.save(0, {"params": params}, mesh=mesh)  # warm the IO path
        plain_ms, save_ms = [], []
        for i in range(1, steps + 1):
            t0 = time.perf_counter()
            params, loss = step(params, tk, tg)
            jax.block_until_ready(loss)
            is_save = i % save_every == 0
            if is_save:
                ck.save(i, {"params": params}, mesh=mesh)
            # graftlint: allow[untimed-dispatch] loss is fenced above; the save tail is host-side IO (the thing this stage measures)
            dt = (time.perf_counter() - t0) * 1000.0
            (save_ms if is_save else plain_ms).append(dt)
        if background:
            ck.flush()
            ck.close()
        plain = statistics.median(plain_ms)
        save = statistics.median(save_ms)
        return {"plain_step_ms": round(plain, 2),
                "save_step_ms": round(save, 2),
                "save_overhead_ms": round(max(save - plain, 0.0), 3)}

    blocking = run_mode(background=False)
    background = run_mode(background=True)
    # floor at 0.1ms (timer noise): a background overhead measured as ~0
    # must not explode the ratio into a meaningless number
    ratio = ((blocking["save_overhead_ms"] + 0.1)
             / (max(background["save_overhead_ms"], 0.0) + 0.1))
    detail = {
        "blocking": blocking,
        "background": background,
        "save_every": save_every,
        "steps": steps,
        "mesh": {"data": dp, "expert": ep},
        "blocking_vs_background_overhead": round(ratio, 2),
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return ratio


def measure_elastic_sync() -> float:
    """The SparkNet experiment (arXiv:1511.06051 §4): accuracy vs sync
    period. K simulated elastic workers train the same total number of
    local steps under ``sync_every`` ∈ {1, 8, 32} (parameter averaging
    every window, the exact ``scaleout.elastic`` round protocol via
    ``simulate_elastic``), and the A/B reports held-out loss per setting
    plus aggregate local steps/s — infrequent averaging buys throughput
    (fewer syncs) at a quantified accuracy cost. Headline = steps/s at
    sync_every=8."""
    from deeplearning4j_tpu.scaleout.elastic import (
        SyntheticRegressionModel,
        simulate_elastic,
    )

    # lr 0.2 keeps training mid-flight at these step counts, so the sync
    # period visibly moves the final loss (the SparkNet trade-off); at
    # small lr every setting converges and the A/B collapses
    if _fast():
        total_steps, workers = 32, 2
        model_kw = dict(d_in=8, d_hidden=16, batch=16, lr=0.2)
    else:
        total_steps, workers = 48, 4
        model_kw = dict(d_in=32, d_hidden=64, batch=128, lr=0.2)

    seeds = list(range(workers))
    results = {}
    for sync_every in (1, 8, 32):
        rounds = max(total_steps // sync_every, 1)
        model = SyntheticRegressionModel(**model_kw)
        t0 = time.perf_counter()
        final, _losses = simulate_elastic(model, seeds, sync_every, rounds)
        # graftlint: allow[untimed-dispatch] simulate_elastic is host-synchronous (device_get per round inside run_steps)
        wall = time.perf_counter() - t0
        results[str(sync_every)] = {
            "rounds": rounds,
            "final_eval_loss": round(model.eval_loss(final), 6),
            "steps_per_sec": round(workers * rounds * sync_every / wall, 1),
        }
    detail = {
        "workers": workers,
        "total_local_steps": total_steps,
        "per_sync_every": results,
        "loss_s1_over_s32": round(
            (results["1"]["final_eval_loss"] + 1e-12)
            / (results["32"]["final_eval_loss"] + 1e-12), 4),
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return results["8"]["steps_per_sec"]


def measure_elastic_trace() -> float:
    """ISSUE 7 overhead budget: distributed tracing threaded through a
    REAL elastic round (master + worker + tracker RPCs + blob publishes +
    flight-recorder checkpoints) must cost <5% vs the identical untraced
    round. Estimator: ONE long-lived cluster, tracing flipped on/off on
    alternating rounds, and the overhead taken as the MEDIAN OF
    ADJACENT-PAIR DELTAS (traced round minus the untraced round right
    before it) — round-level interleaving plus pairing cancels the
    scheduler drift and the ±15% per-round jitter that run-level A/B
    (and even per-arm medians) cannot; the same paired-median discipline
    as the PR 2 metrics budget, one level finer. A second, fully-traced
    short run
    then exercises the forensic chain: span files →
    tools/trace_report.py timeline (every round committed) → Chrome
    export → flight dump. Headline = overhead percent (lower is
    better)."""
    import shutil
    import tempfile
    import threading

    from deeplearning4j_tpu.scaleout.elastic import (
        ElasticMaster,
        ElasticWorker,
        SyntheticRegressionModel,
    )
    from deeplearning4j_tpu.telemetry import trace as trace_mod
    from tools.trace_report import build_timeline, chrome_trace, \
        load_trace_dir

    # rounds sized so local compute dominates (a realistic cadence): the
    # tracing cost per round is O(spans) ≈ fixed, so a too-tiny round
    # would measure artifact IO against nothing but poll sleeps
    if _fast():
        ab_rounds, sync_every, warm = 44, 32, 4
        model_kw = dict(d_in=32, d_hidden=128, batch=256, lr=0.05,
                        mesh_devices=1)
    else:
        ab_rounds, sync_every, warm = 64, 48, 4
        model_kw = dict(d_in=64, d_hidden=256, batch=512, lr=0.05,
                        mesh_devices=1)

    base = tempfile.mkdtemp(prefix="bench_elastic_trace_")

    def start_cluster(tag: str):
        blob = f"file://{base}/blob_{tag}"
        master = ElasticMaster(
            SyntheticRegressionModel(**model_kw), blob,
            sync_every=sync_every, min_workers=1, round_timeout_s=120,
            tick_s=0.0005)  # fine tick: poll quantization would otherwise
        # amplify sub-ms tracing work into a whole extra poll cycle
        worker = ElasticWorker(
            master.address, blob, SyntheticRegressionModel(**model_kw),
            worker_id="w0", worker_seed=1, sync_every=sync_every,
            poll_s=0.0005, round_timeout_s=120)
        t = threading.Thread(target=worker.run, daemon=True)
        t.start()
        master.wait_for_workers(1)
        return master, t

    # ---- A/B: one cluster, tracing alternated per round ----
    tracer = trace_mod.Tracer("master",
                              trace_dir=os.path.join(base, "trace_ab"))
    master, t = start_cluster("ab")
    walls = []  # (traced?, wall) per round, in order
    try:
        for r in range(ab_rounds):
            on = r % 2 == 1
            trace_mod.set_tracer(tracer if on else None)
            master.tracer = tracer if on else None
            t0 = time.perf_counter()
            master.train(1, finish=(r == ab_rounds - 1))
            # graftlint: allow[untimed-dispatch] the elastic round protocol is host-synchronous (run_steps device_gets before publishing); nothing is enqueued when the clock stops
            wall = time.perf_counter() - t0
            if r >= warm:
                walls.append((on, wall))
    finally:
        trace_mod.set_tracer(None)
        master.shutdown()
        t.join(timeout=60)
    # adjacent (plain, traced) pairs → per-pair delta; 20%-trimmed mean
    # over pairs (drops the scheduler-hiccup outliers the shared-CPU box
    # produces, more sample-efficient than the median for the rest)
    deltas = sorted(tw - pw for (p_on, pw), (t_on, tw)
                    in zip(walls[::2], walls[1::2]) if not p_on and t_on)
    trim = len(deltas) // 5
    kept = deltas[trim:len(deltas) - trim] or deltas
    delta = statistics.fmean(kept)
    plain = statistics.median(w for on, w in walls if not on)
    traced = plain + delta
    overhead_pct = delta / plain * 100.0

    # ---- forensic chain smoke: a short fully-traced run ----
    trace_dir = os.path.join(base, "trace_full")
    trace_mod.set_tracer(trace_mod.Tracer("master", trace_dir=trace_dir))
    try:
        master, t = start_cluster("full")
        master.train(4)
        master.shutdown()
        t.join(timeout=60)
    finally:
        trace_mod.set_tracer(None)
    spans = load_trace_dir(trace_dir)
    timeline = build_timeline(spans)
    committed = [r for r in timeline["rounds"] if r["status"] == "committed"]
    chrome = chrome_trace(spans)
    detail = {
        "ab_rounds": ab_rounds,
        "sync_every": sync_every,
        "plain_round_ms": round(plain * 1000, 2),
        "traced_round_ms": round(traced * 1000, 2),
        "overhead_pct": round(overhead_pct, 2),
        "spans": len(spans),
        "rounds_committed_in_report": len(committed),
        "chrome_events": len(chrome["traceEvents"]),
        "flight_dump": os.path.exists(
            os.path.join(trace_dir, "flightrec_master.json")),
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    shutil.rmtree(base, ignore_errors=True)
    return overhead_pct


def measure_ref_micro() -> float:
    """ISSUE 16 bench-noise reference: a fixed deterministic jitted
    matmul+relu loop that NEVER changes across rounds, so its rate
    measures the MACHINE (thermal state, co-tenancy),
    not the code. tools/bench_report.py divides every tracked metric's
    round-over-round delta by this row's drift when the drift is within
    ±10% — a slow bench box stops reading as a code regression — and
    when the reference itself moved MORE than 10% it flags the round
    pair and suppresses regression-gating for it instead (normalizing
    by a broken reference would hide real regressions).

    Sized to be cheap (sub-second compute) but long enough that jit
    dispatch overhead doesn't dominate: one (n,n) fp32 matmul+relu per
    iteration, chained so nothing can be constant-folded away."""
    import jax
    import jax.numpy as jnp

    n = 256 if _fast() else 512
    iters = 80 if _fast() else 200

    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)

    @jax.jit
    def ref_step(x):
        # the /n keeps the chained activations O(1) so 200 iterations
        # can't overflow to inf (an inf would still time the same, but
        # a NaN-guard change elsewhere must not alter this stage's work)
        return jnp.maximum(x @ b, 0.0) * (1.0 / n)

    ref_step(a).block_until_ready()  # compile + warmup outside the clock
    t0 = time.perf_counter()
    x = a
    for _ in range(iters):
        x = ref_step(x)
    x.block_until_ready()
    return iters / (time.perf_counter() - t0)


def measure_serve() -> float:
    """ISSUE 10 serving bench: the continuous-batching decode engine
    (deeplearning4j_tpu/serve/) under the synthetic open-loop traffic
    generator vs the naive recompute-per-token baseline that ``cli
    predict`` used to be.

    Both sides run the SAME bf16-prepared weights (serve/quant.py), so the
    headline ratio isolates what the KV cache + iteration-level batching
    buy, not a dtype change. The naive baseline is the honest fixed-shape
    version of full-forward generation: one jitted full forward over the
    padded decode window per token, batch 1, requests served sequentially
    — O(window) work per token where the decode step does O(1).

    Headline value = engine generated-tokens/sec under the open-loop run;
    the detail carries exact p50/p95/mean request latency (LOWER-IS-BETTER
    rows in tools/bench_report.py — latency growth trips
    ``--fail-on-regression``), the naive baseline rate, the
    ``serve_vs_naive`` ratio (>1 asserted in test_bench_smoke), occupancy,
    and the int8 weight-only-quantized A/B twin (tokens/s + at-rest weight
    bytes vs bf16).

    ISSUE 16 adds the ``fast_path`` block: prefix-cache on/off under
    shared-system-prompt traffic, speculative on/off under the same
    traffic, and chunked-vs-unchunked prefill under a long-prompt
    barrage (with inter-token p99 — chunking's actual win). The ratios
    land as HIGHER-IS-BETTER ``serve_fastpath_*`` rows in
    tools/bench_report.py; the p99s as LOWER-IS-BETTER rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import (
        init_lm_params,
        lm_prefill,
    )
    from deeplearning4j_tpu.serve import (
        DecodeEngine,
        prepare_serve_params,
        run_open_loop,
    )

    if _fast():
        vocab, d, heads, experts, dff, layers = 128, 32, 2, 2, 64, 2
        slots, max_len, max_new, n_req, rate = 4, 64, 8, 12, 400.0
        prompt_lo, prompt_hi = 4, 12
        naive_req = 4
        slo_ms = 25.0
    else:
        vocab, d, heads, experts, dff, layers = LMC_VOCAB, 256, 4, 4, 512, 2
        slots, max_len, max_new, n_req, rate = 8, 256, 32, 32, 50.0
        prompt_lo, prompt_hi = 16, 48
        naive_req = 8
        slo_ms = 250.0

    params = init_lm_params(jax.random.PRNGKey(0), vocab, d, heads, experts,
                            dff, n_layers=layers)
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(0, vocab,
                                rng.randint(prompt_lo, prompt_hi)))
               for _ in range(n_req)]

    # ---- naive recompute-per-token baseline (same bf16 weights): the
    # full-prompt pass re-run per token with the K/V outputs thrown away —
    # exactly the work a cache-less fixed-shape serving loop does ----
    bf16_params = prepare_serve_params(params, "bf16")

    def _naive_next(p, toks, pos):
        logits, _ks, _vs = lm_prefill(p, toks, heads)
        return jnp.argmax(
            jax.lax.dynamic_index_in_dim(logits[0], pos, 0, keepdims=False),
            -1)

    naive_next = jax.jit(_naive_next, donate_argnums=())

    def naive_run(reqs):
        total = 0
        t0 = time.perf_counter()
        for prompt in reqs:
            toks = np.zeros((1, max_len), np.int32)
            toks[0, :len(prompt)] = prompt
            pos = len(prompt) - 1
            for _ in range(max_new):
                nxt = int(np.asarray(  # per-token sync IS the baseline
                    naive_next(bf16_params, jnp.asarray(toks), pos)))
                pos += 1
                toks[0, pos] = nxt
                total += 1
        return total, time.perf_counter() - t0

    naive_run(prompts[:1])  # compile + warmup
    naive_total, naive_t = naive_run(prompts[:naive_req])
    naive_rate = naive_total / naive_t

    # ---- the engine under open-loop load (bf16 headline) ----
    def warm(eng):
        # warm every prefill bucket the traffic will hit (a bucket-length
        # prompt compiles exactly that bucket) + the decode step, outside
        # the timed run
        for b in sorted({eng.bucket_for(len(p)) for p in prompts}):
            eng.generate([1] * min(b, max_len - 1), max_new_tokens=2)

    engine = DecodeEngine(params, heads, n_slots=slots, max_len=max_len,
                          serve_dtype="bf16")
    warm(engine)
    report = run_open_loop(engine, prompts, rate_rps=rate,
                           max_new_tokens=max_new, slo_ms=slo_ms)
    stats = engine.stats()

    # ---- int8 weight-only A/B twin ----
    engine8 = DecodeEngine(params, heads, n_slots=slots, max_len=max_len,
                           serve_dtype="int8")
    warm(engine8)
    report8 = run_open_loop(engine8, prompts[:max(n_req // 2, 2)],
                            rate_rps=rate, max_new_tokens=max_new)

    # ---- watch overhead twins (ISSUES 11/12/18): the SAME bf16
    # open-loop run with each runtime watch armed — the lock-order
    # watchdog (lockwatch: the engine's scheduler lock, the registry
    # under it, and the condition handoff all become watched
    # primitives), the process tracer (every request a serve.request
    # span tree, every scheduler iteration an engine.step span, eager
    # JSONL), and the socket watchdog (netwatch: enforced default
    # timeouts, per-endpoint counters, the blocked-too-long stall
    # dumper). Budgets (asserted in test_bench_smoke with one shared
    # noise retry): <5% tokens/s for lockwatch and netwatch; <10% for
    # tracing in fast mode, where the eager line-buffered JSONL sink —
    # the write-ahead durability posture ISSUE 12 chose on purpose —
    # is a fixed per-span cost that a ~0.1s micro-run can't amortize
    # (full-length runs sit well under 5%).
    #
    # Estimator: SAME-ENGINE paired A/B, median-of-5 per side, rounds
    # alternating off/on back to back, each leg replaying the prompt
    # list up to >=36 requests. One fast-mode open-loop run is ~0.2s
    # on CPU, where a single GC pause reads as ±10% "overhead" — the
    # longer legs amortize that, and five rounds give the median room
    # to shed the stragglers. Comparing a twin engine against the
    # headline engine is also out: an engine driven more often keeps
    # its prefix pages and allocator hotter, which measured as a
    # systematic ~1.5% phantom overhead. So each watch is A/B'd on
    # its OWN engine: the off leg runs with the watch disarmed, the
    # on leg re-runs the same engine armed, and the ratio of the two
    # medians isolates pure arming cost. For lockwatch that means
    # armed accounting vs the disarmed WatchedLock flag check (the
    # interception wrapper itself is a few ns per acquire — built in
    # once, identical on both legs). Engine request ids are per-engine
    # monotonic, so the traced rounds share one trace dir without
    # attribution collisions.
    import tempfile

    from deeplearning4j_tpu.scaleout.remote_tracker import (
        StateTrackerClient,
        StateTrackerServer,
    )
    from deeplearning4j_tpu.telemetry import trace as trace_mod
    from deeplearning4j_tpu.utils import lockwatch, netwatch

    lockwatch.reset()
    lockwatch.enable(raise_on_cycle=True)
    try:
        engine_w = DecodeEngine(params, heads, n_slots=slots,
                                max_len=max_len, serve_dtype="bf16")
        warm(engine_w)
    finally:
        lockwatch.disable()
    trace_dir = tempfile.mkdtemp(prefix="bench_serve_trace_")
    tracer = trace_mod.Tracer("serve-bench", trace_dir=trace_dir)
    engine_t = DecodeEngine(params, heads, n_slots=slots,
                            max_len=max_len, serve_dtype="bf16")
    warm(engine_t)
    netwatch.reset()

    # replay the prompt list so every leg carries >=36 requests (a
    # no-op in full mode, where the headline list is already bigger)
    twin_prompts = prompts * max(1, -(-36 // max(len(prompts), 1)))
    trials = {name: {"off": [], "on": []}
              for name in ("lockwatch", "tracing", "netwatch")}
    report_t = None
    for _ in range(5):
        rep = run_open_loop(engine_w, twin_prompts, rate_rps=rate,
                            max_new_tokens=max_new)
        trials["lockwatch"]["off"].append(round(rep.tokens_per_sec, 1))
        lockwatch.enable(raise_on_cycle=True)
        try:
            rep = run_open_loop(engine_w, twin_prompts, rate_rps=rate,
                                max_new_tokens=max_new)
        finally:
            lockwatch.disable()
        trials["lockwatch"]["on"].append(round(rep.tokens_per_sec, 1))
        rep = run_open_loop(engine_t, twin_prompts, rate_rps=rate,
                            max_new_tokens=max_new)
        trials["tracing"]["off"].append(round(rep.tokens_per_sec, 1))
        prev_tracer = trace_mod.set_tracer(tracer)
        try:
            report_t = run_open_loop(engine_t, twin_prompts, rate_rps=rate,
                                     max_new_tokens=max_new)
        finally:
            trace_mod.set_tracer(prev_tracer)
        trials["tracing"]["on"].append(round(report_t.tokens_per_sec, 1))
        rep = run_open_loop(engine, twin_prompts, rate_rps=rate,
                            max_new_tokens=max_new)
        trials["netwatch"]["off"].append(round(rep.tokens_per_sec, 1))
        netwatch.enable()
        try:
            rep = run_open_loop(engine, twin_prompts, rate_rps=rate,
                                max_new_tokens=max_new)
            # a REAL tracker RPC roundtrip inside the armed window so
            # the detail carries live per-endpoint counters: both the
            # client socket and the server handler socket cross the
            # wrap_socket seam
            with StateTrackerServer() as _tsrv:
                _tcli = StateTrackerClient(_tsrv.address)
                _tcli.add_worker("bench")
                _tcli.increment("netwatch_bench", 1.0)
                _tcli.close()
        finally:
            netwatch.disable()
        trials["netwatch"]["on"].append(round(rep.tokens_per_sec, 1))

    watch = lockwatch.summary()
    watch_rec = lockwatch.metrics_record()
    lockwatch.reset()
    nwatch = netwatch.summary()
    nwatch_rec = netwatch.metrics_record()
    netwatch.reset()
    tracer.close()

    def _paired(name):
        off = sorted(trials[name]["off"])[len(trials[name]["off"]) // 2]
        on = sorted(trials[name]["on"])[len(trials[name]["on"]) // 2]
        return off, on, round((1.0 - on / off) * 100.0, 2)

    lock_base_tps, lock_tps, lockwatch_overhead_pct = _paired("lockwatch")
    trace_base_tps, trace_tps, trace_overhead_pct = _paired("tracing")
    nw_base_tps, nw_tps, netwatch_overhead_pct = _paired("netwatch")

    from tools.trace_report import load_trace_dir, serve_attribution

    attribution = serve_attribution(load_trace_dir(trace_dir))
    # the acceptance sum: queue+prefill+decode+gap within 1ms of latency
    attribution_max_err_ms = max(
        (abs(r["total_ms"] - r["queue_wait_ms"] - r["prefill_ms"]
             - r["decode_ms"] - r["gap_ms"])
         for r in attribution if r["status"] != "open"), default=None)

    # ---- ISSUE 16 fast-path twins: the three serve-engine fast paths
    # A/B'd against the plain engine on the traffic shape each exists
    # for, at a SATURATING offered rate (the paced headline rate keeps
    # both sides idle-bound and the ratio reads pure noise — a capacity
    # A/B has to queue work). All greedy, all token-identical by
    # construction (pinned in tests/test_serve.py) — the twins measure
    # ONLY the speed side.
    #
    # (1) prefix on/off: every request carries the SAME hot page-aligned
    #     system prompt (the fleet shape prefix caching exists for, at
    #     its extreme); the on-engine admits each via full-hit page
    #     seeding — zero prefill dispatches — where the off-engine pays
    #     the full-bucket prefill per request. Short generations keep
    #     the run admission-dominated: that's the phase this path
    #     accelerates.
    # (2) spec on/off: the headline prompt mix, decode-heavy, on a
    #     speculative engine (layer-truncated draft, k=2) vs plain.
    #     accepted_per_verify is the quality number (accepted draft
    #     tokens per verify dispatch); with this bench's random-token
    #     prompts the truncated draft accepts little, so expect the
    #     honest <1 ratio here on CPU — the row exists to track drift.
    # (3) chunked vs unchunked: a long-prompt barrage near the decode
    #     window. Chunking is NOT a throughput play — its win is the
    #     inter-token p99 (decode ticks interleave with prefill chunks
    #     instead of stalling behind a monolithic one), so both p99s
    #     ride along as LOWER-IS-BETTER rows.
    from deeplearning4j_tpu.serve import SpeculativeConfig
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

    if _fast():
        sys_len, page_tokens, n_fp, fp_new = 56, 8, 24, 4
        long_lo, long_hi, n_long, chunk = 40, 49, 6, 8
    else:
        sys_len, page_tokens, n_fp, fp_new = 224, 16, 24, 8
        long_lo, long_hi, n_long, chunk = 160, 201, 8, 32
    spec_k = 2
    sat_rate = 1e5  # all arrivals effectively immediate → queue saturates
    sys_prompt = list(rng.randint(0, vocab, sys_len))
    fp_prompts = [list(sys_prompt) for _ in range(n_fp)]
    long_prompts = [list(rng.randint(0, vocab,
                                     rng.randint(long_lo, long_hi)))
                    for _ in range(n_long)]

    def _twin(prompts_t, new_tokens, warm_hit=False, **engine_kw):
        # fresh registry per twin so counters (prefill dispatches, cache
        # hits, accepts) are this run's alone, not the process total
        eng = DecodeEngine(params, heads, n_slots=slots, max_len=max_len,
                           serve_dtype="bf16", registry=MetricsRegistry(),
                           **engine_kw)
        for b in sorted({eng.bucket_for(len(p)) for p in prompts_t}):
            eng.generate([1] * min(b, max_len - 1), max_new_tokens=2)
        if warm_hit:
            # two generates: the first inserts the system prompt's pages
            # (the resident steady state), the second takes the hit path
            # so seed-from-pages compiles outside the clock
            eng.generate(sys_prompt, max_new_tokens=1)
            eng.generate(sys_prompt, max_new_tokens=1)
        rep = run_open_loop(eng, prompts_t, rate_rps=sat_rate,
                            max_new_tokens=new_tokens)
        return eng, rep

    # median-of-3 per side for the tracked prefix ratio: one saturated
    # run is ~tens of ms on CPU, where a single GC pause flips the
    # ratio's sign — the median is the honest central tendency (all
    # trials land in the detail so a noisy box is visible, not hidden)
    px_off_trials, px_on_trials = [], []
    for _ in range(3):
        _, rep_off = _twin(fp_prompts, fp_new)
        eng_px, rep_px = _twin(fp_prompts, fp_new, warm_hit=True,
                               prefix_cache=True,
                               prefix_page_tokens=page_tokens)
        px_off_trials.append(round(rep_off.tokens_per_sec, 1))
        px_on_trials.append(round(rep_px.tokens_per_sec, 1))
    px_off = sorted(px_off_trials)[1]
    px_on = sorted(px_on_trials)[1]
    _, rep_soff = _twin(prompts, max_new)
    eng_sp, rep_sp = _twin(prompts, max_new,
                           speculative=SpeculativeConfig(k=spec_k))
    _, rep_coff = _twin(long_prompts, max_new)
    _, rep_ch = _twin(long_prompts, max_new, prefill_chunk=chunk)

    px_stats = eng_px.stats()["prefix_cache"]
    sp_stats = eng_sp.stats()["speculative"]
    fast_path = {
        "traffic": {"sys_tokens": sys_len, "n_requests": n_fp,
                    "fp_new_tokens": fp_new, "page_tokens": page_tokens,
                    "long_prompt_range": [long_lo, long_hi - 1],
                    "n_long_requests": n_long, "prefill_chunk": chunk},
        "baseline_tokens_per_sec": px_off,
        "prefix_on_tokens_per_sec": px_on,
        "prefix_on_vs_off": round(px_on / px_off, 3),
        "prefix_trials": {"off": px_off_trials, "on": px_on_trials},
        "cache_hit_rate": round(px_stats["hit_rate"], 4),
        "cache_tokens_reused": px_stats["tokens_reused"],
        "spec_k": spec_k,
        "spec_off_tokens_per_sec": round(rep_soff.tokens_per_sec, 1),
        "spec_on_tokens_per_sec": round(rep_sp.tokens_per_sec, 1),
        "spec_on_vs_off": round(
            rep_sp.tokens_per_sec / rep_soff.tokens_per_sec, 3),
        "accepted_per_verify": round(
            sp_stats["accepted_tokens"]
            / max(1, sp_stats["verify_steps"]), 3),
        "spec_accept_rate": round(sp_stats["accept_rate"], 4),
        "unchunked_tokens_per_sec": round(rep_coff.tokens_per_sec, 1),
        "chunked_tokens_per_sec": round(rep_ch.tokens_per_sec, 1),
        "chunk_vs_unchunked": round(
            rep_ch.tokens_per_sec / rep_coff.tokens_per_sec, 3),
        "inter_token_p99_ms_unchunked": (
            round(rep_coff.inter_token_p99_ms, 2)
            if rep_coff.inter_token_p99_ms is not None else None),
        "inter_token_p99_ms_chunked": (
            round(rep_ch.inter_token_p99_ms, 2)
            if rep_ch.inter_token_p99_ms is not None else None),
    }

    detail = {
        "slots": slots, "max_len": max_len, "n_requests": n_req,
        "max_new_tokens": max_new, "offered_rps": rate,
        "serve_dtype": "bf16",
        "tokens_per_sec": round(report.tokens_per_sec, 1),
        "latency": {
            "p50_ms": round(report.latency_p50_ms, 2),
            "p95_ms": round(report.latency_p95_ms, 2),
            "p99_ms": round(report.latency_p99_ms, 2),
            "mean_ms": round(report.latency_mean_ms, 2),
            "first_token_p50_ms": (
                round(report.first_token_p50_ms, 2)
                if report.first_token_p50_ms is not None else None),
            "first_token_p99_ms": (
                round(report.first_token_p99_ms, 2)
                if report.first_token_p99_ms is not None else None),
        },
        "completed": report.completed,
        # goodput under SLO (ISSUE 15 satellite): requests completing
        # WITHIN slo_ms per second — the HIGHER-IS-BETTER bench_report
        # row (serve_goodput_rps) ROADMAP 2's fleet bench will gate on
        "goodput": {
            "slo_ms": slo_ms,
            "goodput_rps": round(report.goodput_rps, 3),
            "slo_attainment": round(report.slo_attainment, 4),
        },
        "naive_tokens_per_sec": round(naive_rate, 1),
        "naive_requests": naive_req,
        "serve_vs_naive": round(report.tokens_per_sec / naive_rate, 2),
        "occupancy_mean": round(stats["occupancy_mean"], 2),
        "decode_steps": stats["decode_steps"],
        "prefill_buckets": stats["prefill_buckets"],
        "weight_bytes": stats["weight_bytes"],
        "int8": {
            "tokens_per_sec": round(report8.tokens_per_sec, 1),
            "p50_ms": round(report8.latency_p50_ms, 2),
            "weight_bytes": engine8.weight_bytes,
            "weight_bytes_vs_bf16": round(
                engine8.weight_bytes / max(engine.weight_bytes, 1), 3),
        },
        "watch_twin_trials": trials,
        "lockwatch": {
            "overhead_pct": lockwatch_overhead_pct,
            "tokens_per_sec_unwatched": lock_base_tps,
            "tokens_per_sec_watched": lock_tps,
            "cycles": watch["cycles"],
            "watchdog_dumps": watch["watchdog_dumps"],
            "graph": watch["graph"],
            "engine_lock": watch["locks"].get("serve.engine", {}),
            "metrics": watch_rec,
        },
        "tracing": {
            "overhead_pct": trace_overhead_pct,
            "tokens_per_sec_untraced": trace_base_tps,
            "tokens_per_sec_traced": trace_tps,
            "requests_traced": len(attribution),
            "open_requests": sum(1 for r in attribution
                                 if r["status"] == "open"),
            "attribution_max_err_ms": attribution_max_err_ms,
            "latency_p99_ms_traced": round(report_t.latency_p99_ms, 2),
            "sample_attribution": attribution[-1] if attribution else None,
        },
        "netwatch": {
            "overhead_pct": netwatch_overhead_pct,
            "tokens_per_sec_unwatched": nw_base_tps,
            "tokens_per_sec_watched": nw_tps,
            "endpoints": nwatch["endpoints"],
            "stall_dumps": nwatch["stall_dumps"],
            "default_timeout_s": nwatch["default_timeout_s"],
            "metrics": nwatch_rec,
        },
        "fast_path": fast_path,
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return report.tokens_per_sec


def measure_fleet() -> float:
    """ISSUE 19 fleet bench: the multi-replica router (serve/router.py)
    over real TCP-tracker membership, two in-process replicas each
    running the full FleetReplica serve/heartbeat loops.

    Two phases:

    - healthy: the serve-stage open-loop traffic routed through the
      fleet with session keys (affinity exercised), measured exactly
      like ``serve`` so the ``latency``/``goodput`` detail blocks land
      as fleet_latency_* / fleet_goodput_rps rows in bench_report.
    - chaos: a second batch of longer requests, one replica ``die()``d
      mid-stream (no deregistration — the router must detect it off
      heartbeat staleness), a replacement cold-started from live params
      through the burial callback. Every accepted request must complete
      token-identical to a single-engine oracle; the ``requeue`` block
      carries requeue_to_first_token_ms — the recovery-latency number
      this PR's LOWER-IS-BETTER row tracks (how long a client stream
      stalls across a replica death).

    Headline value = healthy-phase generated-tokens/sec through the
    router (fleet_tokens_per_sec)."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import init_lm_params
    from deeplearning4j_tpu.scaleout.remote_tracker import (
        StateTrackerClient,
        StateTrackerServer,
    )
    from deeplearning4j_tpu.serve import (
        DecodeEngine,
        FleetReplica,
        FleetRouter,
        run_open_loop,
    )

    if _fast():
        vocab, d, heads, experts, dff, layers = 128, 32, 2, 2, 64, 2
        slots, max_len, max_new, n_req, rate = 4, 64, 8, 12, 200.0
        prompt_lo, prompt_hi = 4, 12
        slo_ms = 50.0
        chaos_n, chaos_new = 8, 16
    else:
        vocab, d, heads, experts, dff, layers = LMC_VOCAB, 256, 4, 4, 512, 2
        slots, max_len, max_new, n_req, rate = 8, 256, 32, 24, 50.0
        prompt_lo, prompt_hi = 16, 48
        slo_ms = 250.0
        chaos_n, chaos_new = 12, 32

    params = init_lm_params(jax.random.PRNGKey(0), vocab, d, heads, experts,
                            dff, n_layers=layers)
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, vocab,
                                rng.randint(prompt_lo, prompt_hi)))
               for _ in range(n_req)]
    chaos_prompts = [list(rng.randint(0, vocab,
                                      rng.randint(prompt_lo, prompt_hi)))
                     for _ in range(chaos_n)]
    engine_kw = dict(n_slots=slots, max_len=max_len, serve_dtype="bf16")

    def warm(eng):
        for b in sorted({eng.bucket_for(len(p))
                         for p in prompts + chaos_prompts}):
            eng.generate([1] * min(b, max_len - 1), max_new_tokens=2)

    # the single-engine oracle the chaos phase's outputs are pinned to
    oracle = DecodeEngine(params, heads, **engine_kw)
    warm(oracle)
    expected = [oracle.generate(p, max_new_tokens=chaos_new)
                for p in chaos_prompts]

    with StateTrackerServer() as tsrv:
        replicas = []
        for rid in ("r1", "r2"):
            eng = DecodeEngine(params, heads, **engine_kw)
            warm(eng)
            rep = FleetReplica(eng, tsrv.address, rid,
                               heartbeat_s=0.05, poll_s=0.005,
                               publish_s=0.1)
            rep.start()
            replicas.append(rep)

        spawned = []

        def cold_start(_failed_rid):
            # device-to-device replacement: adopt the live tree through
            # the redistribution plans, rejoin the same membership
            rep = FleetReplica.from_live_params(
                params, heads, tsrv.address, "r3",
                engine_kwargs=engine_kw,
                heartbeat_s=0.05, poll_s=0.005, publish_s=0.1)
            rep.start()
            spawned.append(rep)

        rtracker = StateTrackerClient(tsrv.address)
        router = FleetRouter(rtracker, stale_after_s=0.3, dead_after_s=0.8,
                             poll_s=0.005, cold_start=cold_start)
        # let both replicas publish a first heartbeat + load row so the
        # healthy phase starts with full membership
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            router.step()
            if router.fleet_snapshot()["alive"] >= 2:
                break
            time.sleep(0.02)

        # ---- healthy phase: open-loop through the router, with
        # session keys so affinity is on the measured path ----
        sessions = [f"s{i % 4}" for i in range(n_req)]
        report = run_open_loop(router, prompts, rate_rps=rate,
                               max_new_tokens=max_new, slo_ms=slo_ms,
                               sessions=sessions)
        healthy_snap = router.fleet_snapshot()

        # ---- chaos phase: kill r1 once it is mid-stream on at least
        # one request, let the burial requeue + cold-start machinery
        # finish every request anyway ----
        tok0 = replicas[0].engine.stats()["tokens_total"]
        reqs = [router.submit(p, max_new_tokens=chaos_new,
                              session=f"c{i % 3}")
                for i, p in enumerate(chaos_prompts)]
        t_kill = None
        deadline = time.monotonic() + 120.0
        while router.has_work():
            if time.monotonic() > deadline:
                raise TimeoutError("fleet chaos phase did not drain")
            router.step()
            if t_kill is None:
                # kill off the victim's OWN counters, not the router's
                # sweep-sampled view: fires the instant r1 has generated
                # >= 2 chaos tokens while still holding active work, so
                # the death is mid-stream even when a whole request
                # drains between two router sweeps
                st = replicas[0].engine.stats()
                if st["tokens_total"] >= tok0 + 2 and (
                        st["active_slots"] > 0 or st["queue_depth"] > 0):
                    replicas[0].die()
                    t_kill = time.monotonic()
        snap = router.fleet_snapshot()

        requeued = [r for r in reqs if r.requeues > 0]
        gaps_ms = [(r.t_first_after_requeue - r.t_requeue) * 1000.0
                   for r in requeued
                   if r.t_requeue is not None
                   and r.t_first_after_requeue is not None]
        token_identical = all(r.generated == exp
                              for r, exp in zip(reqs, expected))

        for rep in replicas + spawned:
            rep.stop()
        rtracker.close()

    detail = {
        "replicas": 2, "slots": slots, "max_len": max_len,
        "n_requests": n_req, "max_new_tokens": max_new,
        "offered_rps": rate, "serve_dtype": "bf16",
        "tokens_per_sec": round(report.tokens_per_sec, 1),
        "completed": report.completed,
        "latency": {
            "p50_ms": round(report.latency_p50_ms, 2),
            "p95_ms": round(report.latency_p95_ms, 2),
            "p99_ms": round(report.latency_p99_ms, 2),
            "mean_ms": round(report.latency_mean_ms, 2),
            "first_token_p50_ms": (
                round(report.first_token_p50_ms, 2)
                if report.first_token_p50_ms is not None else None),
            "first_token_p99_ms": (
                round(report.first_token_p99_ms, 2)
                if report.first_token_p99_ms is not None else None),
        },
        "goodput": {
            "slo_ms": slo_ms,
            "goodput_rps": round(report.goodput_rps, 3),
            "slo_attainment": round(report.slo_attainment, 4),
        },
        "healthy": {
            "alive": healthy_snap["alive"],
            "dispatches": {r["replica_id"]: r["dispatches"]
                           for r in healthy_snap["replicas"]},
            "affinity_sessions": len(healthy_snap["affinity"]),
        },
        "chaos": {
            "n_requests": chaos_n, "max_new_tokens": chaos_new,
            "killed_replica": "r1",
            "kill_fired": t_kill is not None,
            "completed": sum(1 for r in reqs if r.t_done is not None),
            "requeued_requests": len(requeued),
            "token_identical": token_identical,
            "failed_replicas": snap["failed_replicas"],
            "alive_after": snap["alive"],
            "replacement_joined": any(
                r["replica_id"] == "r3" and r["state"] == "alive"
                for r in snap["replicas"]),
        },
        # the recovery number: how long a requeued client stream waits
        # between its replica dying and its first post-requeue token
        "requeue": {
            "requeued_requests": len(gaps_ms),
            "requeue_to_first_token_ms": (
                round(float(np.mean(gaps_ms)), 2) if gaps_ms else None),
            "requeue_to_first_token_max_ms": (
                round(max(gaps_ms), 2) if gaps_ms else None),
        },
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return report.tokens_per_sec


def measure_observability() -> float:
    """ISSUE 15 watchtower bench: the SAME open-loop decode-engine run
    twice — unarmed vs with the full watch layer armed (a MetricsHistory
    sampler snapshotting the engine registry on a tight cadence plus an
    AlertEngine evaluating the default rule pack over it, both on
    background threads) — so the headline isolates what *being watched*
    costs the serving hot path.

    Headline value = overhead_pct (armed vs unarmed tokens/s; <5%
    budget asserted in test_bench_smoke with the shared noise retry).
    The detail also proves the chain end to end: the armed run's history
    answers live rate/percentile queries, a deterministic injected-fault
    demo drives nonfinite_step_rate and serve_latency_slo_burn through
    pending→firing with transitions logged, and the alert/history JSONL
    artifacts render through the REAL tools/alert_report.py."""
    import tempfile

    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import init_lm_params
    from deeplearning4j_tpu.serve import DecodeEngine, run_open_loop
    from deeplearning4j_tpu.telemetry.alerts import (
        AlertEngine,
        AlertRule,
        default_rules,
    )
    from deeplearning4j_tpu.telemetry.history import MetricsHistory
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

    if _fast():
        vocab, d, heads, experts, dff, layers = 128, 32, 2, 2, 64, 2
        slots, max_len, max_new, n_req, rate = 4, 64, 8, 12, 400.0
        prompt_lo, prompt_hi = 4, 12
    else:
        vocab, d, heads, experts, dff, layers = LMC_VOCAB, 256, 4, 4, 512, 2
        slots, max_len, max_new, n_req, rate = 8, 256, 32, 32, 50.0
        prompt_lo, prompt_hi = 16, 48

    params = init_lm_params(jax.random.PRNGKey(0), vocab, d, heads, experts,
                            dff, n_layers=layers)
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, vocab,
                                rng.randint(prompt_lo, prompt_hi)))
               for _ in range(n_req)]

    def warm(eng):
        for b in sorted({eng.bucket_for(len(p)) for p in prompts}):
            eng.generate([1] * min(b, max_len - 1), max_new_tokens=2)

    # ---- unarmed baseline ----
    reg_base = MetricsRegistry()
    engine = DecodeEngine(params, heads, n_slots=slots, max_len=max_len,
                          serve_dtype="bf16", registry=reg_base)
    warm(engine)
    report = run_open_loop(engine, prompts, rate_rps=rate,
                           max_new_tokens=max_new)

    # ---- armed twin: history sampler + alert evaluator on background
    # threads, sampling/evaluating at a cadence far above production
    # (20Hz/10Hz vs the 1Hz default) so the measured overhead brackets
    # any real deployment ----
    watch_dir = tempfile.mkdtemp(prefix="bench_observability_")
    reg_w = MetricsRegistry()
    engine_w = DecodeEngine(params, heads, n_slots=slots, max_len=max_len,
                            serve_dtype="bf16", registry=reg_w)
    warm(engine_w)
    history = MetricsHistory(
        registry=reg_w, interval_s=0.05,
        spill_path=os.path.join(watch_dir, "history_serve.jsonl"))
    alert_engine = AlertEngine(
        history, rules=default_rules(), registry=reg_w, process="serve",
        interval_s=0.1,
        log_path=os.path.join(watch_dir, "alerts_serve.jsonl"))
    history.start()
    alert_engine.start()
    try:
        report_w = run_open_loop(engine_w, prompts, rate_rps=rate,
                                 max_new_tokens=max_new)
        history.sample_once()  # deterministic final edge for the queries
        states_armed = alert_engine.evaluate_once()
    finally:
        alert_engine.close()
        history.close()
    overhead_pct = round(
        (1.0 - report_w.tokens_per_sec / report.tokens_per_sec) * 100.0, 2)

    # live-query proof off the armed run's real history
    token_rate = history.rate("serve_tokens_total", window_s=300.0)
    p95_windowed = history.percentile_over("serve_request_ms", 95.0,
                                           window_s=300.0)
    quiet = {s["rule"]: s["state"] for s in states_armed}

    # ---- deterministic firing demo: inject the faults the pack watches
    # (guard skips + SLO-busting latencies) into the SAME registry and
    # tick the watch layer — pending→firing transitions land in the log
    # and the alert_report renders them ----
    reg_w.counter("guard_skipped_steps_total").inc(0)
    history.sample_once()
    reg_w.counter("guard_skipped_steps_total").inc(5)
    for _ in range(60):
        reg_w.histogram("serve_request_ms").observe(2600.0)
    time.sleep(0.05)  # a strictly later sample timestamp for the window
    history.sample_once()
    demo_rules = [r for r in default_rules()
                  if r.name in ("nonfinite_step_rate",
                                "serve_latency_slo_burn")]
    demo_engine = AlertEngine(
        history, rules=demo_rules, registry=reg_w, process="serve-demo",
        log_path=os.path.join(watch_dir, "alerts_serve-demo.jsonl"))
    demo_states = {s["rule"]: s["state"]
                   for s in demo_engine.evaluate_once()}
    demo_engine.close()

    from tools.alert_report import collect as alert_collect

    art = alert_collect(watch_dir)
    fired = [t for t in art["transitions"] if t["to"] == "firing"]

    detail = {
        "slots": slots, "max_len": max_len, "n_requests": n_req,
        "offered_rps": rate,
        "tokens_per_sec": round(report.tokens_per_sec, 1),
        "tokens_per_sec_watched": round(report_w.tokens_per_sec, 1),
        "overhead_pct": overhead_pct,
        "history": {
            "samples": int(reg_w.counter("history_samples_total").value),
            "series": int(reg_w.gauge("history_series").value),
            "serve_tokens_rate_per_s": (round(token_rate, 1)
                                        if token_rate is not None
                                        else None),
            "serve_request_p95_windowed_ms": p95_windowed,
        },
        "alerts": {
            "rules": len(default_rules()),
            "quiet_run_firing": sorted(r for r, st in quiet.items()
                                       if st == "firing"),
            "demo_states": demo_states,
            "report_transitions": len(art["transitions"]),
            "report_fired": sorted({t["rule"] for t in fired}),
        },
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return overhead_pct


def measure_runprof() -> float:
    """ISSUE 17 runtime-profiler bench, three proofs in one stage:

    1. **Headline = overhead_pct**: the SAME open-loop decode-engine run
       twice — unarmed vs with the runprof seam armed on the scheduler
       loop (per-tick phase timing + streaming gauge flushes) — the <5%
       budget asserted in test_bench_smoke with the shared noise retry.
    2. **Measured-MFU cross-check**: the composed-flagship single-device
       LM step behind ``runprof=`` for a timed window; the
       ``runprof_measured_mfu`` gauge (XLA FLOPs / fenced device
       seconds / peak) is compared against the same wall-clock MFU
       arithmetic every train stage's headline uses (XLA FLOPs / wall
       step seconds / peak). measured >= wall by construction (the
       fenced device wall excludes host gaps); the ratio lands in the
       detail and tier-1 pins it at test shapes.
    3. **Session -> report chain**: an N-step capture session opened
       over the LM window, the final JSON reloaded through the REAL
       telemetry.runprof.load_session and rendered through the REAL
       tools/profile_report runtime section."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.transformer_lm import (
        init_lm_params,
        make_single_device_train_step,
    )
    from deeplearning4j_tpu.serve import DecodeEngine, run_open_loop
    from deeplearning4j_tpu.telemetry.registry import (
        MetricsRegistry,
        flat_record,
    )
    from deeplearning4j_tpu.telemetry.runprof import (
        RunProfiler,
        load_session,
    )
    from deeplearning4j_tpu.telemetry.xprofile import DEFAULT_PEAK_FLOPS

    if _fast():
        vocab, d, heads, experts, dff, layers = 128, 32, 2, 2, 64, 2
        slots, max_len, max_new, n_req, rate = 4, 64, 8, 12, 400.0
        prompt_lo, prompt_hi = 4, 12
        lm_steps = 24
    else:
        vocab, d, heads, experts, dff, layers = LMC_VOCAB, 256, 4, 4, 512, 2
        slots, max_len, max_new, n_req, rate = 8, 256, 32, 32, 50.0
        prompt_lo, prompt_hi = 16, 48
        lm_steps = 48

    params = init_lm_params(jax.random.PRNGKey(0), vocab, d, heads, experts,
                            dff, n_layers=layers)
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, vocab,
                                rng.randint(prompt_lo, prompt_hi)))
               for _ in range(n_req)]

    def warm(eng):
        for b in sorted({eng.bucket_for(len(p)) for p in prompts}):
            eng.generate([1] * min(b, max_len - 1), max_new_tokens=2)

    # ---- unarmed baseline ----
    reg_base = MetricsRegistry()
    engine = DecodeEngine(params, heads, n_slots=slots, max_len=max_len,
                          serve_dtype="bf16", registry=reg_base)
    warm(engine)
    report = run_open_loop(engine, prompts, rate_rps=rate,
                           max_new_tokens=max_new)

    # ---- armed twin: the runprof seam on the scheduler loop ----
    sess_dir = tempfile.mkdtemp(prefix="bench_runprof_")
    reg_p = MetricsRegistry()
    serve_prof = RunProfiler(registry=reg_p, session_dir=sess_dir)
    engine_p = DecodeEngine(params, heads, n_slots=slots, max_len=max_len,
                            serve_dtype="bf16", registry=reg_p,
                            runprof=serve_prof)
    warm(engine_p)
    report_p = run_open_loop(engine_p, prompts, rate_rps=rate,
                             max_new_tokens=max_new)
    overhead_pct = round(
        (1.0 - report_p.tokens_per_sec / report.tokens_per_sec) * 100.0, 2)
    serve_gauges = {
        k: round(v, 4) for k, v in flat_record(
            reg_p, prefixes=("runprof_",)).items()}

    # ---- measured-MFU cross-check on the composed-flagship LM step,
    # with the capture session riding the same window ----
    lm_reg = MetricsRegistry()
    lm_prof = RunProfiler(registry=lm_reg, update_every=4,
                          session_dir=sess_dir)
    lm_step = make_single_device_train_step(heads, donate=True,
                                            runprof=lm_prof)
    toks = jax.random.randint(jax.random.PRNGKey(2),
                              (2, (256 if _fast() else LMC_SEQ) + 1),
                              0, vocab)
    tk, tg = toks[:, :-1], toks[:, 1:]
    lm_params = init_lm_params(jax.random.PRNGKey(1), vocab, d, heads,
                               experts, dff, n_layers=layers)
    lm_params = jax.tree_util.tree_map(jnp.array, lm_params)
    lm_params, loss = lm_step(lm_params, tk, tg)  # compile + AOT profile
    float(loss)
    sid = lm_prof.start_session(steps=lm_steps)
    t0 = time.perf_counter()
    for _ in range(lm_steps):
        lm_params, loss = lm_step(lm_params, tk, tg)
    float(loss)
    wall_step_s = (time.perf_counter() - t0) / lm_steps
    lm_prof.stop_session()  # idempotent vs the steps=N auto-stop

    xprof = lm_step.step_profile
    measured_mfu = flat_record(lm_reg, prefixes=("runprof_",)).get(
        "runprof_measured_mfu")
    wall_mfu = (xprof.flops / wall_step_s / DEFAULT_PEAK_FLOPS
                if xprof is not None and xprof.flops else None)

    # ---- session -> report chain, through the real readers ----
    final_path = lm_prof.sessions_completed[-1]
    sess = load_session(final_path)
    from tools.profile_report import render_runtime_text

    rendered = render_runtime_text([sess])
    summ = sess.get("summary") or {}

    detail = {
        "slots": slots, "max_len": max_len, "n_requests": n_req,
        "offered_rps": rate,
        "tokens_per_sec": round(report.tokens_per_sec, 1),
        "tokens_per_sec_runprof": round(report_p.tokens_per_sec, 1),
        "overhead_pct": overhead_pct,
        "serve_gauges": serve_gauges,
        "lm_steps": lm_steps,
        "wall_step_ms": round(wall_step_s * 1000.0, 3),
        "measured_mfu": (round(measured_mfu, 6)
                         if measured_mfu is not None else None),
        "wall_mfu": round(wall_mfu, 6) if wall_mfu is not None else None,
        "measured_vs_wall_mfu": (round(measured_mfu / wall_mfu, 4)
                                 if measured_mfu and wall_mfu else None),
        "session": {
            "id": sid,
            "steps": summ.get("steps"),
            "partial": sess.get("partial"),
            "device_ms_mean": summ.get("device_ms_mean"),
            "host_ms_mean": summ.get("host_ms_mean"),
            "session_mfu": summ.get("measured_mfu"),
            "chrome_events": len(sess.get("chrome_trace") or []),
            "report_rendered": ("runtime sessions" in rendered
                                and str(sid) in rendered),
        },
    }
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return overhead_pct


def measure_autotune() -> float:
    """ISSUE 20 roofline-guided autotuner A/B: run the real two-phase
    search (AOT-profile every candidate, prune strictly-dominated
    configs without ever executing them, wall-clock only the Pareto
    frontier with paired-median timing) on the composed LM step and the
    decode engine, and report the winner's tuned-vs-default step-time
    ratio. The LM seam's candidates flow through the SAME ``tuned=``
    seam the cache feeds (make_single_device_train_step(tuned=cfg)), so
    the headline measures the production adoption path, not a side
    harness, and every candidate that cannot reproduce the default's
    numerics is disqualified before it can win.

    Headline = LM tuned_vs_default, which is >= 1.0 by construction
    (the default config is always a candidate, so the worst case is
    "tuning found nothing better"). On a CPU round the margin can sit
    inside the ref_micro +/-10% noise band; the detail marks that case
    informational instead of claiming a win.
    """
    from deeplearning4j_tpu.tune import seams as tune_seams
    from deeplearning4j_tpu.tune.search import search
    from deeplearning4j_tpu.tune.space import get_space

    fast = _fast()
    repeats = 3 if fast else 5

    def _run(h):
        return search(get_space(h.seam), h.context, h.default_config,
                      h.compile_fn, h.measure_fn, h.outputs_match,
                      repeats=repeats)

    lm = _run(tune_seams.lm_seam(seq_len=128 if fast else 256,
                                 n_layers=1 if fast else 2))
    sv = _run(tune_seams.serve_seam(n_prompts=3 if fast else 6,
                                    max_new_tokens=4 if fast else 8))

    detail: dict = {"seams": {}, "repeats": repeats}
    for res in (lm, sv):
        detail["seams"][res.seam] = {
            "default": res.default_config,
            "winner": res.winner_config,
            "tuned_vs_default": (round(res.tuned_vs_default, 4)
                                 if res.tuned_vs_default else None),
            "counts": res.counts,
            "rank_correlation": (round(res.rank_correlation, 3)
                                 if res.rank_correlation is not None
                                 else None),
        }
    headline = lm.tuned_vs_default or 1.0
    # informational flag: a sub-10% margin is within the band
    # bench_report treats as machine drift (the ref_micro reference),
    # so a CPU round should read the headline as "search ran, default
    # held" rather than as a measured speedup
    detail["headline_within_noise"] = bool(headline - 1.0 < 0.10)
    detail["note"] = (
        "tuned_vs_default >= 1.0 by construction (default is always a "
        "candidate); headline_within_noise=true means the margin is "
        "inside the ref_micro +/-10% drift band and is informational"
    )
    print("STAGE_DETAIL " + json.dumps(detail), flush=True)
    return headline



# ---------------------------------------------------------------------------
# Stage orchestration. Each stage is `python bench.py --stage NAME`, run by
# main() in a subprocess with a timeout, so a wedged XLA compile is contained.

def _fast() -> bool:
    return os.environ.get("BENCH_FAST") == "1"


def _split_stage(name: str) -> tuple:
    """'conv_wide_bf16' → ('conv', 'bf16'); 'mlp_fp32_true' → ('mlp',
    'fp32_true'); 'attn_long_bf16[_densecore]' → ('attn_long', 'bf16');
    'lm_composed[_densecore]' → ('lm_composed', 'fp32')."""
    if name.startswith("lm_composed"):
        # the flagship LM runs f32 params at DEFAULT matmul precision
        return "lm_composed", "fp32"
    if name.startswith("conv_wide_"):
        precision = name[len("conv_wide_"):]
        if precision.endswith("_im2col"):
            precision = precision[: -len("_im2col")]
        return "conv", precision
    for prefix, variants in (("attn_long_", ("_densecore",)),
                             ("lstm_wide_", ("_nokernels",)),
                             ("mlp_", ("_nofused",))):
        if name.startswith(prefix):
            precision = name[len(prefix):]
            for v in variants:
                if precision.endswith(v):
                    precision = precision[: -len(v)]
            return prefix[:-1], precision
    model, _, precision = name.partition("_")
    return model, precision


def _attn_long_memory_detail() -> dict:
    """Compiled temp-allocation footprint of the T=2048 train step with the
    blockwise core vs the materializing dense core — the O(T)-memory
    evidence for the long-context claim (no execution; the shared
    telemetry/xprofile.py compiled-step introspection of the exact jitted
    program)."""
    import jax

    from deeplearning4j_tpu.nn import functional as F
    from deeplearning4j_tpu.ops.flash_attention import set_attention_impl
    from deeplearning4j_tpu.telemetry.xprofile import profile_compiled

    conf = _conf("attn_long")
    params = F.init_params(conf, jax.random.PRNGKey(0))
    states = F.init_train_state(conf, params)
    x, y = _make_data("attn_long", 1, 2)
    out = {}
    for impl in ("blockwise", "dense"):
        set_attention_impl(impl)
        try:
            step = F.make_train_step(conf)
            prof = profile_compiled(step, params, states, 0, x[0], y[0],
                                    jax.random.PRNGKey(1),
                                    label=f"attn_long_{impl}")
            if prof.temp_bytes is not None:
                out[f"{impl}_temp_mb"] = round(prof.temp_bytes / 1e6, 1)
        finally:
            set_attention_impl(None)
    return out


def run_stage(name: str) -> float:
    steps = 2 * CHUNK if _fast() else None
    if name in ("cpu_mlp_fp32", "cpu_word2vec", "cpu_word2vec_large",
                "cpu_lm_composed"):
        if name == "cpu_mlp_fp32":
            return measure("mlp", "fp32", steps=CHUNK,
                           batch=64 if _fast() else None)
        name = name[len("cpu_"):]
        if name == "lm_composed":
            # forced-CPU baseline: SAME stage, blockwise core, tiny batch
            # (a CPU full-shape step is seconds — per-sample rate is what
            # the vs_cpu ratio needs); telemetry A/B only on the main stage
            os.environ["DL4J_TPU_ATTN_IMPL"] = "blockwise"
            return measure_lm_composed(batch=None if _fast() else 1,
                                       telemetry=False)
    if name.startswith("lm_composed"):
        # the env seam (not set_attention_impl) on purpose: proves the
        # no-code-edit switch the driver's dryrun can use too
        os.environ["DL4J_TPU_ATTN_IMPL"] = (
            "dense" if name.endswith("_densecore") else "blockwise")
        return measure_lm_composed(
            telemetry=not name.endswith("_densecore"))
    if name == "ckpt":
        return measure_ckpt()
    if name == "ckpt_async":
        return measure_ckpt_async()
    if name == "elastic_sync":
        return measure_elastic_sync()
    if name == "elastic_trace":
        return measure_elastic_trace()
    if name == "guardrails":
        return measure_guardrails()
    if name == "profile":
        return measure_profile()
    if name == "optimizer":
        return measure_optimizer()
    if name == "moe":
        return measure_moe()
    if name == "comm_overlap":
        return measure_comm_overlap()
    if name == "ref_micro":
        return measure_ref_micro()
    if name == "serve":
        return measure_serve()
    if name == "fleet":
        return measure_fleet()
    if name == "observability":
        return measure_observability()
    if name == "runprof":
        return measure_runprof()
    if name == "autotune":
        return measure_autotune()
    if name == "word2vec":
        if _fast():
            return measure_word2vec(n_sentences=100, sent_len=20, vocab=200)
        return measure_word2vec()
    if name == "word2vec_sharded":
        from deeplearning4j_tpu.parallel.mesh import data_parallel_mesh
        import jax

        mesh = data_parallel_mesh(min(len(jax.devices()), 8))
        if _fast():
            return measure_word2vec(n_sentences=100, sent_len=20, vocab=200,
                                    mesh=mesh)
        return measure_word2vec(mesh=mesh)
    if name == "word2vec_large":
        if _fast():
            return measure_word2vec(n_sentences=200, sent_len=20, vocab=500,
                                    layer_size=64, batch_size=4096)
        return measure_word2vec(n_sentences=20_000, sent_len=100,
                                vocab=50_000, layer_size=256,
                                batch_size=65_536)
    if name == "mlp_bf16_nofused":
        # A/B: the MLP stage with the pallas fused-dense epilogue forced off
        from deeplearning4j_tpu.ops.pallas_kernels import set_fused_dense

        set_fused_dense(False)
        return measure("mlp", "bf16", steps=steps,
                       batch=64 if _fast() else None)
    model, precision = _split_stage(name)
    if model == "conv" and name.endswith("_im2col"):
        # A/B: the legacy im2col slice+einsum conv core (rounds 2-4) on the
        # same stage — quantifies the round-5 switch to the conv emitter
        from deeplearning4j_tpu.nn.layers.convolution import set_conv_emitter

        set_conv_emitter(False)
        return measure("conv", precision, steps=steps,
                       batch=8 if _fast() else None)
    if model == "attn_long":
        if name.endswith("_densecore"):
            # A/B: force the (T,T)-materializing core on the same model
            from deeplearning4j_tpu.ops.flash_attention import (
                set_attention_impl,
            )

            set_attention_impl("dense")
        rate = measure(model, precision, steps=8 if _fast() else None,
                       batch=2 if _fast() else None)
        if not name.endswith("_densecore") and not _fast():
            print("STAGE_DETAIL " + json.dumps(_attn_long_memory_detail()),
                  flush=True)
        return rate
    if model == "lstm_wide":
        if name.endswith("_nokernels"):
            # A/B: identical stage, pallas kernels forced off
            from deeplearning4j_tpu.ops.pallas_kernels import (
                set_fused_dense,
                set_lstm_gates,
            )

            set_fused_dense(False)
            set_lstm_gates(False)
        return measure(model, precision, steps=16 if _fast() else None,
                       batch=8 if _fast() else None)
    return measure(model, precision, steps=steps,
                   batch=64 if _fast() else None)


# (stage, per-stage cap seconds). CPU baseline runs FIRST: it is the
# vs_baseline denominator and must land even if a chip stage runs long.
# The caps date from the r04/r05 chip runs and include each stage's cold
# compile; on a local chip with the compile cache warm: not measured.
STAGES = [
    # the ISSUE 16 noise reference runs before everything: its rate is
    # the machine-drift denominator bench_report normalizes every other
    # row by, so it must land even on a round that later runs out of
    # budget (and running first means it samples the same box state the
    # expensive stages are about to see)
    ("ref_micro", 60),
    ("cpu_mlp_fp32", 180),
    ("mlp_bf16", 180),
    ("mlp_bf16_nofused", 150),
    ("mlp_fp32", 150),
    ("mlp_fp32_true", 150),
    ("lenet_bf16", 150),
    ("conv_wide_bf16", 170),
    ("conv_wide_bf16_im2col", 150),
    ("lstm_bf16", 170),
    ("lstm_fp32", 130),
    ("lstm_wide_bf16", 200),
    ("lstm_wide_bf16_nokernels", 170),
    ("attn_bf16", 170),
    ("attn_long_bf16", 220),
    ("attn_long_bf16_densecore", 170),
    ("cpu_lm_composed", 280),
    ("lm_composed", 280),
    ("lm_composed_densecore", 240),
    ("ckpt", 150),
    ("ckpt_async", 200),
    ("elastic_sync", 200),
    ("elastic_trace", 200),
    ("guardrails", 220),
    ("profile", 220),
    ("optimizer", 240),
    ("moe", 220),
    ("comm_overlap", 240),
    ("serve", 300),
    ("fleet", 300),
    ("observability", 240),
    ("runprof", 260),
    ("autotune", 420),
    ("cpu_word2vec", 150),
    ("word2vec", 120),
    ("word2vec_sharded", 150),
    ("cpu_word2vec_large", 300),
    ("word2vec_large", 200),
]


def _flush_partial(detail: dict) -> None:
    with open(PARTIAL_PATH, "w") as f:
        json.dump(detail, f, indent=1)


def _spawn(stage: str, timeout: float) -> tuple:
    """Run one stage in a subprocess; (rate, split_dict|None, error|None)."""
    env = dict(os.environ)
    if stage.startswith("cpu_"):
        # JAX_PLATFORMS=cpu in the child's environment would hold it to the
        # CPU just as well; the child flips jax.config before first backend
        # use, keyed off this variable, until the benchmark is replaced
        # (ROADMAP Speed item 1).
        env["BENCH_FORCE_CPU"] = "1"
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--stage", stage],
            capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
        )
    except subprocess.TimeoutExpired:
        return None, None, f"timeout>{timeout:.0f}s"
    rate, split = None, None
    for line in out.stdout.splitlines():
        if line.startswith("STAGE_RESULT "):
            rate = float(line.split()[1])
        elif line.startswith("W2V_SPLIT "):
            split = json.loads(line[len("W2V_SPLIT "):])
        elif line.startswith("STAGE_DETAIL "):
            split = json.loads(line[len("STAGE_DETAIL "):])
    if rate is not None:
        return rate, split, None
    tail = (out.stderr or out.stdout or "").strip().splitlines()[-3:]
    return None, None, f"rc={out.returncode}: " + " | ".join(tail)


def main() -> None:
    default_budget = sum(cap for _, cap in STAGES) + 60
    budget = float(os.environ.get("BENCH_BUDGET_SEC", str(default_budget)))
    deadline = time.monotonic() + budget
    detail: dict = {
        "precision_note": (
            "fp32 = DEFAULT matmul precision (one bf16 MXU pass; measured "
            "153.5 TF/s on 4096^3 vs 185.7 bf16 — tools/"
            "probe_matmul_precision.py); fp32_true = HIGHEST (bf16x6, "
            "29.7 TF/s). Each MFU is vs its own peak: bf16/fp32 197 TF/s, "
            "fp32_true 32.8 TF/s."
        ),
    }

    # BENCH_ONLY="a,b" runs just those stages through the same budget/
    # subprocess discipline — how test_bench_smoke guards a new stage
    # without paying for the whole suite
    only = [s.strip() for s in os.environ.get("BENCH_ONLY", "").split(",")
            if s.strip()]
    for stage, cap in STAGES:
        if only and stage not in only:
            continue
        if "word2vec" in stage:
            key = f"{stage}_words_per_sec"
        elif stage == "ckpt":
            key = f"{stage}_save_mb_per_sec"
        elif stage == "ckpt_async":
            key = f"{stage}_blocking_vs_background"
        elif stage == "elastic_sync":
            key = f"{stage}_steps_per_sec"
        elif stage in ("elastic_trace", "guardrails", "profile",
                       "observability", "runprof"):
            key = f"{stage}_overhead_pct"
        elif stage == "optimizer":
            # replicated/sharded compiled peak-bytes ratio: >1 means the
            # ZeRO-sharded update's footprint is smaller (tracked by
            # bench_report; the sharded blob's absolute peak rides the
            # LOWER-IS-BETTER optimizer_profile_peak_bytes row)
            key = f"{stage}_peak_bytes_ratio"
        elif stage in ("moe", "serve", "fleet"):
            key = f"{stage}_tokens_per_sec"
        elif stage == "comm_overlap":
            # strict/overlapped pp step-time ratio (>1 = overlap faster)
            key = f"{stage}_overlap_vs_strict"
        elif stage == "autotune":
            # default/tuned LM step-time ratio (>1 = search found a
            # faster numerics-identical config; 1.0 = default held)
            key = f"{stage}_tuned_vs_default"
        else:
            key = f"{stage}_samples_per_sec"
        remaining = deadline - time.monotonic()
        if remaining < 25:
            detail[key] = None
            detail[f"{stage}_status"] = "skipped_budget"
            _flush_partial(detail)
            continue
        rate, split, err = _spawn(stage, min(cap, remaining - 5))
        if rate is None:
            detail[key] = None
            detail[f"{stage}_status"] = f"failed: {err}"
            print(f"bench stage {stage} FAILED: {err}", file=sys.stderr)
        else:
            detail[key] = round(rate, 1)
            if split:
                subkey = ("host_device_split" if "word2vec" in stage
                          else "detail")
                detail[f"{stage}_{subkey}"] = split
            model, precision = _split_stage(stage)
            if model in TRAIN_FLOPS:
                detail[f"{stage}_mfu"] = round(mfu(model, rate, precision), 4)
        _flush_partial(detail)

    cpu = detail.get("cpu_mlp_fp32_samples_per_sec")
    value = detail.get("mlp_bf16_samples_per_sec")
    if value is None:  # fall back so the line always carries a number
        value = detail.get("mlp_fp32_samples_per_sec") or 0.0
    vs = round(value / cpu, 2) if (cpu and value) else None
    w2v_tpu = detail.get("word2vec_words_per_sec")
    w2v_cpu = detail.get("cpu_word2vec_words_per_sec")
    if w2v_tpu and w2v_cpu:
        detail["word2vec_vs_cpu"] = round(w2v_tpu / w2v_cpu, 2)
    w2vl_tpu = detail.get("word2vec_large_words_per_sec")
    w2vl_cpu = detail.get("cpu_word2vec_large_words_per_sec")
    if w2vl_tpu and w2vl_cpu:
        detail["word2vec_large_vs_cpu"] = round(w2vl_tpu / w2vl_cpu, 2)
    w2vs = detail.get("word2vec_sharded_words_per_sec")
    if w2vs and w2v_tpu:
        detail["word2vec_sharded_vs_single"] = round(w2vs / w2v_tpu, 2)
    co = detail.get("comm_overlap_detail", {})
    if co:
        # lift the stage's two other A/B ratios to tracked top-level rows
        # (the headline already carries pp overlap_vs_strict)
        if "a2a" in co:
            detail["comm_overlap_a2a_2d_vs_flat"] = co["a2a"]["2d_vs_flat"]
        if "ring" in co:
            detail["comm_overlap_ring_prefetch_vs_rotate_after"] = \
                co["ring"]["prefetch_vs_rotate_after"]
    at = detail.get("autotune_detail", {})
    sv_ratio = ((at.get("seams") or {}).get("serve") or {}).get(
        "tuned_vs_default")
    if sv_ratio:
        # lift the serve engine's tuned-vs-default to a tracked row next
        # to the LM headline (both HIGHER-IS-BETTER, >= 1.0 by design)
        detail["autotune_serve_tuned_vs_default"] = sv_ratio
    rp = detail.get("runprof_detail", {})
    if rp and rp.get("measured_mfu") is not None:
        # lift the cross-check MFU to a tracked top-level row so
        # bench_report trends it next to runprof_overhead_pct
        detail["runprof_measured_mfu"] = rp["measured_mfu"]
    lmc = detail.get("lm_composed_samples_per_sec")
    lmc_dense = detail.get("lm_composed_densecore_samples_per_sec")
    if lmc and lmc_dense:
        detail["lm_composed_vs_densecore"] = round(lmc / lmc_dense, 2)
    lmc_cpu = detail.get("cpu_lm_composed_samples_per_sec")
    if lmc and lmc_cpu:
        detail["lm_composed_vs_cpu"] = round(lmc / lmc_cpu, 2)
    detail["lm_composed_note"] = (
        "lm_composed = the multi-block (n_layers=2) transformer-LM "
        "flagship (causal MHA + top-2 MoE FFN, T=2048, d_model=512, "
        "V=2048, E=4 dense experts) trained end to end on one chip with "
        "the blockwise flash core forced via DL4J_TPU_ATTN_IMPL; "
        "_densecore is the same stage with the (T,T)-materializing core; "
        "cpu_lm_composed is the same blockwise stage in a forced-CPU "
        "child (batch=1). MFU is vs the fp32-DEFAULT peak; dense_moe "
        "executes all E experts per token and the FLOP model counts that."
    )
    detail["moe_note"] = (
        "moe = one grouped MoE layer (top-2 router + E expert FFNs, "
        "E = G x expert-axis size) trained on a dp×ep mesh, A/B-ing the "
        "two dispatch impls (parallel/moe.py): alltoall = GShard capacity "
        "exchange (tokens sharded over the expert axis too, comm "
        "proportional to E·C·d), replicated = replicated-token compute + "
        "dense psum combine (comm O(n_row·d) regardless of occupancy). "
        "Value is alltoall tokens/s at G=4; the detail blob carries every "
        "(impl, G) config's tokens/s, estimated per-device comm bytes, "
        "capacity, and measured drop fraction."
    )
    detail["comm_overlap_note"] = (
        "comm_overlap = ISSUE 14 comm/compute-overlap A/Bs: (1) flat vs "
        "hierarchical 2D MoE all_to_all on dp×ep (the expert axis "
        "factorized per arXiv:2112.01075 — identical routed values, two "
        "group-factorized exchange definitions replacing each flat one), "
        "(2) strict vs double-buffered-overlap pipeline ticks on dp×pp "
        "(ppermute of tick t's output issued while tick t+1 computes; "
        "bit-identical loss+params), (3) rotate-after vs prefetch ring "
        "attention on dp×sp (bit-identical). Value is the strict/"
        "overlapped pp step-time ratio; each config records its compiled "
        "StepProfile comm fraction, and counted_configs gates which A/Bs "
        "are claimable as overlap wins (CPU collectives are memcpys, so "
        "ratios there are informational). The 2D a2a step profile embeds "
        "as the stage blob; comm_overlap_collective_wire_bytes rides the "
        "LOWER-IS-BETTER bench_report row."
    )
    detail["serve_note"] = (
        "serve = ISSUE 10 decode engine (deeplearning4j_tpu/serve/): the "
        "flagship LM generating under a synthetic open-loop (Poisson) "
        "traffic generator through the KV-cached continuous-batching "
        "scheduler, bf16 weights. Value is generated tokens/s; the detail "
        "carries exact p50/p95 request latency (LOWER-IS-BETTER rows in "
        "bench_report), the naive recompute-per-token baseline at the SAME "
        "bf16 weights (one full forward over the padded window per token, "
        "sequential — what cli predict used to do), the serve_vs_naive "
        "ratio, mean slot occupancy, the int8 weight-only A/B twin "
        "(serve_dtype seam, serve/quant.py), and the ISSUE 12 tracing "
        "twin: the same open-loop run with request-scoped spans armed "
        "(trace_overhead_pct <5% budget) plus the per-request latency "
        "attribution reconstructed through tools/trace_report.py. "
        "Latency rows carry p50/p95/p99 (ISSUE 12: the SLO tail)."
    )
    detail["word2vec_sharded_note"] = (
        "word2vec_sharded = the toy word2vec stage driven through "
        "make_sharded_sgns_step on the data-parallel mesh (pair batches "
        "sharded over the data axis, one in-graph psum per step over ICI) "
        "— the next lever the r05 word2vec note called out; "
        "word2vec_sharded_vs_single compares it to the single-chip "
        "device-epoch stage at the same corpus."
    )
    detail["guardrails_note"] = (
        "guardrails = ISSUE 8 numerical-fault guard A/B: the composed-"
        "flagship single-device step with the in-graph guard (loss/grad "
        "finiteness + skip-on-nonfinite select, optimize/guardrails.py) "
        "vs the identical unguarded step, paired-median overhead percent "
        "(<5% budget, asserted in test_bench_smoke); the detail's "
        "recovery block demos an injected-NaN batch being skipped "
        "(params carried bitwise, finite) and replayed from its bundle "
        "via tools/step_replay.py."
    )
    detail["runprof_note"] = (
        "runprof = ISSUE 17 runtime-profiler A/B: the open-loop serve "
        "stage unarmed vs with the runprof= seam timing every scheduler "
        "tick (telemetry/runprof.py ring buffers + streaming gauges), "
        "overhead percent (<5% budget, asserted in test_bench_smoke); "
        "the detail carries the composed-LM measured-MFU cross-check "
        "(runprof_measured_mfu gauge — XLA FLOPs / fenced device "
        "seconds — vs the wall-clock MFU arithmetic; measured >= wall "
        "by construction) and an N-step capture session reloaded and "
        "rendered through the real load_session/profile_report chain. "
        "runprof_measured_mfu rides its own tracked row."
    )
    detail["profile_note"] = (
        "profile = ISSUE 9 compiled-step profiler A/B: the composed-"
        "flagship single-device step behind the profile= seam "
        "(telemetry/xprofile.py — AOT lower/compile once, StepProfile "
        "captured from XLA cost/memory analysis + the HLO collective "
        "inventory, then the SAME executable every call) vs the identical "
        "plain step, paired-median overhead percent (<5% budget, asserted "
        "in test_bench_smoke). The detail embeds the StepProfile blob, "
        "the analytic-vs-XLA FLOPs cross-check, the measured-MFU/roofline "
        "attribution, and the memory-watermark sampler pass; "
        "tools/profile_report.py diffs these blobs across rounds."
    )
    detail["optimizer_note"] = (
        "optimizer = ISSUE 13 in-graph optimizer A/B on the composed "
        "dp×ep flagship: SGD vs Adam(replicated update) vs Adam/LAMB "
        "(ZeRO-style update-sharded per arXiv:2004.13336 — each dp "
        "replica stores+updates 1/dp of the moments and allgathers "
        "params; optimize/updaters.py). Value is the replicated/sharded "
        "compiled peak-bytes ratio (>1 = sharded smaller); the detail "
        "carries per-config steps/s + StepProfile footprint + measured "
        "per-replica moment bytes, the sharded-vs-replicated parity "
        "check at identical math, and the sharded Adam profile blob "
        "(optimizer_profile_peak_bytes, LOWER-IS-BETTER in bench_report)."
    )
    detail["ckpt_note"] = (
        "ckpt = sharded save/restore (scaleout/ckpt) of the composed-LM "
        "params at dp×ep through the real Checkpointer (per-shard npz + "
        "atomic manifest + retention); value is save MB/s, detail carries "
        "restore MB/s, bytes, and chunk/file counts."
    )
    detail["attn_note"] = (
        "attn_bf16 (T=64, d=256) is the r04-continuity stage and is "
        "model-bound at that sequence length (the score matmuls are 64x64; "
        "the dense core is correct there — blockwise dispatch starts at "
        "T>=1024). attn_long_bf16 (T=2048, d_model=512) is the "
        "representative long-context stage: blockwise core, O(T) temps "
        "(see attn_long_bf16_detail), with the _densecore twin as the A/B."
    )
    detail["word2vec_note"] = (
        "r05 attribution (on-chip ablations, models/word2vec.py): scatter-"
        "adds were 67-69% of the r04 SGNS epoch at both scales, row-"
        "serialized; shared negatives (pWord2Vec recipe) + window-reduced "
        "center rows cut scatter/gather row ops ~4x, and fit() no longer "
        "downloads the embedding tables (device-authoritative, lazy host "
        "sync — the 2x51 MB download WAS the large-scale drain). Single "
        "chip: 119k -> 890k words/s on the identical toy stage (7.5x "
        "r04); the same code also lifts the 1-core XLA-CPU baseline "
        "(55.8k -> 154k), and at the realistic scale (V=50k, D=256, 2M "
        "words) the chip holds ~800k vs 41k CPU — the row-op bound "
        "crushes a single core while the chip streams it. SGNS at D<=256 "
        "has ~0 MXU content; the next lever is the data-parallel mesh "
        "path (make_sharded_sgns_step, psum over ICI), not more "
        "single-chip row-op tuning."
    )
    print(json.dumps({
        "metric": "mnist_mlp_train_samples_per_sec_per_chip",
        "value": value,
        "unit": "samples/sec",
        "vs_baseline": vs,
        "detail": detail,
    }))


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--stage":
        from deeplearning4j_tpu.utils.compile_cache import ensure_compile_cache

        # stage children take the chip in turn and share one compile cache
        ensure_compile_cache()
        if os.environ.get("BENCH_FORCE_CPU") == "1":
            import jax

            jax.config.update("jax_platforms", "cpu")
            if sys.argv[2] in ("moe", "word2vec_sharded", "optimizer",
                               "comm_overlap"):
                # mesh stages need multiple devices; fake 8 CPU devices
                # BEFORE first backend use (same trick as tests/conftest)
                jax.config.update("jax_num_cpu_devices", 8)
        if sys.argv[2].endswith("_fp32_true"):
            import jax

            # must precede tracing: HIGHEST = bf16x6 passes ~ true fp32
            jax.config.update("jax_default_matmul_precision", "highest")
        print("STAGE_RESULT", run_stage(sys.argv[2]), flush=True)
    else:
        main()
