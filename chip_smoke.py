#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the flagship path once, in ONE process, through the entry points a
user calls, at the flagship's full width (depth as the benchmark cuts it):

  train   init_lm_params -> make_single_device_train_step(donate=True)
  save    Checkpointer.save with lm_checkpoint_meta
  serve   the CLI (``predict --model <ckpt dir>``), then the same prompts
          again on one warm engine: same tokens, nothing compiled
  blockdiff a block-diffusion model (the benchmark's cell at its rehearsal
          size) through the same engine: prefill, two blocks a request,
          every recorded forward against the plain reference
  kernels every Pallas kernel at a shape that takes its Pallas branch,
          forward and gradient against the lax reference, and a check that
          the lowered module holds a Mosaic custom call
  legacy  one MultiLayerNetwork.fit on the char-LSTM that uses them
  mesh    with four devices, the composed ("data", "expert") step on a
          2 x 2 mesh against the one-chip step on the same batch

It needs a TPU: with any other platform it exits non-zero before it builds
anything, and it has no switch that admits the CPU. A failed check raises,
which is a non-zero exit; only a run in which every phase passed prints the
two closing lines: ``summary: {...}`` (per-phase detail, also written to
``chiprun_out/chip_smoke/summary.json``, ending in ``"claim": null``) and,
last, ``{"ok": true, "device": {"platform", "kind", "count"}}`` with those
keys and no others. Wall times are set-up information (they include
compilation); nothing here is a rate or a utilization.
"""

from __future__ import annotations

import collections
import contextlib
import faulthandler
import io
import json
import logging
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# the widths bench.py names for the flagship (LMC_*): one chip holds them whole
FLAGSHIP = dict(vocab=2048, d_model=512, n_heads=4, n_experts=4, d_ff=1024,
                n_layers=2, seq=2048, batch=4)
SERVE = dict(slots=8, max_len=2048, max_new_tokens=32,
             prompt_lens=(16, 24, 40, 48, 1100))
# (m, k, n) of fused_dense; (batch, hidden) of lstm_gates, the second at the
# largest hidden the gate admits; (B, H, T, D) of the flash kernel
KERNELS = dict(dense=(512, 1024, 1024), lstm=((256, 512), (256, 2048)),
               flash=(4, 4, 2048, 128),
               # the routed expert layer at the benchmark's flagship widths:
               # (rows, type, gradients too) of the sat cell's 2,048-row
               # prefill, and the gradients once at the fewest rows that
               # route (no training program calls the routed form)
               routed=dict(d_model=2048, d_ff=1024, n_experts=64, top_k=8,
                           calls=((2048, "bfloat16", False),
                                  (512, "float32", True))))
LEGACY_VOCAB = 512
# the benchmark's block-diffusion cell at its rehearsal size: prompts of
# every remainder mod the block length (one shorter than a block), two
# blocks of answer each
BLOCKDIFF = dict(cell="serve-blockdiff-sat", prompt_lens=(5, 8, 10, 3, 7),
                 max_new_tokens=8, seed=3)

# TPU default precision runs an f32 matmul as one bf16 MXU pass (eps 2^-8 per
# operand), in Mosaic and in XLA alike, and the two round in different
# places: matmul-bearing kernels agree with an f32 "highest" reference to a
# few 1e-3 of its largest magnitude, not to the CPU tests' 1e-5.
TOL_MATMUL = 2e-2
# lstm_gates is elementwise f32; Mosaic and XLA differ in their exp/tanh
TOL_ELEMENTWISE = 1e-4
# one-chip against 2 x 2 mesh: same bf16-pass rounding in another order, and
# a near-tied router logit may send a token to another expert
TOL_MESH_LOSS = 2e-2

# the rehearsal model is float32, which the chip multiplies in one bf16 pass:
# its logits (of order 1) stand a few 1e-2 from the reference's, so a token
# may be another near-tied one; an altered token reads 3.3 and 0.49 (CPU)
TOL_BLOCKDIFF = {"widest_logit_gap": 0.25, "mean_logit_gap": 0.02,
                 "widest_confidence_gap": 0.25, "schedule_faults": 0.0}

HARD_LIMIT_S = 1100  # the contract allows 1200 s


class SmokeFailure(AssertionError):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# --------------------------------------------------------- compile watch ----

class CompileWatch:
    """Counts compile requests, persistent-cache hits and writes through
    jax.monitoring, and names the programs from jax._src.compiler's DEBUG
    lines, which it swallows. Reporting only: what must not compile is
    pinned by retrace_guard, whose event also fires on a cache hit."""

    _HIT = re.compile(r"Persistent compilation cache hit for '([^']+)'")
    _MISS = re.compile(r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'")

    def __init__(self):
        import jax

        self.requests = self.hits = self.writes = 0
        self.hit_names: list = []
        self.miss_names: list = []
        jax.monitoring.register_event_listener(self._on_event)
        logger = logging.getLogger("jax._src.compiler")
        logger.setLevel(logging.DEBUG)
        logger.addFilter(self._filter)

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.writes += 1  # fires when an entry is written

    def _filter(self, record: logging.LogRecord) -> bool:
        msg = record.getMessage()
        for pattern, names in ((self._HIT, self.hit_names),
                               (self._MISS, self.miss_names)):
            m = pattern.search(msg)
            if m:
                names.append(m.group(1))
                return False
        return record.levelno > logging.DEBUG

    def snapshot(self) -> tuple:
        return (self.requests, self.hits, self.writes, len(self.hit_names),
                len(self.miss_names))

    def since(self, snap: tuple) -> dict:
        """What happened after ``snap``: counts (a request that neither hit
        nor was written compiled under JAX's write threshold), and program
        name -> count of the programs found in the cache (``hit``) and
        looked up but not found (``missed``)."""
        requests, hits, writes, n_hit, n_miss = snap

        def tally(names):
            return dict(sorted(collections.Counter(names).items()))

        return {"requests": self.requests - requests,
                "hits": self.hits - hits,
                "writes": self.writes - writes,
                "hit": tally(self.hit_names[n_hit:]),
                "missed": tally(self.miss_names[n_miss:])}


# ------------------------------------------------------------------ phases ----

def seeded_batch(dims: dict, seed: int = 1):
    import numpy as np

    toks = np.random.default_rng(seed).integers(
        0, dims["vocab"], size=(dims["batch"], dims["seq"] + 1),
        dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def fresh_params(dims: dict, seed: int = 0):
    import jax

    from deeplearning4j_tpu.models.transformer_lm import init_lm_params

    # jitted: one program in place of one per leaf
    init = jax.jit(lambda key: init_lm_params(
        key, dims["vocab"], dims["d_model"], dims["n_heads"],
        dims["n_experts"], dims["d_ff"], n_layers=dims["n_layers"]))
    return init(jax.random.PRNGKey(seed))


def run_steps(step, params, tokens, targets, steps: int, label: str):
    """``steps`` calls of ``step`` with the params rebound each time (the
    step may donate them). Every loss finite, the last lower than the
    first, nothing compiled after the second call."""
    import math

    import jax

    from deeplearning4j_tpu.utils.retrace_guard import retrace_guard

    check(steps >= 3, f"{label}: needs >= 3 steps, got {steps}")
    losses = []
    for _ in range(2):
        params, loss = step(params, tokens, targets)
        losses.append(float(loss))
    with retrace_guard(0, label=f"{label} steady state"):
        for _ in range(steps - 2):
            params, loss = step(params, tokens, targets)
            losses.append(float(loss))
    jax.block_until_ready(params)
    check(all(math.isfinite(x) for x in losses),
          f"{label}: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"{label}: loss did not fall over {steps} steps: {losses}")
    return params, losses


def phase_train(dims: dict, steps: int = 5):
    """A1: the single-device step with donated params on a seeded batch."""
    from deeplearning4j_tpu.models.transformer_lm import (
        make_single_device_train_step,
    )

    tokens, targets = seeded_batch(dims)
    step = make_single_device_train_step(dims["n_heads"], donate=True)
    return run_steps(step, fresh_params(dims), tokens, targets, steps,
                     "train")


def phase_save(params, dims: dict, root: str, step: int) -> str:
    """A2: a committed sharded checkpoint the serving path can load."""
    from deeplearning4j_tpu.models.transformer_lm import lm_checkpoint_meta
    from deeplearning4j_tpu.scaleout.ckpt.checkpointer import Checkpointer
    from deeplearning4j_tpu.scaleout.ckpt.reshard import latest_step_dir

    shutil.rmtree(root, ignore_errors=True)  # this phase's own directory
    Checkpointer(root).save(step, {"params": params},
                            meta=lm_checkpoint_meta(params, dims["n_heads"]))
    step_dir = latest_step_dir(root)
    check(step_dir is not None, f"no committed checkpoint under {root}")
    return step_dir


def seeded_prompts(lens, vocab: int, seed: int = 2) -> list:
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in lens]


def phase_serve(root: str, dims: dict, serve: dict, work_dir: str) -> dict:
    """A3: the CLI's predict path in-process, then the same prompts twice on
    one engine built the way the CLI builds it."""
    from deeplearning4j_tpu.cli import driver
    from deeplearning4j_tpu.serve.engine import DecodeEngine
    from deeplearning4j_tpu.utils.retrace_guard import retrace_guard

    prompts = seeded_prompts(serve["prompt_lens"], dims["vocab"])
    new = serve["max_new_tokens"]
    os.makedirs(work_dir, exist_ok=True)
    prompts_path = os.path.join(work_dir, "prompts.txt")
    out_path = os.path.join(work_dir, "generations.txt")
    with open(prompts_path, "w", encoding="utf-8") as f:
        f.writelines(" ".join(map(str, p)) + "\n" for p in prompts)

    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        rc = driver.main(["predict", "--model", root, "--input", prompts_path,
                          "--output", out_path, "--verbose",
                          "--slots", str(serve["slots"]),
                          "--max-len", str(serve["max_len"]),
                          "--max-new-tokens", str(new)])
    check(rc == 0, f"cli predict exited {rc}")
    with open(out_path, "r", encoding="utf-8") as f:
        cli_tokens = [[int(t) for t in line.split()] for line in f]
    counted = re.search(r"decode engine: (\d+) tokens", said.getvalue())
    check(counted is not None, f"cli printed no token count: {said.getvalue()!r}")
    check(int(counted.group(1)) == len(prompts) * new,
          f"cli tokens_total {counted.group(1)} != {len(prompts)} x {new}")
    check([len(t) for t in cli_tokens] == [new] * len(prompts),
          f"cli generations are not {len(prompts)} x {new} tokens: "
          f"{[len(t) for t in cli_tokens]}")
    check(all(0 <= t < dims["vocab"] for row in cli_tokens for t in row),
          "cli generated a token outside [0, vocab)")

    def generate(engine):
        reqs = [engine.submit(p, max_new_tokens=new) for p in prompts]
        engine.run_until_idle()
        check(all(r.done.is_set() and r.finish_reason == "max_new_tokens"
                  for r in reqs),
              f"requests did not complete: "
              f"{[(r.rid, r.finish_reason) for r in reqs]}")
        return [list(r.generated) for r in reqs]

    engine = DecodeEngine.from_checkpoint(root, n_slots=serve["slots"],
                                          max_len=serve["max_len"])
    first = generate(engine)
    check(first == cli_tokens, "engine and cli disagree on greedy tokens")
    with retrace_guard(0, label="serve: same prompts again"):
        again = generate(engine)
    check(again == first, "the same prompts gave other tokens the second time")
    stats = engine.stats()
    check(stats["tokens_total"] == 2 * len(prompts) * new,
          f"engine tokens_total {stats['tokens_total']}")
    buckets = sorted({engine.bucket_for(len(p)) for p in prompts})
    return {"requests": len(prompts), "tokens_per_request": new,
            "prefill_buckets_used": buckets,
            "serve_dtype": stats["serve_dtype"]}


def _overlay(base: dict, cut: dict) -> None:
    for key, value in cut.items():
        if isinstance(value, dict) and isinstance(base.get(key), dict):
            _overlay(base[key], value)
        else:
            base[key] = value


def phase_blockdiff(spec: dict, tol: dict) -> dict:
    """A block-diffusion model through the normal serving path, at the
    rehearsal size of the benchmark's cell and built by the cell's own model
    file: prefill of the prompt's whole blocks, two blocks of answer a
    request (denoising forwards, a commit between them), and agreement of
    every recorded forward with the plain reference."""
    from benchmark.harness import registry
    from deeplearning4j_tpu.telemetry.registry import MetricsRegistry
    from deeplearning4j_tpu.utils.retrace_guard import retrace_guard

    cell = registry.load_cell(registry.load_benchmark(), spec["cell"])
    config, traffic = cell["config_data"], cell["traffic_data"]
    for data in (config, traffic):
        _overlay(data, data.pop("rehearse"))
    model = registry.load_model(cell)
    engine = model.build_serve(config, spec["seed"], MetricsRegistry())
    prompts = seeded_prompts(spec["prompt_lens"], config["vocab_size"])
    new = spec["max_new_tokens"]

    def generate():
        reqs = [engine.submit(p, max_new_tokens=new) for p in prompts]
        engine.run_until_idle()
        check(all(r.done.is_set() and r.finish_reason == "max_new_tokens"
                  and len(r.generated) == new for r in reqs),
              f"requests did not complete: "
              f"{[(r.rid, r.finish_reason, len(r.generated)) for r in reqs]}")
        return reqs

    reqs = generate()
    kinds = [[f[2] for f in r.forwards] for r in reqs]
    check(all("commit" in k and k[-1] == "denoise" for k in kinds),
          f"a request's forwards are not blocks with a commit between: {kinds}")
    with retrace_guard(0, label="block diffusion: same prompts again"):
        again = generate()
    check([r.generated for r in again] == [r.generated for r in reqs],
          "the same prompts gave other tokens the second time")
    compared = model.serve_compare(config, traffic, spec["seed"], reqs)
    for name, (value, _) in compared.items():
        check(value <= tol[name],
              f"block diffusion against the reference: {name} {value:.4g} "
              f"over {tol[name]}")
    return {"requests": len(reqs), "forwards": sum(map(len, kinds)),
            "compared": {k: float(f"{v:.4g}") for k, (v, _) in
                         compared.items()}}


def _expects_mosaic() -> bool:
    """Pallas interprets on the CPU backend only (tier-1 at toy shapes);
    stated here, not read from pallas_kernels, so that a kernel module that
    interprets on the chip fails the check."""
    import jax

    return jax.default_backend() != "cpu"


def _rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _lower_out_and_grads(fn, args):
    """Lowered ``args -> (fn(*args), d mean(out^2) / d args)``."""
    import jax
    import jax.numpy as jnp

    def loss(*a):
        out = fn(*a)
        return sum(jnp.mean(jnp.square(o.astype(jnp.float32)))
                   for o in jax.tree_util.tree_leaves(out)), out

    def out_and_grads(*a):
        (_, out), grads = jax.value_and_grad(
            loss, tuple(range(len(args))), has_aux=True)(*a)
        return out, grads

    return jax.jit(out_and_grads).lower(*args)


def _compare(name: str, fn, ref_fn, args, tol: float, compiled: bool) -> dict:
    """fn against ref_fn at "highest" matmul precision: outputs, and the
    gradient of mean(out^2) in every argument, each as one jitted program.
    ``compiled`` says whether fn's program must hold a Mosaic custom call
    (a silent give-way to the lax reference holds none)."""
    import jax

    lowered = _lower_out_and_grads(fn, args)
    check(("tpu_custom_call" in lowered.as_text()) == compiled,
          f"{name}: the lowered program "
          f"{'holds no' if compiled else 'holds a'} Mosaic custom call")
    out, grads = lowered.compile()(*args)
    with jax.default_matmul_precision("highest"):
        ref, ref_grads = _lower_out_and_grads(ref_fn, args).compile()(*args)
    leaves = jax.tree_util.tree_leaves
    err = max(_rel_err(o, r) for o, r in zip(leaves(out), leaves(ref)))
    gerr = max(_rel_err(g, r) for g, r in zip(grads, ref_grads))
    check(err <= tol and gerr <= tol,
          f"{name}: error {err:.3g} (forward) {gerr:.3g} (gradient) over "
          f"tolerance {tol:g}")
    return {"branch": "mosaic" if compiled else "interpret",
            "fwd_err": float(f"{err:.3g}"), "grad_err": float(f"{gerr:.3g}"),
            "tol": tol}


def _rel_l2(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def _routed_against_dense(shape: dict, tol: float) -> dict:
    """``routed_moe`` against ``dense_moe``, the all-experts form it stands
    in for, at shapes the chooser routes: values, and where a call says so
    the gradient in the input, the router and every expert leaf. Both at the
    chip's default precision, so both route alike; errors are norms over a
    whole array, which one token routed elsewhere on a tie does not decide."""
    import jax

    from deeplearning4j_tpu.models import transformer_lm as lm

    d, f, n_experts, top_k = (shape[k] for k in
                              ("d_model", "d_ff", "n_experts", "top_k"))
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    normal = lambda *s: jax.random.normal(next(keys), s)  # noqa: E731
    router = normal(d, n_experts) / d ** 0.5
    experts = {"w1": normal(n_experts, d, f) / d ** 0.5,
               "b1": 0.1 * normal(n_experts, f),
               "w2": normal(n_experts, f, d) / f ** 0.5,
               "b2": 0.1 * normal(n_experts, d)}
    report = {}
    for n, dtype, with_grads in shape["calls"]:
        name = f"routed_moe_{n}_{dtype}"
        check(lm._routes(n, top_k, n_experts),
              f"{name}: the chooser would not route this call")
        args = jax.tree_util.tree_map(
            lambda a: a.astype(dtype), (router, experts, normal(n, d)))

        def run(form):
            fn = lambda *a: form(*a, top_k)  # noqa: E731
            if not with_grads:
                return jax.jit(fn)(*args), ()
            out, grads = _lower_out_and_grads(fn, args).compile()(*args)
            return out, jax.tree_util.tree_leaves(grads)

        out, grads = run(lm.routed_moe)
        ref, ref_grads = run(lm.dense_moe)
        err = _rel_l2(out, ref)
        gerr = max((_rel_l2(g, r) for g, r in zip(grads, ref_grads)),
                   default=0.0)
        check(err <= tol and gerr <= tol,
              f"{name}: error {err:.3g} (forward) {gerr:.3g} (gradient) "
              f"over tolerance {tol:g}")
        report[name] = {"branch": "routed", "fwd_err": float(f"{err:.3g}"),
                        "grad_err": float(f"{gerr:.3g}"), "tol": tol}
    return report


def phase_kernels(shapes: dict) -> dict:
    """F: each Pallas kernel at a shape that takes its Pallas branch, and the
    routed expert layer against the all-experts one."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.ops.dtypes import BF16_COMPUTE
    from deeplearning4j_tpu.ops.flash_attention import (
        attention_core,
        dense_attention,
    )

    compiled = _expects_mosaic()
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 16))
    normal = lambda *shape: jax.random.normal(next(keys), shape)  # noqa: E731
    report = {}

    m, k, n = shapes["dense"]
    x, w, b = normal(m, k), normal(k, n) / k ** 0.5, normal(n)
    dense = lambda x, w, b: pk.fused_dense(x, w, b, "relu")  # noqa: E731
    dense_ref = lambda x, w, b: pk._dense_ref(  # noqa: E731
        x.astype(jnp.float32), w.astype(jnp.float32), b.astype(jnp.float32),
        "relu")
    check(pk._dense_shapes_ok(x, w), f"fused_dense gate refuses {shapes['dense']}")
    report["fused_dense_f32"] = _compare(
        "fused_dense f32", dense, dense_ref, (x, w, b), TOL_MATMUL, compiled)
    low = tuple(a.astype(BF16_COMPUTE.compute_dtype) for a in (x, w, b))
    check(pk._dense_shapes_ok(*low[:2]), "fused_dense gate refuses bf16")
    report["fused_dense_bf16"] = _compare(
        "fused_dense bf16", dense, dense_ref, low, TOL_MATMUL, compiled)

    for batch, hidden in shapes["lstm"]:
        ifog, c_prev = normal(batch, 4 * hidden), normal(batch, hidden)
        report[f"lstm_gates_{batch}x{hidden}"] = _compare(
            f"lstm_gates ({batch}, {hidden})", pk.lstm_gates,
            pk._lstm_gates_ref, (ifog, c_prev), TOL_ELEMENTWISE, compiled)

    if shapes.get("flash"):
        q, kk, v = (normal(*shapes["flash"]) for _ in range(3))
        report["flash_attention"] = _compare(
            "attention_core(impl='flash')",
            lambda q, k, v: attention_core(q, k, v, causal=True, impl="flash"),
            lambda q, k, v: dense_attention(q, k, v, causal=True),
            (q, kk, v), TOL_MATMUL, compiled)
    if shapes.get("routed"):
        report.update(_routed_against_dense(shapes["routed"], TOL_MATMUL))
    return report


def phase_legacy_fit(vocab: int, batch: int = 64, seq: int = 64) -> dict:
    """One fit of the legacy facade on the char-LSTM, whose recurrence goes
    through lstm_gates."""
    import math

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import char_lstm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    toks = jax.random.randint(jax.random.PRNGKey(4), (batch, seq + 1), 0,
                              vocab)
    x = jax.nn.one_hot(toks[:, :-1], vocab, dtype=jnp.float32)
    y = jax.nn.one_hot(toks[:, 1:], vocab, dtype=jnp.float32)
    net = MultiLayerNetwork(char_lstm(vocab=vocab)).init()
    compiled = _expects_mosaic()
    check(("tpu_custom_call" in jax.jit(net.output).lower(x).as_text())
          == compiled,
          "char_lstm forward lowering: Mosaic custom call "
          f"{'missing' if compiled else 'present'}")
    before = net.score(x, y)
    net.fit(x, y)
    after = net.score(x, y)
    check(math.isfinite(before) and math.isfinite(after) and after < before,
          f"char_lstm score did not fall to a finite value: "
          f"{before} -> {after}")
    check(bool(jnp.all(jnp.isfinite(net.params()))),
          "char_lstm params not finite after fit")
    return {"score_before": float(f"{before:.4g}"),
            "score_after": float(f"{after:.4g}")}


def phase_mesh(dims: dict, one_chip_losses: list, steps: int = 3) -> dict:
    """G: the composed step on a ("data", "expert") 2 x 2 mesh, on the batch
    and from the params the one-chip step started from."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from deeplearning4j_tpu.models.transformer_lm import (
        make_composed_train_step,
        shard_lm_batch,
        shard_lm_params,
    )

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices).reshape(2, 2), ("data", "expert"))
    # every route of a data row fits one expert's buffer: nothing is dropped
    capacity = dims["batch"] * dims["seq"] // 2
    tokens, targets = shard_lm_batch(*seeded_batch(dims), mesh)
    params = shard_lm_params(fresh_params(dims), mesh)
    step = make_composed_train_step(mesh, dims["n_heads"], capacity,
                                    donate=True)
    params, losses = run_steps(step, params, tokens, targets, steps, "mesh")
    gaps = [abs(a - b) for a, b in zip(losses, one_chip_losses)]
    check(max(gaps) <= TOL_MESH_LOSS,
          f"mesh losses {losses} leave the one-chip losses "
          f"{one_chip_losses[:steps]} by {max(gaps):.3g} > {TOL_MESH_LOSS}")
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            params["blocks"]["experts"]):
        held = {s.device for s in leaf.addressable_shards}
        check(held == set(devices),
              f"expert leaf {jax.tree_util.keystr(path)} sits on "
              f"{len(held)} device(s), not four")
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    check(all(b > 0 for b in in_use), f"a device holds nothing: {in_use}")
    return {"mesh": dict(mesh.shape), "capacity": capacity,
            "losses": [float(f"{x:.5g}") for x in losses],
            "max_loss_gap_to_one_chip": float(f"{max(gaps):.3g}"),
            "tol": TOL_MESH_LOSS}


# -------------------------------------------------------------------- main ----

def _versions() -> dict:
    from importlib import metadata

    out = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            out[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            out[dist] = "not installed"
    return out


def verdict_line(device: dict) -> str:
    """The last line of standard output: these two keys and no others, the
    device as JAX reports it. Whoever checks the run parses this line alone;
    the per-phase detail goes on the ``summary:`` line before it."""
    return json.dumps({"ok": True,
                       "device": {"platform": str(device["platform"]),
                                  "kind": str(device["kind"]),
                                  "count": int(device["count"])}})


def main() -> int:
    t_start = time.monotonic()
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend here is "
              f"{backend!r} ({jax.devices()[0].device_kind}). The chip is "
              "reached through the chip tool; tests/ is what runs on the CPU.",
              file=sys.stderr)
        return 1
    # a hang becomes a traceback and a non-zero exit inside the time limit
    faulthandler.dump_traceback_later(HARD_LIMIT_S, exit=True)
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}

    from deeplearning4j_tpu.native.lib import native_available
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    watch = CompileWatch()
    watch_start = watch.snapshot()
    versions = _versions()
    native = native_available()
    print(f"platform: {device['platform']}  device_kind: {device['kind']}  "
          f"devices: {device['count']}")
    print("versions: " + "  ".join(f"{k} {v}" for k, v in versions.items()))
    print(f"compile cache: {cache_dir}")
    print(f"native_available: {native}")
    print(f"use_fused_dense (auto gate): {pk.use_fused_dense()} at "
          f"{jax.device_count()} device(s)")

    phases: dict = {}
    setup_wall_s: dict = {}
    cache: dict = {}

    def run(name: str, fn, *args, **kw):
        snap, t0 = watch.snapshot(), time.monotonic()
        result = fn(*args, **kw)
        setup_wall_s[name] = round(time.monotonic() - t0, 1)
        cache[name] = watch.since(snap)
        c = cache[name]
        print(f"phase {name}: ok  [set-up information: {setup_wall_s[name]} s "
              f"wall, compilation included; {c['requests']} compile "
              f"requests, {c['hits']} cache hits {c['hit']}, "
              f"{c['requests'] - c['hits']} compiled {c['missed']}, "
              f"{c['writes']} written]", flush=True)
        return result

    params, losses = run("train", phase_train, FLAGSHIP)
    phases["train"] = {"steps": len(losses),
                       "losses": [float(f"{x:.5g}") for x in losses]}
    ckpt_root = os.path.join(OUT_DIR, "ckpt")
    step_dir = run("save", phase_save, params, FLAGSHIP, ckpt_root,
                   len(losses))
    phases["save"] = {"step_dir": os.path.relpath(step_dir, HERE)}
    del params
    phases["serve"] = run("serve", phase_serve, ckpt_root, FLAGSHIP, SERVE,
                          OUT_DIR)
    phases["blockdiff"] = run("blockdiff", phase_blockdiff, BLOCKDIFF,
                              TOL_BLOCKDIFF)
    # the kernels are called directly: the use_fused_dense() auto gate, off on
    # a host with more than one chip, is not in their way
    phases["kernels"] = run("kernels", phase_kernels, KERNELS)
    phases["legacy_fit"] = run("legacy_fit", phase_legacy_fit, LEGACY_VOCAB)
    if device["count"] >= 4:
        phases["mesh"] = run("mesh", phase_mesh, FLAGSHIP, losses)
    else:
        phases["mesh"] = f"skipped ({device['count']} devices)"
        print(f"mesh: {phases['mesh']}")

    shutil.rmtree(ckpt_root)  # ~50 MB of weights; the summary is what returns
    total = watch.since(watch_start)
    summary = {
        "ok": True,
        "device": device,
        "versions": versions,
        "compile_cache": {"dir": cache_dir, "requests": total["requests"],
                          "hits": total["hits"], "writes": total["writes"],
                          "by_phase": cache},
        "native_available": native,
        "phases": phases,
        "setup_wall_s": {**setup_wall_s,
                         "total": round(time.monotonic() - t_start, 1)},
        "claim": None,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w",
              encoding="utf-8") as f:
        json.dump(summary, f, indent=1)
    faulthandler.cancel_dump_traceback_later()
    print("summary: " + json.dumps(summary))
    print(verdict_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
