"""Pipeline parallelism: GPipe-style microbatched stage pipeline.

The reference has no pipeline parallelism (SURVEY.md §2.5: data parallelism
is its only axis); this is a TPU-idiomatic extension completing the
dp/tp/sp/pp axis set. Each device on the "pipe" mesh axis owns one STAGE
(a contiguous group of identical layers); activations flow stage-to-stage
via ``ppermute`` (ICI neighbor hops) while microbatches stream in, so at
steady state every stage computes a different microbatch — the classic
(M + S − 1)-tick schedule with S−1 bubble ticks.

Scope: homogeneous stages (same activation shape in and out, e.g. a stack
of d→d DENSE layers between an input projection and a head), which is the
shape-uniformity pipelining itself requires. Differentiation works through
the whole schedule (``ppermute`` transposes to the reverse permutation), so
``jax.grad`` of a loss on the pipeline output yields exact gradients for
every stage's parameters — validated against the sequential forward in
tests.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

PIPE_AXIS = "pipe"


def _pipeline_body(stage_params, x_mbs, stage_fn, axis_name: str,
                   overlap: bool = False):
    """Per-device schedule under shard_map.

    stage_params: this stage's params (leading stage axis of size 1 removed
    by the caller's specs — each leaf arrives as its own stage's slice).
    x_mbs: (M, mb, ...) microbatches — any trailing activation shape (d) for
    dense stacks, (T, d) for sequence models — replicated over the pipe axis
    (only stage 0 reads them). Returns (M, mb, ...): the pipeline output,
    replicated via psum (only the last stage contributes non-zeros).

    ``overlap=False`` is the STRICT tick schedule (M + S − 1 ticks): each
    tick computes a stage and then ppermutes its output — the rotate is
    data-dependent on the same tick's compute, so comm strictly serializes
    against compute.

    ``overlap=True`` (ISSUE 14) is the double-buffered handoff: each tick
    FIRST issues the ppermute of the PREVIOUS tick's output (a value
    already sitting in the scan carry — no data dependence on this tick's
    stage compute, so the collective-permute can fly under the stage math)
    and computes on the buffer received the tick before. A stage-to-stage
    hop therefore takes two ticks — microbatch m reaches stage s at tick
    m + 2s, the schedule runs M + 2(S − 1) ticks — but every tick's
    rotate overlaps its compute. The per-(stage, microbatch) inputs are
    IDENTICAL to the strict schedule's, extra ticks contribute exact
    zeros, so loss AND gradients are bit-identical (pinned in
    tests/test_pipeline.py).
    """
    n_stages = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    n_micro = x_mbs.shape[0]
    hop = 2 if overlap else 1  # ticks per stage-to-stage handoff
    ticks = n_micro + hop * (n_stages - 1)
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def _write_out(outputs, y, t):
        # the last stage finishes microbatch (t − hop·(S−1)) at tick t
        out_idx = t - hop * (n_stages - 1)
        write = (my == n_stages - 1) & (out_idx >= 0)
        return jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(write, y, jax.lax.dynamic_index_in_dim(
                outputs, jnp.maximum(out_idx, 0), axis=0, keepdims=False)),
            jnp.maximum(out_idx, 0), axis=0)

    def _feed(t):
        # stage 0 ingests microbatch t (clamped; masked when t >= M)
        return jax.lax.dynamic_index_in_dim(
            x_mbs, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False)

    def tick(carry, t):
        recv, outputs = carry
        x_in = jnp.where(my == 0, _feed(t), recv)
        # XProf phase naming: each device's row shows its own stage id, so
        # "pp_stage_compute" per tick + the ppermute scope below make the
        # bubble structure readable straight off the timeline
        with jax.named_scope("pp_stage_compute"):
            y = stage_fn(stage_params, x_in)
        outputs = _write_out(outputs, y, t)
        # shift activations one stage forward (ring; stage 0's recv is unused)
        with jax.named_scope("pp_activation_ppermute"):
            recv_next = jax.lax.ppermute(y, axis_name, fwd)
        return (recv_next, outputs), None

    def tick_overlap(carry, t):
        y_prev, recv, outputs = carry
        # the rotate goes FIRST and reads only carried state — XLA is free
        # to run it concurrently with this tick's stage compute below
        with jax.named_scope("pp_activation_ppermute"):
            recv_next = jax.lax.ppermute(y_prev, axis_name, fwd)
        x_in = jnp.where(my == 0, _feed(t), recv)
        with jax.named_scope("pp_stage_compute"):
            y = stage_fn(stage_params, x_in)
        outputs = _write_out(outputs, y, t)
        return (y, recv_next, outputs), None

    recv0 = jnp.zeros(x_mbs.shape[1:], x_mbs.dtype)
    out0 = jnp.zeros(x_mbs.shape, x_mbs.dtype)
    if overlap:
        (_, _, outputs), _ = jax.lax.scan(
            tick_overlap, (recv0, recv0, out0), jnp.arange(ticks))
    else:
        (_, outputs), _ = jax.lax.scan(tick, (recv0, out0),
                                       jnp.arange(ticks))
    # replicate the last stage's outputs everywhere (other stages hold zeros)
    mask = (my == n_stages - 1).astype(x_mbs.dtype)
    return jax.lax.psum(outputs * mask, axis_name)


def pipeline_apply(stage_params, x_mbs: Array, stage_fn: Callable,
                   mesh: Mesh, axis: str = PIPE_AXIS,
                   batch_axis: "str | None" = None,
                   overlap: bool = False) -> Array:
    """Run microbatches through the stage pipeline.

    stage_params: pytree whose leaves have a leading STAGE axis of size S
    (sharded onto ``axis``); ``stage_fn(params_slice, x) -> y`` applies one
    stage with that axis already stripped. x_mbs: (M, mb, ...) microbatches
    (any trailing activation shape). Returns (M, mb, ...) outputs.

    ``batch_axis`` composes dp×pp on a 2-D mesh: the microbatch dim mb is
    sharded over that mesh axis, so each data-parallel row runs the same
    tick schedule on its own batch shard (activations hop stage-to-stage
    within the row). Gradients for the stage params are psummed over the
    batch axis automatically by shard_map's transpose (params are
    replicated along it).

    ``overlap=True`` runs the double-buffered handoff schedule — the
    stage ppermute is issued for the PREVIOUS tick's output while this
    tick's compute runs, bit-identical outputs (see ``_pipeline_body``).
    """
    n_stages = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stage param leading dim {leaf.shape[0]} != pipe axis size "
                f"{n_stages} — a mismatch would silently run a different "
                "(interleaved-stage) model")
    param_spec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    x_spec = P(None, batch_axis)  # (M, mb, ...): mb sharded for dp×pp

    def body(params, x):
        # strip the per-device stage axis (size 1 after sharding)
        local = jax.tree_util.tree_map(lambda a: a[0], params)
        return _pipeline_body(local, x, stage_fn, axis, overlap=overlap)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_spec, x_spec), out_specs=x_spec,
        check_vma=False,
    )(stage_params, x_mbs)


def stack_stage_params(per_stage: list):
    """[{k: array}, ...] → {k: (S, ...) array} for pipeline_apply."""
    from deeplearning4j_tpu.parallel.sharding import stack_along_leading_axis

    return stack_along_leading_axis(per_stage)


def shard_stage_params(stacked, mesh: Mesh, axis: str = PIPE_AXIS):
    """Place stacked stage params with the stage axis on ``axis``."""
    from deeplearning4j_tpu.parallel.sharding import shard_leading_axis

    return shard_leading_axis(stacked, mesh, axis)


def unstack_stage_params(stacked) -> list:
    """{k: (S, ...) array} → [{k: array}, ...] — inverse of
    stack_stage_params (per-stage views for inspection/re-staging)."""
    n_stages = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return [jax.tree_util.tree_map(lambda a: a[i], stacked)
            for i in range(n_stages)]


def merge_stage_axis(stacked):
    """(S, per, ...) stage-stacked leaves → (S·per, ...) — stage i's local
    slice becomes layers [i·per, (i+1)·per) of the contiguous stack. The
    canonicalization step checkpoints of pipeline runs go through (see
    models/transformer_lm.pp_trained_to_lm_params): the persisted layout
    is mesh-independent, so a dp×pp snapshot restores onto any mesh."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
        stacked)


def pipeline_from_conf(conf, params, mesh: Mesh, layers=None,
                       axis: str = PIPE_AXIS):
    """Stage a uniform DENSE segment of a MultiLayerConfiguration onto the
    pipe mesh — the bridge from the framework's conf/param model to
    pipeline_apply.

    ``layers``: indices of the layers to stage (default: every layer whose
    type is DENSE with n_in == n_out, matching the shape-uniformity
    pipelining requires). All staged layers must share n_in/n_out/activation.
    Returns (stacked_sharded_params, stage_fn) ready for pipeline_apply /
    make_pipeline_train_step.
    """
    from deeplearning4j_tpu.nn.api import LayerType
    from deeplearning4j_tpu.nn.layers import dense

    if layers is None:
        layers = [i for i in range(conf.n_layers)
                  if conf.conf(i).layer_type == LayerType.DENSE
                  and conf.conf(i).n_in == conf.conf(i).n_out]
    if len(layers) != mesh.shape[axis]:
        raise ValueError(
            f"{len(layers)} uniform dense layers for a {mesh.shape[axis]}-"
            f"device pipe axis — pass layers= explicitly to choose the "
            "staged segment")
    confs = [conf.conf(i) for i in layers]
    for i, c in zip(layers, confs):
        # explicit layers= must still be dense: anything else would silently
        # run x@W+b in place of the layer's real forward
        if c.layer_type != LayerType.DENSE:
            raise ValueError(
                f"layer {i} is {c.layer_type}, not DENSE — only uniform "
                "dense segments can be pipelined through pipeline_from_conf")
    c0 = confs[0]
    for c in confs[1:]:
        if (c.n_in, c.n_out, c.activation_function) != (
                c0.n_in, c0.n_out, c0.activation_function):
            raise ValueError("staged layers must be uniform "
                             "(same n_in/n_out/activation)")

    def stage_fn(p, x):
        return dense.forward(c0, p, x)

    stacked = stack_stage_params([params[i] for i in layers])
    return shard_stage_params(stacked, mesh, axis), stage_fn


def heterogeneous_pipeline_from_conf(conf, params, mesh: Mesh,
                                     axis: str = PIPE_AXIS):
    """Stage an ENTIRE dense/output MultiLayerConfiguration onto the pipe
    mesh, one layer per device, with NON-uniform widths — the bridge that
    lets zoo models (mnist_mlp, digits_mlp, …) train through the pipeline
    rather than only synthetic d→d stacks.

    The shape uniformity ``ppermute`` requires is recovered by padding:
    every stage's weight is embedded in a (dmax, dmax) zero block, biases
    in (dmax,), and activations travel as (mb, dmax). Each device selects
    its own layer's math with ``lax.switch`` on its stage index — the
    branch statically slices x[:, :n_in], applies the layer forward
    (dense/output, including the activation), and zero-pads back to dmax.
    Padded lanes carry exact zeros end-to-end, so gradients in the padding
    are zero and training matches the unpadded network exactly (pinned in
    tests/test_pipeline.py).

    Returns (stacked_sharded_params, stage_fn, out_width): feed the first
    two to pipeline_apply / make_pipeline_train_step; slice the pipeline
    output to [..., :out_width] before the loss.
    """
    from deeplearning4j_tpu.nn.api import LayerType
    from deeplearning4j_tpu.nn.layers import dense as dense_layer
    from deeplearning4j_tpu.nn.layers import output as output_layer
    from deeplearning4j_tpu.nn.params import BIAS_KEY, WEIGHT_KEY

    n_stages = mesh.shape[axis]
    if conf.n_layers != n_stages:
        raise ValueError(
            f"{conf.n_layers} layers for a {n_stages}-device pipe axis — "
            "heterogeneous staging is one layer per stage")
    confs = [conf.conf(i) for i in range(conf.n_layers)]
    for i, c in enumerate(confs):
        if c.layer_type not in (LayerType.DENSE, LayerType.OUTPUT):
            raise ValueError(
                f"layer {i} is {c.layer_type}; heterogeneous staging "
                "supports DENSE/OUTPUT layers")
    dmax = max(max(c.n_in, c.n_out) for c in confs)

    padded = []
    for c, p in zip(confs, params):
        w = jnp.zeros((dmax, dmax), p[WEIGHT_KEY].dtype)
        w = w.at[: c.n_in, : c.n_out].set(p[WEIGHT_KEY])
        b = jnp.zeros((dmax,), p[BIAS_KEY].dtype)
        b = b.at[: c.n_out].set(p[BIAS_KEY])
        padded.append({WEIGHT_KEY: w, BIAS_KEY: b})

    def make_branch(c):
        fwd = (output_layer.forward if c.layer_type == LayerType.OUTPUT
               else dense_layer.forward)

        def branch(p, x):
            real = {WEIGHT_KEY: p[WEIGHT_KEY][: c.n_in, : c.n_out],
                    BIAS_KEY: p[BIAS_KEY][: c.n_out]}
            y = fwd(c, real, x[:, : c.n_in])
            return jnp.pad(y, ((0, 0), (0, dmax - c.n_out)))

        return branch

    branches = [make_branch(c) for c in confs]

    def stage_fn(p, x):
        my = jax.lax.axis_index(axis)
        return jax.lax.switch(my, branches, p, x)

    stacked = shard_stage_params(stack_stage_params(padded), mesh, axis)
    return stacked, stage_fn, confs[-1].n_out


def pp_update_sharding(mesh: Mesh, axis: str = PIPE_AXIS,
                       batch_axis: str = "data"):
    """ZeRO update-sharding descriptor for stage-stacked pipeline params
    (optimize/updaters.ZeroSharding): every leaf keeps its leading STAGE
    axis (sharded over ``axis``) — moments stay stage-sharded exactly
    like their params — and the flattened per-stage remainder shards over
    ``batch_axis`` (the dp rows of a dp×pp mesh)."""
    from deeplearning4j_tpu.optimize.updaters import ZeroSharding

    if batch_axis not in mesh.axis_names:
        raise ValueError(
            f"update_sharding='sharded' needs the {batch_axis!r} axis on "
            f"the mesh (got {mesh.axis_names})")
    return ZeroSharding(mesh, batch_axis, lambda _ks: (axis,))


def init_pp_opt_state(optimizer, stacked, mesh: Mesh,
                      axis: str = PIPE_AXIS,
                      batch_axis: "str | None" = None):
    """Optimizer state for ``make_pipeline_train_step(optimizer=...)``:
    moments mirror the stacked stage params (stage-sharded — the zeros
    are placed with each leaf's own sharding), or live in the
    stage-kept/dp-sharded ZeRO layout when the config resolves
    ``update_sharding="sharded"``."""
    from deeplearning4j_tpu.optimize.updaters import (
        OptimizerConfig,
        init_opt_state,
    )

    cfg = OptimizerConfig.coerce(optimizer)
    if cfg is None:
        raise ValueError("init_pp_opt_state needs an optimizer")
    zero = None
    if cfg.sharded:
        zero = pp_update_sharding(mesh, axis, batch_axis or "data")
    return init_opt_state(cfg, stacked, zero)


def make_pipeline_train_step(stage_fn: Callable, loss_fn: Callable,
                             mesh: Mesh, axis: str = PIPE_AXIS,
                             lr: float = 0.1,
                             batch_axis: "str | None" = None,
                             with_metrics: bool = False, guard=None,
                             profile=None, optimizer=None,
                             overlap: bool = False, runprof=None,
                             tuned=None, tune_context=None):
    """SGD train step over the pipelined stack.

    loss = mean over microbatches of ``loss_fn(y, labels_mb)`` on the
    pipeline output; gradients flow back through the tick schedule (reverse
    ppermute), so each stage's params receive exact gradients.
    step(stacked_params, x_mbs, y_mbs) -> (new_params, loss).
    ``batch_axis`` composes dp×pp (see pipeline_apply); the loss mean then
    spans the sharded microbatch dim, so GSPMD reduces it across the rows.

    ``with_metrics=True`` appends the in-graph telemetry block (loss,
    grad_norm, param_norm, update_ratio, per-microbatch loss vector) and
    returns (new_params, loss, metrics) — same loss/grad graph, so params
    stay bit-identical to the plain step.

    ``guard=True`` (or a ``GuardConfig``) arms the numerical guardrails on
    the staged update — skip-on-nonfinite + optional global-norm clip
    (optimize/guardrails.py) — returning (new_params, loss, metrics) where
    metrics is the guard block (plus the telemetry block when
    ``with_metrics``); bit-identical to the unguarded step on clean
    microbatches (pinned in tests/test_guardrails.py).

    ``profile=True`` (or a label string) captures a compile-time
    ``StepProfile`` on ``step.step_profile`` (telemetry/xprofile.py) —
    its collective inventory shows the stage-handoff ppermutes as
    collective-permute ops plus the output/grad psums of the schedule.

    ``optimizer=`` (ISSUE 13) swaps the SGD update for the in-graph
    stateful updater (optimize/updaters.py): ``step(params, opt_state,
    x_mbs, y_mbs) -> (new_params, new_opt_state, loss[, metrics])`` with
    ``opt_state`` from ``init_pp_opt_state``. Moments are STAGE-SHARDED
    like their params; ``update_sharding="sharded"`` additionally shards
    the per-stage update over ``batch_axis`` (ZeRO over the dp rows of a
    dp×pp mesh). Moments donate and ride the guard skip-select bitwise.

    ``overlap=True`` (ISSUE 14) swaps the strict tick schedule for the
    double-buffered stage handoff (the ppermute for tick t's output is
    issued while tick t+1's compute runs — see ``_pipeline_body``): loss
    AND updated params are bit-identical to the strict schedule at the
    same 0-compile steady retrace budget, so the knob is a pure-schedule
    A/B (bench ``comm_overlap`` stage measures both).

    ``tuned=`` (ISSUE 20) adopts the autotuner's ``pipeline`` seam:
    ``overlap`` (bitwise-safe — see above) when the ``overlap=`` arg was
    left at its default. The space's ``microbatches`` knob shapes the
    DATA (x_mbs/y_mbs), so the caller's loader applies it — this factory
    only adopts schedule knobs. Explicit dict > cache under
    ``tune_context`` > ``DL4J_TPU_TUNED`` env > off (tune/cache.py).
    """
    from deeplearning4j_tpu.optimize.guardrails import (
        GuardConfig,
        guarded_sgd_update,
    )
    from deeplearning4j_tpu.optimize.updaters import OptimizerConfig
    from deeplearning4j_tpu.telemetry.runprof import maybe_runprof
    from deeplearning4j_tpu.telemetry.xprofile import maybe_profiled
    from deeplearning4j_tpu.tune.cache import resolve_step_tuning

    tuning = resolve_step_tuning(tuned, tune_context, ("pipeline",))
    if not overlap and "overlap" in tuning:
        overlap = bool(tuning["overlap"])

    guard = GuardConfig.coerce(guard)
    label = (f"pipeline[{axis}" + (f"x{batch_axis}]" if batch_axis else "]")
             + ("+overlap" if overlap else ""))

    def _seam(step):
        # profile= then runprof= (ISSUE 17): the runprof wrapper reuses
        # the ProfiledStep's FLOPs/collectives for MFU and comm-wait
        return maybe_runprof(maybe_profiled(step, profile, label),
                             runprof, label)

    def loss_of(params, x_mbs, y_mbs):
        outs = pipeline_apply(params, x_mbs, stage_fn, mesh, axis,
                              batch_axis=batch_axis, overlap=overlap)
        per = jax.vmap(loss_fn)(outs, y_mbs)
        return jnp.mean(per), per

    opt_cfg = OptimizerConfig.coerce(optimizer)
    if opt_cfg is not None:
        from deeplearning4j_tpu.optimize.updaters import (
            guarded_opt_update,
            opt_update,
        )

        opt_cfg = opt_cfg.resolved()
        zero = (pp_update_sharding(mesh, axis, batch_axis or "data")
                if opt_cfg.sharded else None)

        from deeplearning4j_tpu.telemetry.metrics import train_step_metrics

        @partial(jax.jit, donate_argnums=(0, 1))
        def opt_step(params, opt_state, x_mbs, y_mbs):
            (loss, per), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, x_mbs, y_mbs)
            if guard is None:
                out = opt_update(opt_cfg, params, grads, opt_state, lr,
                                 zero=zero, with_metrics=with_metrics)
                new_params, new_state = out[0], out[1]
                gm = out[2] if with_metrics else {}
            else:
                new_params, new_state, gm = guarded_opt_update(
                    params, grads, opt_state, loss, lr, opt_cfg, guard,
                    zero=zero, with_metrics=with_metrics)
            if not with_metrics and guard is None:
                return new_params, new_state, loss
            metrics = dict(gm)
            if with_metrics:
                base = train_step_metrics(params, grads, lr, loss=loss)
                base.pop("update_ratio", None)  # gm carries the true one
                metrics.update({
                    "microbatch_loss": per.reshape(per.shape[0],
                                                   -1).mean(axis=1),
                    **base,
                })
            return new_params, new_state, loss, metrics

        return _seam(opt_step)

    if not with_metrics and guard is None:
        @partial(jax.jit, donate_argnums=(0,))
        def step(params, x_mbs, y_mbs):
            (loss, _), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, x_mbs, y_mbs)
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - lr * g, params, grads)
            return new_params, loss

        return _seam(step)

    from deeplearning4j_tpu.telemetry.metrics import train_step_metrics

    @partial(jax.jit, donate_argnums=(0,))
    def step(params, x_mbs, y_mbs):
        (loss, per), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params, x_mbs, y_mbs)
        if guard is None:
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - lr * g, params, grads)
            gm = {}
        else:
            new_params, gm = guarded_sgd_update(params, grads, loss, lr,
                                                guard)
        metrics = dict(gm)
        if with_metrics:
            metrics.update({
                "microbatch_loss": per.reshape(per.shape[0], -1).mean(axis=1),
                **train_step_metrics(params, grads, lr, loss=loss),
            })
        return new_params, loss, metrics

    return _seam(step)
