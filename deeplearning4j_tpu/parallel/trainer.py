"""Data-parallel training with in-graph parameter averaging.

Parity with the reference's two ParameterAveraging modes
(ref: spark/impl/multilayer/SparkDl4jMultiLayer.java:157-203):

- ``average_each_iteration=True`` — gradients are pmean'd across the "data"
  mesh axis every step (the reference's per-iteration re-broadcast loop,
  :183-203, and the Akka IterativeReduceWorkRouter semantics). This is
  standard synchronous DP-SGD: one XLA AllReduce over ICI per step.

- ``average_each_iteration=False`` (reference default, :157-176) — each
  device runs a full local fit (``local_iterations`` steps on its own shard,
  no cross-device traffic; the IterativeReduceFlatMap worker), then params
  are pmean'd once (the driver-side fold/÷N — here a single in-graph
  AllReduce instead of a host gather).

The Hogwild router (ref: workrouter/HogWildWorkRouter.java) has no XLA-shaped
equivalent — lock-free shared-memory updates contradict SPMD. Its purpose
(staleness-tolerant throughput) is served by the per-fit mode; see
scaleout/ for the API-parity shim.

Implementation: ``shard_map`` over a Mesh; batch sharded on "data"; params
replicated (combine with parallel/sharding.py TP shardings via pjit for 2-D
meshes — see make_pjit_train_step).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.datasets.iterator import DataSetIterator
from deeplearning4j_tpu.nn import functional as F
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updater import apply_updater
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS

Array = jax.Array


def _local_grad_step(conf, params, states, iteration, x, y, w, key,
                     sync_grads: bool, ablate_collectives: bool = False,
                     with_metrics: bool = False, guard=None,
                     optimizer=None, opt_n_shards: int = 1):
    """One update step over a weighted batch shard.

    ``w`` is a per-row weight (0 for padded rows). The loss is the weighted
    mean of per-example losses; with ``sync_grads`` the normalizer is the
    psum'd global weight, so the gradient on an uneven (padded) global
    batch is EXACTLY the gradient of the unpadded batch — no duplicate-row
    bias (the reference sidesteps this by repartitioning the RDD,
    ref: SparkDl4jMultiLayer.java:164).

    ``ablate_collectives`` (instrumentation only — scaling_bench.py) replaces
    the psum with identity so the collective's wall-clock cost can be
    measured by subtraction; the resulting math is per-shard-local and wrong
    on purpose.
    """
    from deeplearning4j_tpu.ops.losses import LossFunction, finalize_loss

    kdrop, _ = jax.random.split(key)
    head = conf.conf(conf.n_layers - 1)

    def loss_fn(ps):
        per = F.network_per_example_loss(conf, ps, x, y, train=True, key=kdrop)
        return jnp.sum(per * w), jnp.sum(w)

    if sync_grads:
        # Differentiate the UNNORMALIZED per-shard loss sum (linear in the
        # per-example losses), then do ONE fused psum over
        # (grads, loss_sum, weight_sum) — a single XLA all-reduce group per
        # step, with no collective inside the backward pass — and finish the
        # chain rule in closed form: the global loss is
        # finalize(Σlsum/Σwsum), so dL/dp = f'(mean)·Σgrads/Σwsum, where
        # f' = 1 except RMSE_XENT's sqrt (f'(m) = 0.5/sqrt(m+eps) = 0.5/score).
        (lsum, wsum), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if not ablate_collectives:
            grads, lsum, wsum = jax.lax.psum((grads, lsum, wsum), DATA_AXIS)
        wsum = jnp.maximum(wsum, 1e-8)
        mean = lsum / wsum
        score = finalize_loss(head.loss_function, mean)
        if LossFunction.coerce(head.loss_function) == LossFunction.RMSE_XENT:
            chain = 0.5 / score / wsum
        else:
            chain = 1.0 / wsum
        grads = jax.tree_util.tree_map(lambda g: g * chain, grads)
        upd_scale = jnp.float32(1.0)
    else:
        def local_loss(ps):
            lsum, ws = loss_fn(ps)
            return finalize_loss(head.loss_function,
                                 lsum / jnp.maximum(ws, 1e-8))

        score, grads = jax.value_and_grad(local_loss)(params)
        # all-padded shard in local mode: freeze params entirely — otherwise
        # apply_updater's L1/L2 decay would still drift them on zero grads
        upd_scale = jnp.where(jnp.sum(w) > 0, 1.0, 0.0).astype(jnp.float32)
    guard_metrics = {}
    guard_finite = None
    if guard is not None:
        # numerical guardrails (optimize/guardrails.py): finiteness of the
        # (post-psum, so replica-consistent) score + grad global-norm,
        # optional clip before the updater sees the grads, and — below —
        # a skip select carrying params AND updater state unchanged
        # through a non-finite step. Clean steps stay bit-identical
        # (exact-1.0 clip scale, exact select pass-through).
        from deeplearning4j_tpu.optimize.guardrails import (
            clip_by_global_norm,
            guard_stats,
        )

        gn, guard_finite = guard_stats(score, grads)
        clipped = jnp.float32(0.0)
        if guard.clip_norm is not None:
            grads, was_clipped = clip_by_global_norm(grads, gn,
                                                     guard.clip_norm)
            clipped = jnp.logical_and(was_clipped,
                                      guard_finite).astype(jnp.float32)
        guard_metrics = {
            "nonfinite": jnp.logical_not(guard_finite).astype(jnp.float32),
            "clipped": clipped,
            "guard_grad_norm": gn,
        }
    if optimizer is not None:
        # ISSUE 13: the in-graph stateful updater replaces the per-layer
        # legacy apply_updater loop — `states` here is the
        # {"m","v","count"} optimizer state (init_sync_opt_state), and
        # in ZeRO mode each replica updates only its 1/dp chunk and
        # all_gathers the params (optimize/updaters.opt_update_shardmap;
        # guard clip above already rescaled the grads the updater sees)
        from deeplearning4j_tpu.optimize.updaters import opt_update_shardmap

        lr0 = conf.conf(0).lr  # python float (static conf), not traced
        out = opt_update_shardmap(optimizer, params, grads, states, lr0,
                                  DATA_AXIS, opt_n_shards,
                                  with_metrics=with_metrics)
        new_params, new_states = out[0], out[1]
        opt_metrics = out[2] if with_metrics else {}
        if guard is not None and guard.skip_nonfinite:
            from deeplearning4j_tpu.optimize.guardrails import guard_select

            new_params = guard_select(guard_finite, new_params, params)
            new_states = guard_select(guard_finite, new_states, states)
        if not with_metrics and guard is not None:
            return new_params, new_states, score, guard_metrics
        if not with_metrics:
            return new_params, new_states, score
        from deeplearning4j_tpu.telemetry.metrics import global_norm

        metrics = {
            "loss": jnp.asarray(score, jnp.float32),
            "grad_norm": global_norm(grads),
            "param_norm": global_norm(params),
            **opt_metrics,
            **guard_metrics,
        }
        return new_params, new_states, score, metrics
    new_params = []
    new_states = []
    updates = []
    for i in range(conf.n_layers):
        upd, st = apply_updater(conf.conf(i), iteration, grads[i], params[i], states[i])
        new_params.append(jax.tree_util.tree_map(
            lambda p, u: p - upd_scale * u, params[i], upd))
        new_states.append(st)
        updates.append(upd)
    if guard is not None and guard.skip_nonfinite:
        from deeplearning4j_tpu.optimize.guardrails import guard_select

        # the skip must freeze the WHOLE training state: a NaN grad would
        # otherwise still poison momentum/adagrad accumulators even with
        # the params carried
        new_params = guard_select(guard_finite, tuple(new_params),
                                  tuple(params))
        new_states = guard_select(guard_finite, tuple(new_states),
                                  tuple(states))
    if not with_metrics and guard is not None:
        return (tuple(new_params), tuple(new_states), score,
                guard_metrics)
    if not with_metrics:
        return tuple(new_params), tuple(new_states), score
    # in-graph telemetry block: appended reductions on intermediates the
    # step already computed — loss/params stay bit-identical to the
    # unthreaded step (pinned in tests/test_telemetry.py)
    from deeplearning4j_tpu.telemetry.metrics import global_norm

    metrics = {
        "loss": jnp.asarray(score, jnp.float32),
        "grad_norm": global_norm(grads),
        "param_norm": global_norm(params),
        "update_ratio": (global_norm(updates) * upd_scale
                         / (global_norm(params) + 1e-12)),
        **guard_metrics,
    }
    return tuple(new_params), tuple(new_states), score, metrics


def init_sync_opt_state(optimizer, params, mesh: Mesh):
    """Optimizer state for ``make_sync_train_step(optimizer=...)``:
    param-mirroring zero moments (replicated mode — the DP trainer keeps
    params replicated, so moments are too), or the flattened (dp, chunk)
    ZeRO layout sharded over the "data" axis (sharded mode: each replica
    stores 1/dp of every moment leaf)."""
    from deeplearning4j_tpu.optimize.updaters import (
        OptimizerConfig,
        ZeroSharding,
        init_opt_state,
    )

    cfg = OptimizerConfig.coerce(optimizer)
    if cfg is None:
        raise ValueError("init_sync_opt_state needs an optimizer")
    zero = ZeroSharding(mesh, DATA_AXIS) if cfg.sharded else None
    return init_opt_state(cfg, params, zero)


def make_sync_train_step(conf: MultiLayerConfiguration, mesh: Mesh,
                         ablate_collectives: bool = False,
                         with_metrics: bool = False, guard=None,
                         profile=None, optimizer=None, runprof=None):
    """Per-step averaging: grads AllReduced every iteration.

    step(params, states, iteration, x, y, w, key) — ``w`` is the per-row
    weight vector (0 = padded row), see _local_grad_step.

    ``ablate_collectives`` is scaling-bench instrumentation (measures the
    collective's cost by subtraction); never use it for training.

    ``with_metrics=True`` appends a replicated in-graph metrics dict
    (loss, grad_norm, param_norm, update_ratio) as a 4th output — the
    norms are of the POST-AllReduce gradient, so every host sees the same
    global numbers; feed them to telemetry.TrainTelemetry.

    ``guard=True`` (or a ``GuardConfig``) arms the numerical guardrails
    (optimize/guardrails.py): a non-finite score or grad norm carries
    params AND updater state unchanged through the step, optional
    global-norm clipping runs before the updater, and the guard block
    (``nonfinite``/``clipped``/``guard_grad_norm``) is appended as the 4th
    output (merged into the metrics dict when ``with_metrics``). The
    finiteness test runs on the post-AllReduce score/grads, so every
    replica takes the same skip decision. Clean steps stay bit-identical
    (pinned in tests/test_guardrails.py).

    ``profile=True`` (or a label string) captures a compile-time
    ``StepProfile`` on ``step.step_profile`` (telemetry/xprofile.py) —
    its collective inventory pins the ONE fused gradient all-reduce this
    step is supposed to issue (the scaling_bench invariant).

    ``optimizer=`` (ISSUE 13; name string or
    ``optimize.updaters.OptimizerConfig``) replaces the legacy per-layer
    ``apply_updater`` with the in-graph stateful updater — ``states``
    then carries the ``{"m","v","count"}`` optimizer state from
    ``init_sync_opt_state`` instead of the AdaGrad/momentum tree.
    ``update_sharding="sharded"`` runs the ZeRO-style update INSIDE the
    shard_map body: each replica slices its 1/dp chunk of the (psum'd)
    grads and moments, updates it, and ``all_gather``s only the params —
    parity ≤1e-6 vs the replicated mode pinned in
    tests/test_updaters.py. Moments stay donated and ride the guard
    skip-select bitwise.
    """
    from deeplearning4j_tpu.optimize.guardrails import GuardConfig
    from deeplearning4j_tpu.optimize.updaters import OptimizerConfig
    from deeplearning4j_tpu.telemetry.runprof import maybe_runprof
    from deeplearning4j_tpu.telemetry.xprofile import maybe_profiled

    guard = GuardConfig.coerce(guard)
    opt_cfg = OptimizerConfig.coerce(optimizer)
    if opt_cfg is not None:
        opt_cfg = opt_cfg.resolved()
    n_dp = int(mesh.shape[DATA_AXIS])

    def step(params, states, iteration, x, y, w, key):
        return _local_grad_step(conf, params, states, iteration, x, y, w, key,
                                True, ablate_collectives,
                                with_metrics=with_metrics, guard=guard,
                                optimizer=opt_cfg, opt_n_shards=n_dp)

    if opt_cfg is not None and opt_cfg.sharded:
        # ZeRO layout: the (dp, chunk) moment leaves shard their leading
        # dim over the dp axis; the step count stays replicated
        state_spec = {"m": P(DATA_AXIS), "v": P(DATA_AXIS), "count": P()}
    else:
        state_spec = P()
    out_specs = ((P(), state_spec, P(), P())
                 if (with_metrics or guard is not None)
                 else (P(), state_spec, P()))
    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), state_spec, P(), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P()),
        out_specs=out_specs,
        check_vma=False,
    )
    label = f"dp_sync[{mesh.shape[DATA_AXIS]}]"
    return maybe_runprof(
        maybe_profiled(jax.jit(sharded, donate_argnums=(0, 1)), profile,
                       label), runprof, label)


def make_local_fit_step(conf: MultiLayerConfiguration, mesh: Mesh,
                        local_iterations: int):
    """Per-fit averaging: each device runs `local_iterations` steps on its own
    shard with zero cross-device traffic, then params/states are pmean'd once."""

    def local_fit(params, states, iteration0, x, y, w, key):
        def body(carry, i):
            params, states = carry
            step_key = jax.random.fold_in(key, i)
            params, states, score = _local_grad_step(
                conf, params, states, iteration0 + i, x, y, w, step_key, False
            )
            return (params, states), score

        (params, states), scores = jax.lax.scan(
            body, (params, states), jnp.arange(local_iterations)
        )
        # the single aggregation round: in-graph AllReduce replaces the
        # reference's results.fold(zeros, Add) ÷ numPartitions on the driver.
        # Weighted by each shard's sample count so all-padded shards (batch
        # smaller than the mesh) contribute nothing; equal-weight pmean when
        # shards are balanced, matching the reference's repartitioned RDDs.
        wsum = jnp.sum(w)
        wtot = jnp.maximum(jax.lax.psum(wsum, DATA_AXIS), 1e-8)
        frac = wsum / wtot
        # one fused all-reduce group for params+states+score
        params, states, score = jax.lax.psum(
            jax.tree_util.tree_map(lambda t: t * frac,
                                   (params, states, scores[-1])), DATA_AXIS)
        return params, states, score

    sharded = jax.shard_map(
        local_fit,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


class ParameterAveragingTrainer:
    """Facade mirroring SparkDl4jMultiLayer: wraps a MultiLayerNetwork and a
    mesh, trains data-parallel, leaves averaged params in the network.

    ``average_each_iteration`` matches the reference's
    ``org.deeplearning4j.spark.iteration.average`` SparkConf flag.
    """

    def __init__(
        self,
        net: MultiLayerNetwork,
        mesh: Optional[Mesh] = None,
        average_each_iteration: bool = False,
        local_iterations: Optional[int] = None,
        checkpointer=None,
        checkpoint_every: int = 0,
    ):
        from deeplearning4j_tpu.parallel.mesh import data_parallel_mesh

        self.net = net
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        self.average_each_iteration = average_each_iteration
        self.local_iterations = (
            local_iterations
            if local_iterations is not None
            else net.conf.conf(0).num_iterations
        )
        self._sync_step = None
        self._fit_step = None
        self._iteration = 0
        # periodic sharded checkpoints (scaleout.ckpt) through the same
        # exception-safe listener dispatch as every other listener: a save
        # failure is logged and skipped, never killing the fit
        self._ckpt_listener = None
        if checkpointer is not None and checkpoint_every > 0:
            from deeplearning4j_tpu.scaleout.ckpt import (
                CheckpointIterationListener,
            )

            self._ckpt_listener = CheckpointIterationListener(
                checkpointer, save_every=checkpoint_every, mesh=self.mesh)

    def resume(self, checkpointer) -> Optional[int]:
        """Restore net params/updater state/RNG/iteration from the latest
        committed checkpoint under ``checkpointer`` (replicated onto this
        trainer's mesh) and continue counting from its step. Returns the
        resumed step, or None when no checkpoint exists yet."""
        from deeplearning4j_tpu.scaleout.ckpt import (
            capture_net_state,
            replicated_shardings,
            restore_net_state,
        )

        if checkpointer.latest_step() is None:
            return None
        net = self.net
        net._ensure_train_step()
        template, _meta = capture_net_state(net)
        state, step, meta = checkpointer.restore(
            template, shardings=replicated_shardings(template, self.mesh))
        restore_net_state(net, state, meta)
        self._iteration = int(meta.get("iteration", step))
        return step

    @property
    def n_devices(self) -> int:
        return int(self.mesh.size)

    def _pad_to_devices(self, x):
        """Pad the batch so it divides the data-axis size (the reference
        repartitions the RDD to the worker count, :164). Padded rows repeat
        the last sample but carry 0 weight in the returned mask, so they
        never enter the loss or gradient."""
        n = x.shape[0]
        d = self.mesh.shape[DATA_AXIS]
        rem = n % d
        if rem == 0:
            return x, jnp.ones((n,), jnp.float32)
        pad = d - rem
        reps = jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)], axis=0)
        w = jnp.concatenate([jnp.ones((n,), jnp.float32),
                             jnp.zeros((pad,), jnp.float32)])
        return reps, w

    def _pad_batch(self, batch):
        """(features, labels, weight-mask), all padded to the data-axis size."""
        x, w = self._pad_to_devices(jnp.asarray(batch.features))
        n = batch.labels.shape[0]
        d = self.mesh.shape[DATA_AXIS]
        y = jnp.asarray(batch.labels)
        if n % d:
            y = jnp.concatenate([y, jnp.repeat(y[-1:], d - n % d, axis=0)], axis=0)
        return x, y, w

    def fit_data_set(self, data: DataSetIterator) -> None:
        """ref: SparkDl4jMultiLayer.fitDataSet(JavaRDD<DataSet>)."""
        net = self.net
        net._ensure_train_step()
        rep = NamedSharding(self.mesh, P())
        # explicit copies: the steps donate their inputs, and the facade (or a
        # clone) may still reference the original buffers
        params = jax.device_put(
            jax.tree_util.tree_map(jnp.array, net.params_tree), rep
        )
        states = jax.device_put(
            jax.tree_util.tree_map(jnp.array, net._train_state), rep
        )

        from deeplearning4j_tpu.optimize.listeners import (
            close_listeners,
            dispatch_listeners,
        )

        listeners = list(net.listeners)
        if self._ckpt_listener is not None:
            listeners.append(self._ckpt_listener)

        def publish(params, states):
            # reference-only refresh (no host sync): listeners — notably the
            # checkpoint listener's capture_net_state — must snapshot the
            # CURRENT training state, not the pre-fit buffers. The next
            # step() call donates these arrays, but dispatch runs before it.
            net._params = params
            net._train_state = states
            net._iteration = self._iteration

        try:
            if self.average_each_iteration:
                if self._sync_step is None:
                    self._sync_step = make_sync_train_step(net.conf, self.mesh)
                step = self._sync_step
                for batch in data:
                    x, y, w = self._pad_batch(batch)
                    params, states, score = step(
                        params, states, jnp.asarray(self._iteration), x, y, w,
                        net._keys.next(),
                    )
                    self._iteration += 1
                    publish(params, states)
                    dispatch_listeners(listeners, net, self._iteration,
                                       float(score))
            else:
                if self._fit_step is None:
                    self._fit_step = make_local_fit_step(
                        net.conf, self.mesh, self.local_iterations
                    )
                step = self._fit_step
                for batch in data:
                    x, y, w = self._pad_batch(batch)
                    params, states, score = step(
                        params, states, jnp.asarray(self._iteration), x, y, w,
                        net._keys.next(),
                    )
                    self._iteration += self.local_iterations
                    publish(params, states)
                    dispatch_listeners(listeners, net, self._iteration,
                                       float(score))
        finally:
            # a crash mid-fit must not leave e.g. a ProfilerIterationListener
            # with an open trace window armed
            close_listeners(listeners)

        net._params = jax.tree_util.tree_map(lambda a: a, params)
        net._train_state = states
