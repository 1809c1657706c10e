"""Pure functional network core.

These are the functions ``jit``/``grad``/``pjit`` actually trace. The stateful
``MultiLayerNetwork`` facade (multilayer.py) wraps them, mirroring how the
reference's mutable MultiLayerNetwork sits over per-layer math
(ref: nn/multilayer/MultiLayerNetwork.java:495-525 feedForward, :959-1010
doBackWard). Backprop is jax.grad of the composed loss instead of the
reference's hand-chained ``backwardGradient`` calls.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import layers as layer_ops
from deeplearning4j_tpu.nn.api import LayerType
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import output as output_layer
from deeplearning4j_tpu.nn.layers.preprocessor import preprocessor
from deeplearning4j_tpu.nn.params import init_layer_params
from deeplearning4j_tpu.optimize.updater import apply_updater, init_updater_state

Array = jax.Array
NetParams = Tuple[dict, ...]


def init_params(conf: MultiLayerConfiguration, key: Array) -> NetParams:
    keys = jax.random.split(key, max(conf.n_layers, 1))
    return tuple(
        init_layer_params(keys[i], conf.conf(i)) for i in range(conf.n_layers)
    )


def _maybe_preprocess(conf: MultiLayerConfiguration, i: int, x: Array) -> Array:
    name = conf.preprocessor_for(i)
    return preprocessor(name)(x) if name else x


def feed_forward(
    conf: MultiLayerConfiguration,
    params: NetParams,
    x: Array,
    *,
    train: bool = False,
    key: Optional[Array] = None,
) -> List[Array]:
    """Activations per layer, input first (ref: MultiLayerNetwork.java:495-525)."""
    acts = [x]
    keys = (
        jax.random.split(key, conf.n_layers) if key is not None else [None] * conf.n_layers
    )
    for i in range(conf.n_layers):
        x = _maybe_preprocess(conf, i, x)
        x = layer_ops.forward(conf.conf(i), params[i], x, train=train, key=keys[i],
                              drop_connect=conf.use_drop_connect)
        acts.append(x)
    return acts


def output(conf: MultiLayerConfiguration, params: NetParams, x: Array) -> Array:
    """Final network output (ref: MultiLayerNetwork.output :1184)."""
    return feed_forward(conf, params, x)[-1]


def hidden_activation(
    conf: MultiLayerConfiguration, params: NetParams, x: Array, upto: int,
    *, train: bool = False, key: Optional[Array] = None,
) -> Array:
    """Forward through layers [0, upto) — pretraining input for layer `upto`
    (ref: MultiLayerNetwork.activationFromPrevLayer :479)."""
    keys = jax.random.split(key, max(upto, 1)) if key is not None else [None] * max(upto, 1)
    for i in range(upto):
        x = _maybe_preprocess(conf, i, x)
        x = layer_ops.forward(conf.conf(i), params[i], x, train=train, key=keys[i])
    return x


def network_loss(
    conf: MultiLayerConfiguration,
    params: NetParams,
    x: Array,
    labels: Array,
    *,
    train: bool = False,
    key: Optional[Array] = None,
) -> Array:
    """Loss through the whole stack; the head uses the fused-logits path."""
    from deeplearning4j_tpu.ops.losses import finalize_loss

    per = network_per_example_loss(conf, params, x, labels, train=train, key=key)
    head = conf.conf(conf.n_layers - 1)
    return finalize_loss(head.loss_function, jnp.mean(per))


def network_per_example_loss(
    conf: MultiLayerConfiguration,
    params: NetParams,
    x: Array,
    labels: Array,
    *,
    train: bool = False,
    key: Optional[Array] = None,
) -> Array:
    """Per-example pre-reduction losses, shape (batch,).

    The scalar ``network_loss`` equals
    ``ops.losses.finalize_loss(head.loss_function, mean(per_example))``;
    data-parallel callers weight rows (padding masks) and normalize the mean
    across shards with a psum so uneven batches stay unbiased.

    Head layers:
    - OUTPUT: fused-logits classifier head. 3-D labels (batch, time, classes)
      are scored per timestep and averaged over time.
    - LSTM: the layer's own decoder projection provides per-timestep logits
      (ref: nn/layers/recurrent/LSTM.java:54-160 trains through its decoder
      with per-timestep softmax); labels are (batch, time, vocab).
    """
    from deeplearning4j_tpu.ops.losses import (
        LossFunction,
        per_example_loss,
        per_example_loss_from_logits,
    )

    n = conf.n_layers
    keys = jax.random.split(key, n) if key is not None else [None] * n
    for i in range(n - 1):
        x = _maybe_preprocess(conf, i, x)
        x = layer_ops.forward(conf.conf(i), params[i], x, train=train, key=keys[i],
                              drop_connect=conf.use_drop_connect)
    x = _maybe_preprocess(conf, n - 1, x)
    head = conf.conf(n - 1)
    if head.layer_type == LayerType.OUTPUT:
        per = output_layer.output_per_example_loss(
            head, params[n - 1], x, labels, train=train,
            key=keys[n - 1], drop_connect=conf.use_drop_connect)
    elif head.layer_type in (LayerType.LSTM, LayerType.ATTENTION):
        # sequence heads own a decoder producing per-timestep logits
        logits = layer_ops.forward(head, params[n - 1], x, train=train,
                                   key=keys[n - 1]).astype(jnp.float32)
        labels = labels.astype(jnp.float32)
        ce_family = (LossFunction.MCXENT, LossFunction.NEGATIVELOGLIKELIHOOD,
                     LossFunction.XENT, LossFunction.RECONSTRUCTION_CROSSENTROPY)
        if LossFunction.coerce(head.loss_function) in ce_family:
            per = per_example_loss_from_logits(head.loss_function, labels, logits)
        else:
            per = per_example_loss(head.loss_function, labels, logits)
    else:
        raise ValueError("network_per_example_loss requires an OUTPUT, "
                         "LSTM, or ATTENTION head layer")
    if per.ndim > 1:  # sequence head: average the per-timestep losses
        per = jnp.mean(per, axis=tuple(range(1, per.ndim)))
    return per


def make_train_step(conf: MultiLayerConfiguration, donate: bool = False,
                    policy=None):
    """Build the jitted full-network training step.

    step(params, updater_states, iteration, x, labels, key)
      -> (new_params, new_states, score)

    One fused XLA program: forward, backward (jax.grad), per-layer updater —
    the TPU equivalent of doBackWard's per-iteration body
    (ref: MultiLayerNetwork.java:976-1002).

    ``donate=True`` donates the params/state buffers to XLA (in-place update,
    halves HBM traffic for the update) — only safe when the caller owns the
    arrays exclusively, i.e. nothing else (facade fields, clones, listeners)
    still references them. MultiLayerNetwork keeps False; the data-parallel
    trainer and benches, which own their loop state, opt in.

    ``policy`` (ops.dtypes.Policy) enables mixed precision: params/activations
    are cast to ``policy.compute_dtype`` (e.g. bfloat16 for the MXU) inside
    the step; master params, updater state, and the loss stay float32.
    """

    step = _raw_train_step(conf, policy)
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def _raw_train_step(conf: MultiLayerConfiguration, policy=None):
    """Unjitted step body shared by make_train_step / make_train_epoch."""

    def step(params, states, iteration, x, labels, key):
        kdrop, _ = jax.random.split(key)

        def loss_fn(ps):
            if policy is not None:
                ps = jax.tree_util.tree_map(
                    lambda a: a.astype(policy.compute_dtype), ps
                )
                xin = x.astype(policy.compute_dtype)
            else:
                xin = x
            return network_loss(conf, ps, xin, labels, train=True, key=kdrop)

        score, grads = jax.value_and_grad(loss_fn)(params)
        new_params = []
        new_states = []
        for i in range(conf.n_layers):
            upd, st = apply_updater(conf.conf(i), iteration, grads[i], params[i], states[i])
            new_params.append(
                jax.tree_util.tree_map(lambda p, u: p - u, params[i], upd)
            )
            new_states.append(st)
        return tuple(new_params), tuple(new_states), score

    return step


def make_train_epoch(conf: MultiLayerConfiguration, n_steps: int,
                     donate: bool = True, policy=None):
    """Device-resident training loop: ``lax.scan`` over ``n_steps`` batches
    inside ONE jitted program.

    epoch(params, states, iteration0, xs, ys, key)
      -> (new_params, new_states, scores)

    xs: (n_steps, batch, features), ys: (n_steps, batch, classes). Keeps the
    loop on the TPU — one dispatch per epoch chunk instead of per step, which
    matters when host→device dispatch latency rivals step compute (small
    models). The per-step RNG key is folded from the
    step index, matching make_train_step semantics.
    """
    step = _raw_train_step(conf, policy)

    def epoch(params, states, iteration0, xs, ys, key):
        def body(carry, inp):
            params, states = carry
            i, x, y = inp
            sub = jax.random.fold_in(key, i)
            params, states, score = step(params, states, iteration0 + i, x, y, sub)
            return (params, states), score

        idx = jnp.arange(n_steps)
        (params, states), scores = jax.lax.scan(body, (params, states),
                                                (idx, xs, ys))
        return params, states, scores

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(epoch, donate_argnums=donate_argnums)


def init_train_state(conf: MultiLayerConfiguration, params: NetParams):
    return tuple(init_updater_state(params[i]) for i in range(conf.n_layers))


def score(
    conf: MultiLayerConfiguration, params: NetParams, x: Array, labels: Array
) -> Array:
    return network_loss(conf, params, x, labels, train=False)
