"""Convolution layer.

The reference loops ``Nd4j.getConvolution().convn(input, filter, VALID)`` per
feature map (ref: nn/layers/convolution/ConvolutionLayer.java:115-128). Here
the whole layer runs as ONE conv on the MXU — ``lax.conv_general_dilated``
for wide contractions, im2col slice+einsum for narrow ones — NCHW / OIHW
layout (ref parameter conventions, ``nn/params.py``), VALID padding to match
the reference.

History: rounds 2-4 used im2col everywhere because the weight-gradient
convolution XLA derives from ``conv_general_dilated`` wedged the TPU
compiler (>150 s for one LeNet-sized layer, measured round 3). Round 5
re-measured: at WIDE shapes the wedge is gone
(conv_wide grad convs compile in ~4 s) and the conv emitter beats im2col
by 4.4x on the HBM-bound first conv_wide layer — im2col materialized a
B*C*KH*KW*H'*W' patch buffer (~80 MB/pass at conv_wide's 32ch 32x32 input)
in forward AND both backward passes, while the conv emitter streams patches
through VMEM. Measured per-layer train-step MFU (B=64, bf16, grads wrt both
w and x): conv1 32->128ch 0.12 -> 0.52, conv2 128->128ch 0.49 -> 0.72;
end-to-end conv_wide stage 2.12x. At NARROW shapes the wedge is back on
jax 0.9.0 / libtpu 0.0.34: the LeNet train step (B=512) compiled in 6.5 s
through im2col, and with the conv emitter forced it had not finished
compiling after 24 minutes when the chip tool killed it (PR 21 chip run;
round 5 had seen 12-16 s per LeNet grad conv). So ``conv2d`` gates on
contraction width — see ``_EMITTER_MIN_CONTRACTION`` — and
``set_conv_emitter(True)`` at LeNet shapes can cost a whole chip call.
``im2col_conv`` stays as the narrow-shape path and the parity oracle.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.params import CONV_BIAS_KEY, CONV_WEIGHT_KEY
from deeplearning4j_tpu.ops.activations import activation

# A/B switch for bench attribution (None = shape-gated auto, see conv2d;
# True forces the conv emitter, False forces the legacy im2col formulation)
_use_conv_emitter: "bool | None" = None

# auto gate: the conv emitter wins when the im2col contraction (C*KH*KW)
# is wide enough to make the patch buffer HBM traffic dominate (measured
# 4.4x at conv_wide's 800-wide conv1); below it im2col compiles in seconds
# while the conv emitter's grad convolutions at LeNet shapes do not finish
# compiling in 24 minutes (module docstring), for compute that is
# model-bound either way (LeNet 0.0116 MFU documented r04)
_EMITTER_MIN_CONTRACTION = 512


def set_conv_emitter(enabled: "bool | None") -> None:
    global _use_conv_emitter
    _use_conv_emitter = enabled


def im2col_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """VALID stride-1 conv via im2col: x (B,C,H,W) * w (O,C,KH,KW) ->
    (B,O,H',W'). Legacy core (see module docstring) — differentiates into
    pads and matmuls only; parity oracle for conv2d."""
    o, c, kh, kw = w.shape
    h_out = x.shape[2] - kh + 1
    w_out = x.shape[3] - kw + 1
    cols = jnp.stack(
        [
            x[:, :, i : i + h_out, j : j + w_out]
            for i in range(kh)
            for j in range(kw)
        ],
        axis=2,
    )  # (B, C, KH*KW, H', W')
    return jnp.einsum("bckhw,ock->bohw", cols, w.reshape(o, c, kh * kw))


def conv2d(x: jax.Array, w: jax.Array) -> jax.Array:
    """VALID stride-1 conv: x (B,C,H,W) * w (O,C,KH,KW) -> (B,O,H',W')."""
    o, c, kh, kw = w.shape
    use_emitter = c * kh * kw >= _EMITTER_MIN_CONTRACTION
    if _use_conv_emitter is not None:
        use_emitter = _use_conv_emitter
    if not use_emitter:
        return im2col_conv(x, w)
    return lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"))


def forward(
    conf: NeuralNetConfiguration,
    params: Dict[str, jax.Array],
    x: jax.Array,
    *,
    train: bool = False,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    w = params[CONV_WEIGHT_KEY]
    b = params[CONV_BIAS_KEY]
    # the weights set the compute dtype: under a bf16 policy the conv runs on
    # the bf16 MXU path (the MXU still accumulates in f32 internally)
    out = conv2d(x.astype(w.dtype), w)
    out = out + b[None, :, None, None]
    return activation(conf.activation_function)(out)
