"""The ``serve_dtype=`` seam: serving-precision weight preparation.

Training keeps f32 master weights; serving wants them cheaper. Three
precisions, one entry point (``prepare_serve_params``):

- ``None`` / ``"f32"`` — passthrough (the parity-oracle precision;
  tests/test_serve.py pins greedy decode against the full-forward oracle
  at f32).
- ``"bf16"`` — every float leaf cast to bfloat16 (the serving default:
  halves weight HBM, single-MXU-pass matmuls on TPU).
- ``"int8"`` — weight-only quantization of the matmul weights (the
  ``_MATMUL_KEYS`` leaf names: q/k/v/o projections, router, expert FFN
  mats, decoder, embedding): symmetric per-output-channel int8 with an
  f32 scale, wrapped in a :class:`QuantTensor` pytree node. Everything
  else (biases, layernorm gains — stacked (L, ...) leaves, so shape alone
  can't tell them apart from matmuls) stays bf16: they are noise in the
  byte count and precision-critical.

Dequantization happens IN-GRAPH: the decode/prefill builders
(models/transformer_lm.make_decode_step / make_prefill_step) take a
``params_transform`` hook and the engine passes :func:`dequantize_tree`,
so the weights live in HBM as int8 (~4× smaller than f32 at rest and on
the restore path) and XLA widens them to bf16 at use. This is the
weight-only recipe: activations and accumulation stay bf16/f32 — the A/B
twin quantifies throughput + memory, not a new numerics regime.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

SERVE_DTYPES = (None, "f32", "bf16", "int8")


@jax.tree_util.register_pytree_node_class
class QuantTensor:
    """An int8-quantized weight + its per-output-channel scale. Registered
    as a pytree node so quantized params flow through jit/tree_map like any
    other leaf pair; ``dequantize()`` (called inside the jitted step via
    the ``params_transform`` seam) widens back to bf16."""

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def nbytes(self) -> int:
        return int(self.q.size * self.q.dtype.itemsize
                   + self.scale.size * self.scale.dtype.itemsize)

    def dequantize(self):
        return self.q.astype(jnp.bfloat16) * self.scale.astype(jnp.bfloat16)

    def __repr__(self):
        return f"QuantTensor(shape={tuple(self.q.shape)})"


# leaf names that ARE matmul weights in the flagship-LM params tree
# (models/transformer_lm.init_lm_params); the last two axes are
# (contraction, output-channel), whatever stacking axes precede them
_MATMUL_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "router", "w1", "w2", "wg", "wu", "wd", "dec_w",
     "embed"})


def _quantize_leaf(path, w):
    """Symmetric per-output-channel int8 for matmul weights: scale over
    the contraction axis (-2), so every output channel keeps its own
    dynamic range. Non-matmul leaves fall back to bf16."""
    key = path[-1].key if path else None
    if (key not in _MATMUL_KEYS or w.ndim < 2
            or not jnp.issubdtype(w.dtype, jnp.floating)):
        return (w.astype(jnp.bfloat16)
                if jnp.issubdtype(w.dtype, jnp.floating) else w)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return QuantTensor(q, scale.astype(jnp.float32))


def _is_quant(x) -> bool:
    return isinstance(x, QuantTensor)


def prepare_serve_params(params, serve_dtype: Optional[str]):
    """Apply the serving-precision seam to a params pytree (see module
    docstring). Raises on an unknown ``serve_dtype``."""
    if serve_dtype not in SERVE_DTYPES:
        raise ValueError(f"unknown serve_dtype {serve_dtype!r}; options: "
                         + ", ".join(str(d) for d in SERVE_DTYPES))
    if serve_dtype in (None, "f32"):
        return params
    if serve_dtype == "bf16":
        return jax.tree_util.tree_map(
            lambda w: w.astype(jnp.bfloat16)
            if jnp.issubdtype(jnp.asarray(w).dtype, jnp.floating) else w,
            params)
    return jax.tree_util.tree_map_with_path(_quantize_leaf, params)


def dequantize_tree(params):
    """The in-graph half of the seam: widen every QuantTensor back to a
    dense bf16 array, pass everything else through. Identity-shaped for
    f32/bf16 trees, so the engine wires it unconditionally as the
    ``params_transform`` of its jitted steps."""
    return jax.tree_util.tree_map(
        lambda x: x.dequantize() if _is_quant(x) else x, params,
        is_leaf=_is_quant)


def activation_dtype(serve_dtype: Optional[str]):
    """The dtype decode activations (and so the KV cache) run at under a
    given serve_dtype: f32 for the parity precision, bf16 otherwise."""
    return jnp.float32 if serve_dtype in (None, "f32") else jnp.bfloat16


def params_nbytes(params) -> int:
    """Total at-rest weight bytes of a (possibly quantized) params tree —
    the memory claim the bench's int8 A/B twin reports."""
    return int(sum(
        leaf.nbytes if _is_quant(leaf) else jnp.asarray(leaf).nbytes
        for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=_is_quant)))
