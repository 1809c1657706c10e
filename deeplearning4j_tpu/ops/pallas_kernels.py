"""Pallas TPU kernels for hot ops.

The reference's hot loops are BLAS calls behind the ND4J executioner
(SURVEY.md §3.1: mmul per layer in feedForward, dot/axpy in word2vec).
On TPU those map to XLA, which already fuses well; pallas buys us the spots
where manual fusion/epilogues beat XLA's defaults:

- ``fused_dense``: tiled matmul with the bias add AND activation fused into
  the MXU epilogue — one VMEM round-trip instead of three HBM-bound ops.
- ``lstm_gates``: the fused i/f/o/g gate nonlinearity + cell update of the
  Karpathy-style LSTM (ref nn/layers/recurrent/LSTM.java iFog buffer) as a
  single VPU kernel over the (B, 4H) preactivation block.

Both are differentiable (custom_vjp with lax backward) and dispatch:
interpret mode on the CPU backend (tests run on the CPU mesh), a Mosaic
compile on every other backend, plain-lax fallback for shapes that don't
tile onto the hardware.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.activations import activation as _activation
from deeplearning4j_tpu.ops.activations import derivative as _derivative

Array = jax.Array

# restricted to activations whose derivative is expressible from the OUTPUT
# (needed by the custom VJP); functions come from the shared registry
_FUSABLE = ("linear", "relu", "tanh", "sigmoid")
_ACTS = {name: _activation(name) for name in _FUSABLE}


def _interpret() -> bool:
    # only the CPU backend interprets; any other backend compiles the
    # kernel through Mosaic or fails there, it never interprets quietly
    return jax.default_backend() == "cpu"


# Fused-dense layer gating. pallas_call is not GSPMD-partitionable: under a
# tensor-parallel mesh it would all-gather Megatron-sharded weights and drop
# the output sharding, so the auto default only engages on single-device
# sessions. ``set_fused_dense(True/False)`` overrides (e.g. force-on for a
# single-logical-device program on a multi-chip host, or in tests).
_fused_dense_override: "bool | None" = None


def set_fused_dense(enabled: "bool | None") -> None:
    global _fused_dense_override
    _fused_dense_override = enabled


def use_fused_dense() -> bool:
    if _fused_dense_override is not None:
        return _fused_dense_override
    return jax.device_count() == 1


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ------------------------------------------------------------ fused dense ----

def _dense_kernel(x_ref, w_ref, b_ref, o_ref, act: str):
    acc = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    acc = acc + b_ref[:]
    o_ref[:] = _ACTS[act](acc).astype(o_ref.dtype)


def _dense_pallas(x: Array, w: Array, b: Array, act: str,
                  tile_m: int = 128, tile_n: int = 128) -> Array:
    m, k = x.shape
    _, n = w.shape
    tile_m = min(tile_m, m)
    tile_n = min(tile_n, n)
    grid = (_cdiv(m, tile_m), _cdiv(n, tile_n))
    return pl.pallas_call(
        functools.partial(_dense_kernel, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile_n), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            # bias travels as (1, N): 1-D operands trip Mosaic's layout
            # verifier (lane tiling T(128) vs XLA's T(1024))
            pl.BlockSpec((1, tile_n), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=_interpret(),
    )(x, w, b.reshape(1, n))


def _dense_ref(x: Array, w: Array, b: Array, act: str) -> Array:
    return _ACTS[act](x @ w + b)


def _dense_shapes_ok(x: Array, w: Array) -> bool:
    m, k = x.shape
    _, n = w.shape
    # sublane multiple of 8, lane multiple of 128: Mosaic also took bf16
    # operands at m = 8 and 24, under the bf16 sublane tile of 16 (PR 21
    # chip run). K is NOT tiled (each program loads a (tile_m,K)+(K,tile_n)
    # strip), so bound it to keep the per-program VMEM footprint
    # ≲ 2*128*K*4B ≤ ~4MB of the 16 MiB scoped limit; K = 4096 compiled in
    # f32 and bf16.
    return m % 8 == 0 and k % 128 == 0 and n % 128 == 0 and k <= 4096


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_dense(x: Array, w: Array, b: Array, activation: str = "linear"):
    """act(x @ w + b) with the epilogue fused into the matmul tile."""
    if activation not in _ACTS:
        raise ValueError(f"unsupported activation {activation!r}; "
                         f"options: {sorted(_ACTS)}")
    if _dense_shapes_ok(x, w):
        return _dense_pallas(x, w, b, activation)
    return _dense_ref(x, w, b, activation)


def _fused_dense_fwd(x, w, b, activation):
    out = fused_dense(x, w, b, activation)
    return out, (x, w, out)


def _fused_dense_bwd(activation, res, g):
    x, w, out = res
    d = g * _derivative(activation, out)
    return d @ w.T, x.T @ d, d.sum(0)


fused_dense.defvjp(_fused_dense_fwd, _fused_dense_bwd)


# ------------------------------------------------------------- lstm gates ----

# A/B switch for the fused-gate kernel (None = auto by shape): the bench
# quantifies the kernel's value by running the same LSTM stage with the
# kernel forced off (set_lstm_gates(False) → plain lax gate math).
_lstm_gates_override: "bool | None" = None


def set_lstm_gates(enabled: "bool | None") -> None:
    global _lstm_gates_override
    _lstm_gates_override = enabled


def _lstm_gates_kernel(ifog_ref, c_ref, c_out_ref, h_out_ref):
    """(B, 4H) fused preactivations + (B, H) c_prev -> c_new, h_new.
    Gate order i,f,o,g (ref LSTM.java iFog layout).

    Gate math runs in f32 regardless of the storage dtype: with the math
    left in bf16 Mosaic rejects the kernel ('vector.broadcast' op failed to
    verify that source operand and result have same element type; round-4
    finding, seen again on jax 0.9.0 / libtpu 0.0.34, PR 21 chip run), and
    f32 VPU math keeps the cell update numerically stable under the bf16
    policy."""
    h = c_ref.shape[-1]
    ifog = ifog_ref[:].astype(jnp.float32)
    i = jax.nn.sigmoid(ifog[:, 0 * h : 1 * h])
    f = jax.nn.sigmoid(ifog[:, 1 * h : 2 * h])
    o = jax.nn.sigmoid(ifog[:, 2 * h : 3 * h])
    gg = jnp.tanh(ifog[:, 3 * h : 4 * h])
    c_new = f * c_ref[:].astype(jnp.float32) + i * gg
    c_out_ref[:] = c_new.astype(c_out_ref.dtype)
    h_out_ref[:] = (o * jnp.tanh(c_new)).astype(h_out_ref.dtype)


# Rows x hidden of one lstm_gates tile. The kernel's scoped VMEM holds the
# (tile_b, 4h) + 3 x (tile_b, h) f32 blocks double-buffered, about 65 B per
# (row, hidden) element: on a v5e (16 MiB scoped limit) 256 Ki elements asked
# for 16.96 MB and was refused, 128 Ki compiled at every shape tried
# (256 x 512, 128 x 1024, 64 x 2048; PR 21 chip run).
_LSTM_TILE_ELEMS = 128 * 1024


def _lstm_gates_pallas(ifog: Array, c_prev: Array):
    b, h = c_prev.shape
    tile_b = min(256, _LSTM_TILE_ELEMS // h // 8 * 8, b)  # whole sublanes
    grid = (_cdiv(b, tile_b),)
    return pl.pallas_call(
        _lstm_gates_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, 4 * h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_b, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_b, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h), c_prev.dtype),
            jax.ShapeDtypeStruct((b, h), c_prev.dtype),
        ],
        interpret=_interpret(),
    )(ifog, c_prev)


def _lstm_gates_ref(ifog: Array, c_prev: Array):
    h = c_prev.shape[-1]
    i = jax.nn.sigmoid(ifog[:, 0 * h : 1 * h])
    f = jax.nn.sigmoid(ifog[:, 1 * h : 2 * h])
    o = jax.nn.sigmoid(ifog[:, 2 * h : 3 * h])
    gg = jnp.tanh(ifog[:, 3 * h : 4 * h])
    c_new = f * c_prev + i * gg
    return c_new, o * jnp.tanh(c_new)


@jax.custom_vjp
def lstm_gates(ifog: Array, c_prev: Array):
    """Fused LSTM cell nonlinearity: (c_new, h_new) from (B,4H) + (B,H)."""
    h = c_prev.shape[-1]
    # h bound keeps tile_b >= 64 rows under _LSTM_TILE_ELEMS
    use_pallas = (h % 128 == 0 and ifog.shape[0] % 8 == 0 and h <= 2048)
    if _lstm_gates_override is not None:
        use_pallas = _lstm_gates_override and use_pallas
    if use_pallas:
        return _lstm_gates_pallas(ifog, c_prev)
    return _lstm_gates_ref(ifog, c_prev)


def _lstm_gates_fwd(ifog, c_prev):
    # outputs come from the fused kernel (so training uses it too); the gate
    # residuals are recomputed in lax — cheap VPU work XLA fuses around the
    # kernel call
    c_new, h_new = lstm_gates(ifog, c_prev)
    h = c_prev.shape[-1]
    i = jax.nn.sigmoid(ifog[:, 0 * h : 1 * h])
    f = jax.nn.sigmoid(ifog[:, 1 * h : 2 * h])
    o = jax.nn.sigmoid(ifog[:, 2 * h : 3 * h])
    gg = jnp.tanh(ifog[:, 3 * h : 4 * h])
    tanh_c = jnp.tanh(c_new)
    return (c_new, h_new), (i, f, o, gg, c_prev, tanh_c)


def _lstm_gates_bwd(res, grads):
    i, f, o, gg, c_prev, tanh_c = res
    dc_new, dh = grads
    do = dh * tanh_c
    dc = dc_new + dh * o * (1.0 - tanh_c * tanh_c)
    di = dc * gg
    df = dc * c_prev
    dgg = dc * i
    dc_prev = dc * f
    d_ifog = jnp.concatenate([
        di * i * (1.0 - i),
        df * f * (1.0 - f),
        do * o * (1.0 - o),
        dgg * (1.0 - gg * gg),
    ], axis=-1)
    return d_ifog, dc_prev


lstm_gates.defvjp(_lstm_gates_fwd, _lstm_gates_bwd)
