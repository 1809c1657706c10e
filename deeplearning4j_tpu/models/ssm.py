"""Mamba-2's mixer: the state-space layer of a hybrid decoder.

The layer (Dao and Gu, "Transformers are SSMs", 2024; the ``mamba`` layers
of HF ``GraniteMoeHybrid``), on the normed input u:

- ``[z, xBC, dt] = u @ in_proj``, widths ``d_inner | d_inner + 2 G N | H``;
- ``xBC = silu(causal depthwise conv1d_K(xBC) + conv_b)``, split into
  ``x`` (H heads of P), ``B`` and ``C`` (G groups of N; head h reads group
  ``h // (H / G)``);
- ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
- per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` and
  ``y_t = S_t C_t + D x_t``, the state S of shape (P, N);
- ``out = (RMSNorm(y * silu(z)) * norm_g) @ out_proj``, the norm over all
  ``d_inner``.

Two forms of the recurrence. The prompt pass runs the SSD **chunked scan**
(``ssd_chunked``): within a chunk of ``ssm_chunk`` rows the outputs are a
masked matmul, across chunks a short sequential scan carries the state in
float32. The cached step runs the **one-token update** (``mixer_step``) on
the state a slot keeps. A prompt right-padded to its bucket has dt and x
zeroed past its length, so the state crosses the padding unchanged
(``exp(0) = 1``, input 0) and the state handed to the cache is the one at
the prompt's last real token; the conv state is that prompt's last K - 1
rows of ``xBC`` before the conv (zeros where the prompt is shorter).

The cached step is one Pallas kernel (``_state_step``) that reads each
slot's state of the layer once, advances it in place and forms ``S_t C_t``
from the same tile (XLA's form read the state a second time for the
product with C).

Named scopes: ``ssm_scan`` covers the chunked scan of the prompt pass,
``ssm_step`` the cached step's read of a slot's state, its update, its
output and its write back; both lie inside the block's ``lm_ssm``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import pallas_kernels

Array = jax.Array

SSM_SCAN_SCOPE = "ssm_scan"
SSM_STEP_SCOPE = "ssm_step"

_F32 = jnp.float32
# the recurrence's products in float32, as the configurations state it: at
# the default precision the v5e would take one bfloat16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


def widths(spec) -> dict:
    """The mixer's sizes from a ``BlockSpec``."""
    h, p, n, g = spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state, \
        spec.ssm_groups
    d_inner = h * p
    return {"heads": h, "head_dim": p, "state": n, "groups": g,
            "d_inner": d_inner, "conv_dim": d_inner + 2 * g * n,
            "kernel": spec.ssm_conv}


def init_mixer(key: Array, d_model: int, spec) -> dict:
    """One mixer's weights. The projections are N(0, 1/fan_in); the conv
    N(0, 1/K) with a zero bias; ``A``, ``dt`` and ``D`` as Mamba-2 draws
    them: A uniform in [1, 16], dt log-uniform in [0.001, 0.1] (floored at
    1e-4) stored through the inverse softplus, D = 1."""
    w = widths(spec)
    ks = jax.random.split(key, 5)
    n = jax.random.normal
    h, d_inner, conv_dim = w["heads"], w["d_inner"], w["conv_dim"]
    a = jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    lo, hi = jnp.log(0.001), jnp.log(0.1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(ks[3], (h,)) * (hi - lo)
                             + lo), 1e-4)
    return {
        "in_proj": n(ks[0], (d_model, d_inner + conv_dim + h))
        / (d_model ** 0.5),
        "conv_w": n(ks[1], (w["kernel"], conv_dim)) / (w["kernel"] ** 0.5),
        "conv_b": jnp.zeros((conv_dim,)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(a),
        "D": jnp.ones((h,)),
        "norm_g": jnp.ones((d_inner,)),
        "out_proj": n(ks[4], (d_inner, d_model)) / (d_inner ** 0.5),
    }


def _split(zxbcdt: Array, w: dict) -> tuple:
    d_inner, conv_dim = w["d_inner"], w["conv_dim"]
    return (zxbcdt[..., :d_inner], zxbcdt[..., d_inner:d_inner + conv_dim],
            zxbcdt[..., d_inner + conv_dim:])


def _split_xbc(xbc: Array, w: dict) -> tuple:
    """(..., conv_dim) -> x (..., G, H/G, P), B and C (..., G, N)."""
    g, n, d_inner = w["groups"], w["state"], w["d_inner"]
    lead = xbc.shape[:-1]
    x = xbc[..., :d_inner].reshape(lead + (g, w["heads"] // g,
                                           w["head_dim"]))
    b = xbc[..., d_inner:d_inner + g * n].reshape(lead + (g, n))
    c = xbc[..., d_inner + g * n:].reshape(lead + (g, n))
    return x, b, c


def _dt_a(p: dict, dt: Array, w: dict) -> tuple:
    """dt (..., H) -> (softplus(dt + dt_bias), A) in float32, both split
    (..., G, H/G)."""
    g = w["groups"]
    shape = dt.shape[:-1] + (g, w["heads"] // g)
    dt = jax.nn.softplus(dt.astype(_F32) + p["dt_bias"].astype(_F32))
    a = -jnp.exp(p["A_log"].astype(_F32))
    return dt.reshape(shape), a.reshape((g, w["heads"] // g))


def _gated_out(p: dict, y: Array, z: Array, eps: float,
               dtype) -> Array:
    """RMSNorm over all of d_inner of y * silu(z), its gain, out_proj."""
    y = y * jax.nn.silu(z.astype(_F32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = (y * p["norm_g"].astype(_F32)).astype(dtype)
    return y @ p["out_proj"]


def ssd_chunked(x: Array, dt: Array, a: Array, b: Array, c: Array,
                chunk: int, initial=None) -> tuple:
    """The SSD chunked scan, in float32. x (n, T, G, R, P), dt (n, T, G, R)
    (0 on a padding row), a (G, R), b and c (n, T, G, N); ``initial`` (n, G,
    R, P, N) or None for zeros. Returns y (n, T, G, R, P) without the D term
    and the state after row T - 1, (n, G, R, P, N). T need not be a multiple
    of ``chunk``: the rows added are padding."""
    n, t = x.shape[:2]
    q = chunk
    nc = -(-t // q)
    if nc * q != t:
        pad = lambda v: jnp.pad(  # noqa: E731
            v, ((0, 0), (0, nc * q - t)) + ((0, 0),) * (v.ndim - 2))
        x, dt, b, c = pad(x), pad(dt), pad(b), pad(c)

    def chunks(v):
        return v.reshape((n, nc, q) + v.shape[2:])

    x, dt, b, c = chunks(x), chunks(dt), chunks(b), chunks(c)
    cs = jnp.cumsum(dt * a, axis=2)                     # (n, c, Q, G, R)
    # inside a chunk: y_i = sum_{j <= i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j
    cs_t = jnp.moveaxis(cs, 2, -1)                      # (n, c, G, R, Q)
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, cs_t[..., :, None] - cs_t[..., None, :],
                              -jnp.inf))                # (n, c, G, R, Q, Q)
    cb = jnp.einsum("zcigs,zcjgs->zcgij", c, b, precision=_HIGHEST)
    weights = cb[:, :, :, None] * decay * jnp.moveaxis(dt, 2, -1)[..., None, :]
    y = jnp.einsum("zcgrij,zcjgrp->zcigrp", weights, x, precision=_HIGHEST)
    # each chunk's own part of the state at its last row
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dt           # (n, c, Q, G, R)
    own = jnp.einsum("zcjgs,zcjgr,zcjgrp->zcgrps", b, to_end, x,
                     precision=_HIGHEST)
    # across chunks, in order: the state entering each chunk
    s0 = (jnp.zeros(own.shape[:1] + own.shape[2:], _F32) if initial is None
          else initial.astype(_F32))

    def across(s, step):
        total, part = step
        return total[..., None, None] * s + part, s

    final, entering = jax.lax.scan(
        across, s0, (jnp.moveaxis(jnp.exp(cs[:, :, -1]), 1, 0),
                     jnp.moveaxis(own, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # (n, c, G, R, P, N)
    y = y + jnp.einsum("zcigs,zcgrps->zcigrp", c, entering,
                       precision=_HIGHEST) \
        * jnp.exp(cs)[..., None]
    y = y.reshape((n, nc * q) + y.shape[3:])[:, :t]
    return y, final


def _conv(window: Array, w: Array, bias: Array, rows: int) -> Array:
    """Causal depthwise conv: ``window`` (n, K - 1 + rows, C) -> (n, rows,
    C), row t the sum over k of w[k] * window[t + k], plus the bias, then
    silu; in float32."""
    k = w.shape[0]
    window = window.astype(_F32)
    out = bias.astype(_F32) + sum(w[i].astype(_F32) * window[:, i:i + rows]
                                  for i in range(k))
    return jax.nn.silu(out)


def _real_rows(lengths: Array, t: int) -> Array:
    """(n, T): whether a row of a right-padded prompt is one of its own (a
    function of its own so that the benchmark can plant padding in the
    state: ``benchmark/models/granite.py``)."""
    return jnp.arange(t)[None, :] < lengths[:, None]


def mixer_prefill(p: dict, u: Array, spec, lengths) -> tuple:
    """The mixer over a right-padded prompt: u (n, T, d), ``lengths`` the
    real rows of each (n,) or one for all. Returns (out (n, T, d), (conv
    state (n, K - 1, conv_dim) in u's type, state (n, H, P, N) float32)),
    both as they stand after each prompt's last real row."""
    w = widths(spec)
    n, t, _ = u.shape
    k = w["kernel"]
    z, xbc, dt = _split(u @ p["in_proj"], w)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (n,))
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv_state = jax.vmap(lambda v, m: jax.lax.dynamic_slice_in_dim(
        v, m, k - 1, 0))(padded, lengths)
    x, b, c = _split_xbc(_conv(padded, p["conv_w"], p["conv_b"], t), w)
    dt, a = _dt_a(p, dt, w)
    real = _real_rows(lengths, t)
    dt = jnp.where(real[..., None, None], dt, 0.0)
    x = jnp.where(real[..., None, None, None], x, 0.0)
    with jax.named_scope(SSM_SCAN_SCOPE):
        y, state = ssd_chunked(x, dt, a, b, c, spec.ssm_chunk)
    y = y + p["D"].astype(_F32).reshape(a.shape)[..., None] * x
    out = _gated_out(p, y.reshape(n, t, w["d_inner"]), z, spec.norm_eps,
                     u.dtype)
    return out, (conv_state, state.reshape(n, w["heads"], w["head_dim"],
                                           w["state"]))


# The leaves' layouts as the v5e lays them out by default, so that the
# carried leaves are never re-laid round the layer loop: the conv state
# (L, S, K - 1, C) with slots second-minor (its K - 1 rows are too few to
# tile), the recurrent state row-major.
_STATE_LAYOUT = {4: Layout(major_to_minor=(0, 2, 1, 3)),
                 5: Layout(major_to_minor=(0, 1, 2, 3, 4))}


def _write_layer(cache: Array, new: Array, layer: Array) -> Array:
    """``new`` (S, ...) into the whole leaf (L, S, ...) at ``layer``, in
    place, in the layout the leaf arrives in (as ``_write_cache_rows``
    keeps the K/V leaves)."""
    at = (layer,) + (0,) * (cache.ndim - 1)
    return with_layout_constraint(
        jax.lax.dynamic_update_slice(cache, new[None].astype(cache.dtype), at),
        _STATE_LAYOUT[cache.ndim])


def _advance(state: Array, decay: Array, update: Array) -> Array:
    """One row of the recurrence on a float32 state (a function of its own
    so that the benchmark can plant a state kept in another type)."""
    return state * decay + update


# Most heads of one slot a grid step of ``_state_step`` (the block is the
# largest divisor of a group's heads up to this): a (64, 64, 128) float32
# block is 2 MB, in and out double-buffered 8.9 MB of VMEM, and Granite's 48
# slots are 48 steps a layer. At Granite's widths on a v5e (48 slots, the
# layer loop over all 36 layers) a layer took 0.409, 0.345, 0.339 and 0.338
# ms at 8, 16, 32 and 64 heads (493 to 596 GB/s of state read and written);
# 2 and 4 slots a step at 64 heads 0.341 (VMEM limit raised); the update
# alone without y 0.336, XLA's update alone 0.341, XLA's update and its
# product with C 0.474.
STATE_HEAD_BLOCK = 64


def _state_step_kernel(layer_ref, state_ref, decay_ref, dtx_ref, b_ref,
                       c_ref, state_out_ref, y_ref):
    """One slot's block of heads: state (hb, P, N) advanced by decay (hb,
    1) and (dt x) (hb, P) times B (1, N), written back, and y (hb, P) its
    sum over N against C (1, N), in float32 on the VPU."""
    del layer_ref  # read by the index maps
    state = _advance(state_ref[...], decay_ref[...][..., None],
                     dtx_ref[...][..., None] * b_ref[...][None])
    state_out_ref[...] = state
    y_ref[...] = jnp.sum(state * c_ref[...][None], axis=-1)


def _state_step(ssm_cache: Array, layer: Array, decay: Array, dtx: Array,
                b: Array, c: Array) -> tuple:
    """The one-row update of layer ``layer`` of the whole leaf ``ssm_cache``
    (L, S, H, P, N), in place, and its output: decay (S, H), dtx (S, H, P),
    b and c (S, G, N), all float32. Every (slot, block of heads) tile of the
    layer is read once and written once; the other layers are not touched.
    Each block's last two dimensions are whole dimensions of its array, so
    Mosaic takes any widths. Returns (the leaf, y (S, H, P))."""
    _, s, h, p, n = ssm_cache.shape
    g = b.shape[1]
    hb = math.gcd(STATE_HEAD_BLOCK, h // g)
    blocks_a_group = h // g // hb
    tile = pl.BlockSpec((None, None, hb, p, n),
                        lambda i, j, layer: (layer[0], i, j, 0, 0))
    rows = lambda width: pl.BlockSpec(  # noqa: E731
        (None, None, hb, width), lambda i, j, layer: (i, j, 0, 0))
    group = pl.BlockSpec((None, None, 1, n), lambda i, j, layer: (
        i, j // blocks_a_group, 0, 0))
    cache, y = pl.pallas_call(
        _state_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s, h // hb),
            in_specs=[tile, rows(1), rows(p), group, group],
            out_specs=[tile, rows(p)]),
        out_shape=[jax.ShapeDtypeStruct(ssm_cache.shape, _F32),
                   jax.ShapeDtypeStruct((s, h // hb, hb, p), _F32)],
        input_output_aliases={1: 0},
        interpret=pallas_kernels._interpret(),
        name="ssm_state_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), ssm_cache,
      decay.reshape(s, h // hb, hb, 1), dtx.reshape(s, h // hb, hb, p),
      b[:, :, None], c[:, :, None])
    return cache, y.reshape(s, h, p)


def mixer_step(p: dict, u: Array, spec, conv_cache: Array, ssm_cache: Array,
               layer: Array) -> tuple:
    """The mixer for one new row a slot: u (S, 1, d); ``conv_cache`` (L, S,
    K - 1, conv_dim) and ``ssm_cache`` (L, S, H, P, N) the WHOLE carried
    leaves, of which this reads and rewrites layer ``layer``. Returns (out
    (S, 1, d), (conv_cache, ssm_cache))."""
    w = widths(spec)
    s = u.shape[0]
    g, r = w["groups"], w["heads"] // w["groups"]
    z, xbc, dt = _split(u[:, 0] @ p["in_proj"], w)
    window = jnp.concatenate(
        [jax.lax.dynamic_index_in_dim(conv_cache, layer, 0, keepdims=False),
         xbc[:, None].astype(conv_cache.dtype)], axis=1)   # (S, K, C)
    conv_cache = _write_layer(conv_cache, window[:, 1:], layer)
    x, b, c = _split_xbc(_conv(window, p["conv_w"], p["conv_b"], 1)[:, 0], w)
    dt, a = _dt_a(p, dt, w)
    with jax.named_scope(SSM_STEP_SCOPE):
        ssm_cache, y = _state_step(ssm_cache, layer, jnp.exp(dt * a),
                                   dt[..., None] * x, b, c)
        y = y.reshape(x.shape)
    y = y + p["D"].astype(_F32).reshape(g, r)[..., None] * x
    out = _gated_out(p, y.reshape(s, w["d_inner"]), z, spec.norm_eps,
                     u.dtype)
    return out[:, None], (conv_cache, ssm_cache)
