"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no long-context mechanism at all (SURVEY.md §2.5: LSTM
materializes whole sequences in Java, no attention anywhere). These are the
TPU-native long-context primitives the rebuild adds as first-class citizens:

- ``ring_attention``: each device holds one sequence shard of Q/K/V; K/V
  blocks rotate around the ring via ``ppermute`` (ICI neighbor exchange)
  while a streaming online-softmax accumulates the output — memory per
  device stays O(T/P), communication overlaps block compute.
- ``ulysses_attention``: all-to-all swaps the sharded axis from sequence to
  heads, computes full-sequence attention locally on H/P heads, swaps back —
  cheaper at moderate sequence lengths when H divides the mesh axis.

Both run under ``shard_map`` over a named mesh axis and are validated on the
8-device CPU mesh in tests (the driver dry-runs the same path).

Attention-core seam: the LOCAL math inside both variants goes through
ops/flash_attention's core selection (per-call ``attn_impl=`` >
``set_attention_impl`` > ``DL4J_TPU_ATTN_IMPL`` env > auto by local length).
For the ring that means each rotated K/V block is processed by the blockwise
online-softmax tiles (``blockwise_block_partials`` — O(block) memory, exact
logsumexp merge) instead of a materialized (T_local, T_local) score
rectangle; for ulysses the post-AllToAll full-sequence attention runs
through ``attention_core``. The composed dp×sp×ep flagship path therefore
gets blockwise math end to end.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

_NEG_INF = -1e30


def _block_attn(q, k, v, bias):
    """Scores for one (q-block, k-block) pair: returns (scores_max,
    exp-normalized partials). q: (B,H,Tq,D), k/v: (B,H,Tk,D)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1] * 1.0)
    if bias is not None:
        scores = scores + bias
    m = scores.max(axis=-1)  # (B,H,Tq)
    p = jnp.exp(scores - m[..., None])
    pv = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m, p.sum(-1), pv


def _ring_block_core(q, k_cur, v_cur, q_offset, k_offset, causal: bool,
                     impl: str):
    """The attention seam inside the ring: one rotated (Q-shard, K/V-shard)
    pair → online-softmax partials (bm, bl, bo) for the merge.

    "blockwise" tiles the pair through flash_attention's online softmax
    (O(block) score memory, the composed-flagship fast path) and reports the
    normalized form (m=lse, l=1, o=o_norm) — algebraically the same merge;
    "dense" is the original materializing ``_block_attn``.
    """
    if impl == "blockwise":
        from deeplearning4j_tpu.ops.flash_attention import (
            blockwise_block_partials,
        )

        o_norm, lse = blockwise_block_partials(
            q, k_cur, v_cur, q_offset=q_offset, k_offset=k_offset,
            causal=causal)
        return lse, jnp.ones_like(lse), o_norm
    if causal:
        t_q, t_k = q.shape[2], k_cur.shape[2]
        q_pos = q_offset + jnp.arange(t_q)  # (Tq,)
        k_pos = k_offset + jnp.arange(t_k)  # (Tk,)
        mask = q_pos[:, None] >= k_pos[None, :]
        bias = jnp.where(mask, 0.0, _NEG_INF)[None, None]
    else:
        bias = None
    return _block_attn(q, k_cur, v_cur, bias)


def _ring_attention_sharded(q, k, v, axis_name: str, causal: bool,
                            impl: str = "dense", prefetch: bool = True):
    """Per-device body under shard_map. q/k/v: (B, H, T_local, D).

    ``prefetch=True`` (ISSUE 14, the default) issues the rotation of block
    b+1 BEFORE block b's attention tiles consume the current buffer — the
    rotate reads only the loop carry, never the attend's outputs, so
    ordering it first lets the collective-permute fly under the flash
    tiles (rotate-then-attend on the double buffer the carry already is).
    ``prefetch=False`` keeps the historical rotate-after-attend trace
    order — the parity oracle: both orders compute the IDENTICAL values
    (pinned bitwise in tests/test_ring_attention.py)."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[2]

    def body(step, carry):
        o, l, m, k_cur, v_cur = carry
        # k_cur originated on device (my_idx - step) mod P
        src = (my_idx - step) % axis_size

        def attend(o, l, m):
            # XProf phase name for the per-rotation attention (the rotation
            # index is the loop-carried `step`; each device's timeline row
            # shows axis_size of these scopes per call)
            with jax.named_scope(f"ring_attend[{axis_name}]"):
                bm, bl, bo = _ring_block_core(
                    q, k_cur, v_cur, my_idx * t_local, src * t_local, causal,
                    impl)
            # online softmax merge
            new_m = jnp.maximum(m, bm)
            scale_old = jnp.exp(m - new_m)
            scale_new = jnp.exp(bm - new_m)
            new_o = o * scale_old[..., None] + bo * scale_new[..., None]
            new_l = l * scale_old + bl * scale_new
            return new_o, new_l, new_m

        def attend_maybe_skipped(o, l, m):
            if causal:
                # K blocks from strictly-later devices are fully masked —
                # skip both einsums (roughly half of all (device, step)
                # pairs)
                return jax.lax.cond(
                    src <= my_idx, attend, lambda o, l, m: (o, l, m), o, l, m
                )
            return attend(o, l, m)

        # rotate K/V one step around the ring (device i -> i+1); the last
        # step's blocks are never attended to, so skip that exchange
        def rotate(kv):
            k_c, v_c = kv
            perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
            with jax.named_scope(f"ring_kv_rotate[{axis_name}]"):
                return (jax.lax.ppermute(k_c, axis_name, perm),
                        jax.lax.ppermute(v_c, axis_name, perm))

        def do_rotate():
            return jax.lax.cond(
                step < axis_size - 1, rotate, lambda kv: kv, (k_cur, v_cur)
            )

        if prefetch:
            # comm first: the next block starts rotating while this
            # block's tiles run on the already-received buffer
            k_nxt, v_nxt = do_rotate()
            o, l, m = attend_maybe_skipped(o, l, m)
        else:
            o, l, m = attend_maybe_skipped(o, l, m)
            k_nxt, v_nxt = do_rotate()
        return o, l, m, k_nxt, v_nxt

    # f32 accumulators regardless of input dtype (the blockwise core's
    # partials are f32; dense partials promote) — matching flash_attention's
    # accumulation discipline
    o0 = jnp.zeros(q.shape, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    m0 = jnp.full(q.shape[:3], _NEG_INF, jnp.float32)
    o, l, m, _, _ = jax.lax.fori_loop(0, axis_size, body, (o0, l0, m0, k, v))
    # fully-masked rows (can't happen with causal self-attention, where
    # position t always sees itself) would have l == 0; guard anyway
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(q: Array, k: Array, v: Array, mesh: Mesh, axis: str,
                   causal: bool = False,
                   batch_axis: Optional[str] = None,
                   attn_impl: Optional[str] = None,
                   prefetch: bool = True) -> Array:
    """Multi-head attention with the SEQUENCE axis sharded over ``axis``.

    q/k/v: (B, H, T, D) global arrays (T divisible by the axis size).
    Returns (B, H, T, D) with the same sharding.

    ``batch_axis`` composes dp×sp on a 2-D mesh: the batch dim is sharded
    over that axis, so each data-parallel row runs its own K/V ring over
    ``axis`` — the composed-mesh path used by models/transformer_lm.py.

    ``attn_impl`` forces the per-rotated-block core ("blockwise" | "dense");
    default None resolves through flash_attention's override/env/auto chain
    on the LOCAL block length T/P ("flash" resolves to blockwise here — the
    fused pallas kernel is not a mergeable per-block core).

    ``prefetch`` (ISSUE 14, default True) starts the rotation of block
    b+1 before block b's tiles consume it — bit-identical values, comm
    issued under compute; ``prefetch=False`` is the historical
    rotate-after-attend oracle for A/B (bench ``comm_overlap`` stage).
    """
    from deeplearning4j_tpu.ops.flash_attention import resolve_attention_impl

    t_local = q.shape[2] // mesh.shape[axis]
    impl = attn_impl or resolve_attention_impl(t_local)
    if impl == "flash":
        impl = "blockwise"
    spec = P(batch_axis, None, axis, None)
    fn = partial(_ring_attention_sharded, axis_name=axis, causal=causal,
                 impl=impl, prefetch=prefetch)
    sharded = jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return sharded(q, k, v)


def _ulysses_sharded(q, k, v, axis_name: str, causal: bool,
                     impl: Optional[str]):
    """all-to-all: (B, H, T/P, D) -> (B, H/P, T, D), full local attention,
    then back. Requires H % P == 0."""
    from deeplearning4j_tpu.ops.flash_attention import attention_core

    # split heads across devices, gather the full sequence
    def seq_to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    with jax.named_scope("ulysses_all2all_seq2heads"):
        qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # the post-AllToAll core runs the SAME seam as every other attention
    # call (per-call impl > global override > env > auto on the full T)
    with jax.named_scope("ulysses_local_attention"):
        out = attention_core(qh, kh, vh, causal=causal, impl=impl)
    with jax.named_scope("ulysses_all2all_heads2seq"):
        return heads_to_seq(out)


def ulysses_attention(q: Array, k: Array, v: Array, mesh: Mesh, axis: str,
                      causal: bool = False,
                      attn_impl: Optional[str] = None) -> Array:
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all to head
    sharding, local attention through the flash_attention core seam
    (``attn_impl`` forces it; default = override/env/auto on the full
    sequence length), all-to-all back. H must be divisible by the axis
    size."""
    axis_size = mesh.shape[axis]
    if q.shape[1] % axis_size != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[1]}) divisible by axis size "
            f"({axis_size}); use ring_attention instead"
        )
    spec = P(None, None, axis, None)
    fn = partial(_ulysses_sharded, axis_name=axis, causal=causal,
                 impl=attn_impl)
    sharded = jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return sharded(q, k, v)


def reference_attention(q: Array, k: Array, v: Array,
                        causal: bool = False) -> Array:
    """Unsharded dense attention for verification."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1] * 1.0)
    if causal:
        t = q.shape[2]
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)


def sequence_sharding(mesh: Mesh, axis: str) -> NamedSharding:
    """NamedSharding placing the sequence axis of (B,H,T,D) on ``axis``."""
    return NamedSharding(mesh, P(None, None, axis, None))
