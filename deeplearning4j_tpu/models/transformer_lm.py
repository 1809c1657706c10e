"""Transformer LM with MoE FFNs — the composed-parallelism flagship.

The reference is pre-transformer (SURVEY.md §2.5); rounds 3-4 added the
parallel axes (dp/tp/sp/pp/ep) individually, and the round-4 verdict's gap
was that no model ever COMPOSED them. This model closes it: ``n_layers``
causal decoder blocks (pre-LN multi-head attention + pre-LN top-2 MoE FFN,
both with residuals, stacked via ``lax.scan`` over per-layer params between
an embedding and a vocab decoder) that train on:

- a single device (dense reference — the parity oracle),
- dp×ep: batch sharded over "data", experts over "expert"
  (``make_composed_train_step``),
- dp×sp×ep: additionally the sequence axis over "sp" with ring attention
  rotating K/V blocks inside each data-parallel row — three parallelism
  strategies in ONE jitted step,
- dp×pp: the layer stack split at LAYER BOUNDARIES into pipeline stages on
  a "pipe" axis, microbatches sharded over "data"
  (``make_pp_stages``/parallel.pipeline).

Attention core: every path goes through ops/flash_attention's selection
seam — an explicit ``attn_impl=`` argument on each builder, else the
``set_attention_impl`` / ``DL4J_TPU_ATTN_IMPL`` overrides, else auto by
sequence length (blockwise flash for T at or above the dispatch threshold,
dense below it — the same shape gating the conv emitter uses). On the
dp×sp×ep mesh the ring's per-rotated-block core runs the same seam, so the
composed flagship gets blockwise math end to end (ring_attention
``attn_impl`` pass-through).

All composed paths are pinned against the dense reference to 1e-5 (loss AND
updated params) in tests/test_composed.py and gated by the driver's
``dryrun_multichip``. Sharding is GSPMD-first: the model body is pure; the
collectives live in ``ring_attention``/``moe_apply`` (shard_map), and
jax.grad outside them gets exact gradients through psum/ppermute
transposes (expert grads reduce over token axes automatically).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn.layers.attention import (
    _layernorm,
    _merge_heads,
    _split_heads,
)
from deeplearning4j_tpu.ops.flash_attention import attention_core
from deeplearning4j_tpu.parallel.moe import (
    EXPERT_AXIS,
    _routing,
    dropped_route_fraction,
    load_balance_loss,
    moe_apply,
    route_shards,
    router_load_fraction,
)
from deeplearning4j_tpu.parallel.ring_attention import ring_attention

Array = jax.Array

DATA_AXIS = "data"
SEQ_AXIS = "sp"

# The named scopes of the shared blocks: every flagship program (train,
# prefill, decode, verify, chunk) carries them in the ``op_name`` of its
# compiled instructions, which is where a device trace is mapped back to
# the model (benchmark/trace/reduce.py). No other scope in the package
# begins with ``lm_``, so that needle means "under any of them". A scope
# is metadata: outputs, instruction numbering and the compile-cache key do
# not change with it, so an executable cached before a scope existed comes
# back without it.
LM_SCOPES = ("lm_embed", "lm_attn", "lm_cache_write", "lm_moe", "lm_loss",
             "lm_update", "lm_sample")
# What only the block-diffusion step names: its sampling, confidence and
# choice of positions. Apart from LM_SCOPES, whose every name every flagship
# serve program carries (tests/benchmark holds them to that). The rotary term
# has no scope of its own: it is 0.5% of a block step on the v5e (PR 28).
LM_UNMASK_SCOPE = "lm_unmask"
LM_SPEC_SCOPES = (LM_UNMASK_SCOPE,)
# What only a spec with a layer pattern names: the state-space mixer (with
# ``ssm_scan`` and ``ssm_step`` of models/ssm.py inside it) and the dense
# gated FFN. Apart from LM_SCOPES for the same reason as LM_SPEC_SCOPES.
LM_SSM_SCOPE = "lm_ssm"
LM_MLP_SCOPE = "lm_mlp"
LM_HYBRID_SCOPES = (LM_SSM_SCOPE, LM_MLP_SCOPE)


class BlockSpec(NamedTuple):
    """What kind of decoder block the serving programs run: static and
    hashable, read at trace time by the one set of block functions
    (``_init_block``, ``lm_prefill``, ``_cached_layers`` and what they
    call), so that the default compiles to exactly the flagship's programs
    and another model is the same functions under another spec. The query
    head count stays the ``n_heads`` argument it always was. The training
    step factories do not take a spec yet."""
    norm: str = "layernorm"     # "layernorm" (gain, bias, eps 1e-5) | "rmsnorm"
    norm_eps: float = 1e-5      # of the RMSNorm
    final_norm: bool = False    # a norm between the last block and the head
    rope_theta: Optional[float] = None  # rotary positions on q and k; None: no positions
    qk_norm: bool = False       # RMSNorm over each head of q and k, gains shared by heads
    n_kv_heads: Optional[int] = None    # K/V heads (the cache's); None: one a query head
    head_dim: Optional[int] = None      # None: d_model // n_heads
    ffn: str = "relu"           # experts: "relu" (w1, w2) | "swiglu" (wg, wu, wd);
    #                             "dense_swiglu": one gated FFN (w_in, w_out), no router
    norm_topk_prob: bool = True  # gates renormalised over the chosen k
    bias: bool = True           # expert and output biases
    accum_f32: bool = False     # router logits, scores and logits leave their matmul in float32
    attn_mask: str = "causal"   # "causal" | "block": i sees j iff j // B <= i // B
    block_length: int = 1       # B
    generation: str = "autoregressive"  # | "block_diffusion" (serve/engine.py)
    denoising_steps: int = 1    # D: B // D positions unmasked a denoising forward
    mask_token_id: Optional[int] = None
    # a hybrid of layer kinds (ISSUE 37): one period of "attention" and
    # "mamba" (models/ssm.py) layers, repeated through the depth; None: every
    # layer the attention block above
    layer_pattern: Optional[tuple] = None
    ssm_heads: int = 0          # H of the mixer, P each (d_inner = H * P)
    ssm_head_dim: int = 0
    ssm_state: int = 0          # N
    ssm_groups: int = 1         # G groups of B and C
    ssm_conv: int = 4           # K of the causal depthwise conv
    ssm_chunk: int = 256        # rows a chunk of the prompt pass's scan
    embedding_multiplier: float = 1.0   # on the embedding's rows
    residual_multiplier: float = 1.0    # on both branches before their residual
    attention_multiplier: Optional[float] = None  # softmax scale; None: 1/sqrt(Dh)
    logits_scaling: float = 1.0  # logits divided by it
    tied_head: bool = False     # the head is the embedding, transposed

    def kv_heads(self, n_heads: int) -> int:
        return self.n_kv_heads or n_heads

    def head_size(self, d_model: int, n_heads: int) -> int:
        return self.head_dim or d_model // n_heads


FLAGSHIP_SPEC = BlockSpec()


def _init_block(key: Array, d_model: int, n_heads: int, n_experts: int,
                d_ff: int, spec: BlockSpec = FLAGSHIP_SPEC,
                init_scale: Optional[float] = None) -> dict:
    ks = jax.random.split(key, 6)
    n = jax.random.normal
    s_d = 1.0 / (d_model ** 0.5) if init_scale is None else init_scale

    def down(k):  # the flagship divides: a product would round otherwise
        w = n(k, (n_experts, d_ff, d_model))
        return w / (d_ff ** 0.5) if init_scale is None else w * init_scale

    hd = spec.head_size(d_model, n_heads)
    d_q, d_kv = n_heads * hd, spec.kv_heads(n_heads) * hd
    # the leaves in the order the flagship's init always made them: its
    # jitted init then lowers to the text it had, and is found compiled
    layernorm = spec.norm == "layernorm"
    p = {"ln_g": jnp.ones((d_model,))}
    if layernorm:
        p["ln_b"] = jnp.zeros((d_model,))
    p["wq"] = n(ks[0], (d_model, d_q)) * s_d
    p["wk"] = n(ks[1], (d_model, d_kv)) * s_d
    p["wv"] = n(ks[2], (d_model, d_kv)) * s_d
    p["wo"] = n(ks[3], (d_q, d_model)) * s_d
    p["ln2_g"] = jnp.ones((d_model,))
    if layernorm:
        p["ln2_b"] = jnp.zeros((d_model,))
    p["router"] = n(ks[4], (d_model, n_experts)) * s_d
    if spec.qk_norm:
        p["q_g"], p["k_g"] = jnp.ones((hd,)), jnp.ones((hd,))
    if spec.ffn == "swiglu":
        p["experts"] = {
            "wg": n(ks[5], (n_experts, d_model, d_ff)) * s_d,
            "wu": n(jax.random.fold_in(ks[5], 1),
                    (n_experts, d_model, d_ff)) * s_d,
            "wd": down(jax.random.fold_in(ks[5], 2)),
        }
    else:
        ex = p["experts"] = {
            "w1": n(ks[5], (n_experts, d_model, d_ff)) * s_d}
        if spec.bias:
            ex["b1"] = jnp.zeros((n_experts, d_ff))
        ex["w2"] = down(jax.random.fold_in(ks[5], 1))
        if spec.bias:
            ex["b2"] = jnp.zeros((n_experts, d_model))
    return p


def init_lm_params(key: Array, vocab: int, d_model: int, n_heads: int,
                   n_experts: int, d_ff: int, n_layers: int = 1,
                   spec: BlockSpec = FLAGSHIP_SPEC,
                   init_scale: Optional[float] = None) -> dict:
    """Embedding + ``n_layers`` stacked decoder blocks + vocab decoder.

    ``params["blocks"]`` leaves carry a leading (n_layers, ...) axis — the
    scan/pipeline-stage layout (lm_forward scans it; make_pp_stages slices
    it at layer boundaries). ``spec`` says which leaves a block has;
    ``init_scale`` draws every matrix at that one scale (None: the
    flagship's own, 1/sqrt(fan-in) and 0.1 for the embedding)."""
    hd = spec.head_size(d_model, n_heads)
    if spec.head_dim is None and d_model % n_heads:
        raise ValueError(f"d_model {d_model} % n_heads {n_heads} != 0")
    if n_heads % spec.kv_heads(n_heads):
        raise ValueError(f"n_heads {n_heads} % n_kv_heads "
                         f"{spec.n_kv_heads} != 0")
    if spec.rope_theta is not None and hd % 2:
        raise ValueError(f"rotary positions need an even head size, got {hd}")
    if n_layers < 1:
        raise ValueError(f"n_layers must be >= 1, got {n_layers}")
    if spec.layer_pattern is not None:
        return _init_hybrid(key, vocab, d_model, n_heads, d_ff, n_layers,
                            spec, init_scale)
    ks = jax.random.split(key, 3 + n_layers)
    n = jax.random.normal
    s_d = 1.0 / (d_model ** 0.5) if init_scale is None else init_scale
    blocks = [_init_block(ks[3 + i], d_model, n_heads, n_experts, d_ff, spec,
                          init_scale)
              for i in range(n_layers)]
    out = {
        "embed": n(ks[0], (vocab, d_model))
        * (0.1 if init_scale is None else init_scale),
        "blocks": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks),
        "dec_w": n(ks[1], (d_model, vocab)) * s_d,
    }
    if spec.bias:
        out["dec_b"] = jnp.zeros((vocab,))
    if spec.final_norm:
        out["lnf_g"] = jnp.ones((d_model,))
        if spec.norm == "layernorm":
            out["lnf_b"] = jnp.zeros((d_model,))
    return out


def _init_hybrid_layer(key: Array, kind: str, d_model: int, n_heads: int,
                       d_ff: int, spec: BlockSpec) -> dict:
    """One layer of a pattern: the mixer of its kind (attention's four
    matrices, or models/ssm.py's), the dense gated FFN, two RMSNorm gains.
    Every matrix N(0, 1/fan_in)."""
    from deeplearning4j_tpu.models import ssm

    ks = jax.random.split(key, 3)
    n = jax.random.normal
    hd = spec.head_size(d_model, n_heads)
    d_q, d_kv = n_heads * hd, spec.kv_heads(n_heads) * hd
    p = {"ln_g": jnp.ones((d_model,))}
    if kind == "mamba":
        p["mixer"] = ssm.init_mixer(ks[0], d_model, spec)
    else:
        kq, kk, kv, ko = jax.random.split(ks[0], 4)
        p["wq"] = n(kq, (d_model, d_q)) / (d_model ** 0.5)
        p["wk"] = n(kk, (d_model, d_kv)) / (d_model ** 0.5)
        p["wv"] = n(kv, (d_model, d_kv)) / (d_model ** 0.5)
        p["wo"] = n(ko, (d_q, d_model)) / (d_q ** 0.5)
    p["ln2_g"] = jnp.ones((d_model,))
    p["mlp"] = {"w_in": n(ks[1], (d_model, 2 * d_ff)) / (d_model ** 0.5),
                "w_out": n(ks[2], (d_ff, d_model)) / (d_ff ** 0.5)}
    return p


def _init_hybrid(key: Array, vocab: int, d_model: int, n_heads: int,
                 d_ff: int, n_layers: int, spec: BlockSpec,
                 init_scale: Optional[float]) -> dict:
    """``init_lm_params`` under a layer pattern: layer i drawn from the
    i-th layer key as its kind says, ``params["blocks"]`` one stack a kind
    (``{"attention": (L_a, ...), "mamba": (L_m, ...)}``, layers in depth
    order within each), the embedding at ``init_scale`` (None: 0.1), a final
    RMSNorm, and an untied head only where the spec has one."""
    pattern = spec.layer_pattern
    if spec.norm != "rmsnorm" or spec.ffn != "dense_swiglu" or \
            not spec.final_norm:
        raise ValueError("a layer pattern takes norm='rmsnorm', "
                         "ffn='dense_swiglu' and final_norm=True")
    if n_layers % len(pattern) or not set(pattern) <= {"attention", "mamba"}:
        raise ValueError(f"{n_layers} layers are not whole periods of "
                         f"{pattern} (kinds: attention, mamba)")
    ks = jax.random.split(key, 3 + n_layers)
    layers = {kind: [] for kind in pattern}
    for i in range(n_layers):
        kind = pattern[i % len(pattern)]
        layers[kind].append(_init_hybrid_layer(ks[3 + i], kind, d_model,
                                               n_heads, d_ff, spec))
    out = {"embed": jax.random.normal(ks[0], (vocab, d_model))
           * (0.1 if init_scale is None else init_scale),
           "blocks": {kind: jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                                   *stack)
                      for kind, stack in layers.items()},
           "lnf_g": jnp.ones((d_model,))}
    if not spec.tied_head:
        out["dec_w"] = jax.random.normal(ks[1], (d_model, vocab)) \
            / (d_model ** 0.5)
    return out


def _pattern_counts(spec: BlockSpec, n_layers: int) -> dict:
    """{kind: its layers in ``n_layers``} under the spec's pattern."""
    pattern = spec.layer_pattern
    return {kind: n_layers // len(pattern) * pattern.count(kind)
            for kind in pattern}


def lm_n_layers(params: dict) -> int:
    blocks = params["blocks"]
    if "attention" in blocks or "mamba" in blocks:  # a layer pattern's
        return sum(jax.tree_util.tree_leaves(b)[0].shape[0]
                   for b in blocks.values())
    return jax.tree_util.tree_leaves(blocks)[0].shape[0]


def expert_fn(p: dict, t: Array) -> Array:
    """One expert's FFN on its (C, d) token slice: which kind is read off
    the leaves the spec's init gave it (three matrices: SiLU-gated, no
    bias; two: ReLU, with biases where the spec has them)."""
    if "wg" in p:
        return (jax.nn.silu(t @ p["wg"]) * (t @ p["wu"])) @ p["wd"]
    if "b1" not in p:
        return jax.nn.relu(t @ p["w1"]) @ p["w2"]
    return jax.nn.relu(t @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


def _route(router_w: Array, x: Array, top_k: int, spec: BlockSpec) -> tuple:
    """A token's experts and their gates, (idx (N, k), gates (N, k)): the
    one routing of both forms of the one-chip expert layer."""
    logits = (jnp.matmul(x, router_w, preferred_element_type=jnp.float32)
              if spec.accum_f32 else x @ router_w)
    return _routing(logits, top_k, spec.norm_topk_prob)


def _gate_matrix(idx: Array, gates: Array, n_experts: int) -> Array:
    """(N, E): a token's gate at each expert it chose, 0 elsewhere."""
    onehot = jax.nn.one_hot(idx, n_experts)  # (N, k, E)
    return jnp.sum(gates[..., None] * onehot, axis=1)


def dense_moe(router_w: Array, experts: dict, x: Array,
              top_k: int = 2, spec: BlockSpec = FLAGSHIP_SPEC) -> Array:
    """Differentiable single-device MoE (every expert on every token,
    gate-combined; no capacity drops) — the parity oracle for moe_apply
    with ample capacity and for ``routed_moe``, the FFN of the one-chip
    train step and of the pp-staged path, and the form of the serving
    programs' expert layer where rows an expert are few (``moe_ffn``)."""
    idx, gates = _route(router_w, x, top_k, spec)
    y_all = jax.vmap(lambda p: expert_fn(p, x))(experts)  # (E, N, d)
    g = _gate_matrix(idx, gates, router_w.shape[1])
    return jnp.einsum("ne,end->nd", g, y_all)


# Rows an expert (N * top_k // E, static at trace time) from which the
# one-chip expert layer sorts its routes and runs grouped matmuls instead of
# every expert on every token. Below it the layer is a read of every
# expert's weights and ``dense_moe`` is the cheapest way to do that. One
# layer on the v5e, all-experts / routed in ms at 16, 32, 64, 128, 256 and
# 512 rows an expert (PERF.md section 3: PR 29's sweep, re-read in PR 30):
#   flagship widths, bfloat16, forward: 0.83/1.03 0.95/1.14 1.76/1.28
#     4.09/1.85 7.76/2.31 15.4/4.97
#   flagship widths, float32, with the backward: 6.6/5.0 7.4/5.5 8.5/6.2
#     14.7/9.6 26.3/15.5 50.1/24.8
#   SDAR's widths, bfloat16, forward: 1.96/2.20 3.80/2.41 7.52/2.81
#     18.8/3.61 37.0/6.63 (none at 512)
# 64 is the first step at which the routed form wins at both widths in
# every mode; at 16 (the decode and block steps) it loses in both forwards.
ROUTED_MIN_ROWS_PER_EXPERT = 64
# And experts a route (E // top_k) from which it does: every expert on every
# token computes E / k times the required work, on dense matmuls that reach
# 70% of the chip's peak where the grouped ones reach a third. At E / k = 2
# the all-experts form is 1.2 to 1.4 times faster (E 4, k 2 at 4,096 rows:
# 0.63 against 0.78 ms forward, 2.06 against 2.81 with the backward), at 3
# the two tie, at 4 the routed form wins (E 32, k 8: 1.96 against 1.26 and
# 6.96 against 6.07; Mixtral's widths, E 8, k 2: 42.1 against 16.8 and 158
# against 111).
ROUTED_MIN_EXPERTS_PER_ROUTE = 4

# The cached step's attention reads a long cache in chunks of ATTN_CHUNK
# positions, up to the chunk of the furthest row any of its queries sees
# (``_attend_chunks``), where the cache holds a whole number of chunks and
# ATTN_MIN_CHUNKS or more; a shorter cache is read whole, as one masked
# slab. The engine retires a slot's position to 0, so the furthest row is
# the longest live request's: in the chat cell some 2 of the 8 chunks of
# 2,048 (PERF.md section 3).
ATTN_CHUNK = 256
ATTN_MIN_CHUNKS = 4


def attn_chunks(max_len: int) -> int:
    """Chunks of ``ATTN_CHUNK`` positions the cached step's attention loops
    over in a cache of ``max_len`` positions; 0 where it reads the cache
    whole."""
    n = max_len // ATTN_CHUNK
    return n if max_len % ATTN_CHUNK == 0 and n >= ATTN_MIN_CHUNKS else 0


def attn_chunk_reads(last_row: int, max_len: int) -> tuple:
    """(chunks read, chunks there are) of one cached step whose furthest
    query sees row ``last_row``: the host's count of what the chunk loop
    reads on the device; a cache read whole reads every chunk."""
    total = attn_chunks(max_len)
    if not total:
        whole = -(-max_len // ATTN_CHUNK)
        return whole, whole
    return min(total, last_row // ATTN_CHUNK + 1), total


@jax.custom_vjp
def _permute_rows(a: Array, perm: Array, inverse: Array) -> Array:
    """``a[perm]`` for a permutation whose inverse the caller has: the
    cotangent goes back through ``inverse`` as a second gather, where the
    transpose of a plain gather is a scatter-add that cannot know its rows
    are distinct."""
    return a[perm]


_permute_rows.defvjp(
    lambda a, perm, inverse: (a[perm], (perm, inverse)),
    lambda res, ct: (_permute_rows(ct, res[1], res[0]), None, None))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _route_rows(x: Array, order: Array, inverse: Array, top_k: int) -> Array:
    """(N, d) token rows → (N * k, d), one a route, in the order ``order``
    (a permutation of the routes, ``inverse`` its inverse; route r is token
    r // k). The cotangent is un-sorted and summed over a token's k routes."""
    return x[order // top_k]


_route_rows.defvjp(
    lambda x, order, inverse, top_k: (x[order // top_k], (order, inverse)),
    lambda top_k, res, ct: (
        jnp.sum(_permute_rows(ct, res[1], res[0])
                .reshape(-1, top_k, ct.shape[-1]), axis=1), None, None))


def _grouped_matmul(rows: Array, w: Array, sizes: Array,
                    layer: Optional[Array] = None) -> Array:
    """(M, K) rows sorted by group @ (G, K, N) → (M, N): group g's
    ``sizes[g]`` consecutive rows through ``w[g]``. The megablox Pallas
    kernel, differentiable through its ``custom_vjp`` (a second grouped
    matmul for the rows, the transposed one for the weights); interpreted on
    the CPU backend like the repo's other Pallas kernels. One tiling, sized
    by the element so that all three kernels' blocks fit VMEM in either
    type (one layer's forward at 256 rows an expert in bfloat16 on the v5e:
    2.7 to 3.0 ms over five tilings with tm 128 or 256, 18.6 at the
    kernel's default of 128 cubed; float32 with the backward at 512 rows:
    28.7 ms at this one, 31.3 and 33.3 at two others; tk 1024 and tn 1024
    in float32 overflow VMEM in the transposed kernel; PR 29's sweep).

    With ``layer``, ``w`` is every layer's (L, G, K, N) and the kernel reads
    that layer's groups where they lie: the other layers' groups are empty,
    and an empty group is never visited."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from deeplearning4j_tpu.ops.pallas_kernels import _interpret

    if layer is not None:
        n_groups = w.shape[1]
        w = w.reshape((-1,) + w.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w.shape[0],), sizes.dtype), sizes,
            (layer * n_groups,))
    (m, k), n = rows.shape, w.shape[2]
    dtype = jnp.result_type(rows.dtype, w.dtype)
    tm = min(256, -(-m // 8) * 8)
    padded = -(-m // tm) * tm  # rows past the last group are not computed
    if padded != m:
        rows = jnp.pad(rows, ((0, padded - m), (0, 0)))
    out = gmm(rows, w, sizes, dtype,
              (tm, min(1024, k), min(2048 // dtype.itemsize, n)),
              interpret=_interpret())
    return out[:m]


def routed_moe(router_w: Array, experts: dict, x: Array,
               top_k: int = 2, spec: BlockSpec = FLAGSHIP_SPEC,
               layer: Optional[Array] = None) -> Array:
    """``dense_moe``'s mathematics with only the routed work done: the
    N * k routes sorted by expert, each expert's matrices applied to its own
    rows as grouped matmuls, the rows un-sorted, scaled by their gate and
    summed over k in float32. Every route of the top-k is computed: no
    capacity, no drop. With ``layer``, ``experts`` holds every layer's
    leaves stacked, as ``params["blocks"]`` keeps them, and the matrices
    are read in place (``_grouped_matmul``)."""
    idx, gates = _route(router_w, x, top_k, spec)
    n, n_experts = x.shape[0], router_w.shape[1]
    with jax.named_scope("moe_routed_sort"):
        flat = idx.reshape(-1)
        # routes by expert (stable: a token's rows keep their order)
        expert_of, order = jax.lax.sort(
            (flat, jnp.arange(flat.shape[0], dtype=flat.dtype)), num_keys=1)
        inverse = jnp.argsort(order)
        sizes = jnp.sum(jax.nn.one_hot(flat, n_experts, dtype=jnp.int32),
                        axis=0)
        rows = _route_rows(x, order, inverse, top_k)
    with jax.named_scope("moe_routed_experts"):
        dot = partial(_grouped_matmul, sizes=sizes, layer=layer)
        bias = lambda name: (experts[name] if layer is None  # noqa: E731
                             else experts[name][layer])
        if "wg" in experts:
            y = dot(jax.nn.silu(dot(rows, experts["wg"]))
                    * dot(rows, experts["wu"]), experts["wd"])
        elif "b1" not in experts:
            y = dot(jax.nn.relu(dot(rows, experts["w1"])), experts["w2"])
        else:
            # a row's bias as a one-hot matmul, exact at "highest": its
            # transpose is a matmul too, where a gather's is a scatter-add
            b1 = jnp.matmul(jax.nn.one_hot(expert_of, n_experts,
                                           dtype=experts["b1"].dtype),
                            bias("b1"), precision="highest")
            y = dot(jax.nn.relu(dot(rows, experts["w1"]) + b1),
                    experts["w2"])
    with jax.named_scope("moe_routed_combine"):
        # float32 out, as dense_moe's gate-combine gives: callers cast back
        y = _permute_rows(y, inverse, order).reshape(n, top_k, -1)
        out = jnp.sum(y.astype(jnp.float32)
                      * gates[..., None].astype(jnp.float32), axis=1)
        if "b2" in experts:
            # the gated sum of the second biases, once a token
            out = out + _gate_matrix(idx, gates, n_experts) @ bias("b2")
        return out


def _routes(n_rows: int, top_k: int, n_experts: int) -> bool:
    """Whether a call of these static shapes takes the routed form."""
    return (n_rows * top_k // n_experts >= ROUTED_MIN_ROWS_PER_EXPERT
            and n_experts >= ROUTED_MIN_EXPERTS_PER_ROUTE * top_k)


def moe_ffn(router_w: Array, experts: dict, x: Array,
            top_k: int = 2, spec: BlockSpec = FLAGSHIP_SPEC,
            layer: Optional[Array] = None) -> Array:
    """The one-chip expert layer, its form chosen from the static shapes of
    the call (``_routes``): routed where rows an expert are many and a route
    leaves most experts out, else every expert on every token. ``layer``
    as in ``routed_moe``."""
    if _routes(x.shape[0], top_k, router_w.shape[1]):
        return routed_moe(router_w, experts, x, top_k, spec, layer)
    if layer is not None:
        experts = jax.tree_util.tree_map(lambda a: a[layer], experts)
    return dense_moe(router_w, experts, x, top_k, spec)


# ---- the block's parts, each reading the spec at trace time; under the
# default spec each emits the operations the flagship's block always had ----

def _rmsnorm(x: Array, g: Array, eps: float) -> Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def _norm(p: dict, name: str, x: Array, spec: BlockSpec) -> Array:
    if spec.norm == "layernorm":
        return _layernorm(x, p[name + "_g"], p[name + "_b"])
    return _rmsnorm(x, p[name + "_g"], spec.norm_eps)


def _rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary positions, rotate-half over the head size, no scaling. x:
    (B, H, T, Dh); positions: (T,) or one row a batch row, (B, T)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)            # (..., T, Dh)
    if positions.ndim == 2:
        ang = ang[:, None]                                # (B, 1, T, Dh)
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., hd // 2:], x32[..., :hd // 2]], -1)
    return (x32 * jnp.cos(ang) + rot * jnp.sin(ang)).astype(x.dtype)


def _qkv(p: dict, hn: Array, n_heads: int, spec: BlockSpec,
         positions: Optional[Array]) -> tuple:
    """The three projections of the normed input as heads: q (B, H, T, Dh),
    k and v (B, H_kv, T, Dh), with the spec's q/k norm and, at
    ``positions`` (read under a rotary spec alone), its rotary term."""
    n_kv = spec.kv_heads(n_heads)
    q = _split_heads(hn @ p["wq"], n_heads)
    k = _split_heads(hn @ p["wk"], n_kv)
    v = _split_heads(hn @ p["wv"], n_kv)
    if spec.qk_norm:
        q = _rmsnorm(q, p["q_g"], spec.norm_eps)
        k = _rmsnorm(k, p["k_g"], spec.norm_eps)
    if spec.rope_theta is not None:
        q = _rope(q, positions, spec.rope_theta)
        k = _rope(k, positions, spec.rope_theta)
    return q, k, v


def _scores(q: Array, k: Array, spec: BlockSpec) -> Array:
    kw = {"preferred_element_type": jnp.float32} if spec.accum_f32 else {}
    scores = jnp.einsum("shqd,shkd->shqk", q, k, **kw)
    if spec.attention_multiplier is not None:
        return scores * spec.attention_multiplier
    return scores / jnp.sqrt(q.shape[-1] * 1.0)


def _lm_head(params: dict, h: Array, spec: BlockSpec) -> Array:
    if spec.final_norm:
        h = _norm(params, "lnf", h, spec)
    w = params["embed"].T if spec.tied_head else params["dec_w"]
    logits = (jnp.matmul(h, w, preferred_element_type=jnp.float32)
              if spec.accum_f32 else h @ w)
    if spec.logits_scaling != 1.0:
        logits = logits / spec.logits_scaling
    return logits + params["dec_b"] if spec.bias else logits


def _embed(params: dict, tokens: Array, spec: BlockSpec) -> Array:
    """The embedding's rows of ``tokens`` under ``lm_embed``, times the
    spec's multiplier (the layer-pattern paths'; the others index the
    embedding where they are)."""
    with jax.named_scope("lm_embed"):
        h = params["embed"][tokens]
        if spec.embedding_multiplier != 1.0:
            h = h * spec.embedding_multiplier
        return h


def _residual(spec: BlockSpec):
    """What a branch's output is multiplied by before its residual: nothing
    at the default 1, so the default emits the operations it always did."""
    r = spec.residual_multiplier
    return (lambda y: y) if r == 1.0 else (lambda y: y * r)


def _block(p: dict, h: Array, n_heads: int, spec: BlockSpec, attend, ffn,
           rows=None, kind: str = "attention") -> tuple:
    """The decoder block, the one every program runs, on h (B, T, d) →
    (h, kept, flat). What differs between training, the prompt pass and the
    cached step is handed in: ``attend(q, k, v) -> (out, kept)``, the
    attention of the caller's program and what that program keeps of the
    layer (nothing in training, the layer's K/V in the prompt pass, the
    carried cache in the cached step); ``ffn(router_w, experts, flat)``, its
    expert layer; ``rows()``, the query rows' positions (B, T) where they
    are not 0..T-1 (read under a rotary spec alone, and there, where the
    projections are made: a program keeps the order it was compiled in).
    ``flat`` is the (B·T, d) pre-expert activations, the load-balance aux
    term's input.

    Under a layer pattern (``kind``): a ``"mamba"`` layer's mixer is
    ``attend(mixer params, normed h) -> (out, kept)`` under ``lm_ssm``
    (models/ssm.py's prompt pass or cached step), the FFN is the dense
    gated one under ``lm_mlp`` (``ffn`` unused, ``flat`` None), and each
    branch is scaled by the spec's residual multiplier."""
    branch = _residual(spec)
    if kind == "mamba":
        with jax.named_scope(LM_SSM_SCOPE):
            out, kept = attend(p["mixer"], _norm(p, "ln", h, spec))
            h = h + branch(out).astype(h.dtype)
    else:
        with jax.named_scope("lm_attn"):
            hn = _norm(p, "ln", h, spec)
            positions = None
            if spec.rope_theta is not None:
                positions = rows() if rows else jnp.arange(h.shape[1])
            q, k, v = _qkv(p, hn, n_heads, spec, positions)
            out, kept = attend(q, k, v)
            # .astype keeps the carry's dtype stable under serve_dtype="bf16"
            # (float32 score math widens the core's output); identity at f32
            h = h + branch(_merge_heads(out) @ p["wo"]).astype(h.dtype)
    if spec.ffn == "dense_swiglu":
        with jax.named_scope(LM_MLP_SCOPE):
            a, b = jnp.split(_norm(p, "ln2", h, spec) @ p["mlp"]["w_in"], 2,
                             axis=-1)
            y = (jax.nn.silu(a) * b) @ p["mlp"]["w_out"]
            return h + branch(y).astype(h.dtype), kept, None
    # at this one site, so that the scope is entered once and the mesh
    # path's moe_apply lies inside it with its moe_all2all_* scopes
    with jax.named_scope("lm_moe"):
        h2 = _norm(p, "ln2", h, spec)
        flat = h2.reshape(-1, h2.shape[-1])
        moe_out = ffn(p["router"], p["experts"], flat)
        return h + moe_out.reshape(h.shape).astype(h.dtype), kept, flat


def _stacked_layers(blocks: dict, h: Array, layer_fn,
                    experts_whole: bool = False) -> tuple:
    """The layer loop that stacks: ONE ``lax.scan`` of ``layer_fn(
    layer_params, h, layer) -> (h, out)`` over the stacked block params,
    ``out`` stacked by layer (training's pre-expert activations, the prompt
    pass's K/V, nothing in a pipeline stage). Compile time stays O(1) in
    depth and a layer's collectives trace once. ``layer`` is None unless
    ``experts_whole``."""
    xs = (blocks, None)
    if experts_whole:
        # The routed form's grouped matmul is a Mosaic call, and a Mosaic
        # call cannot fuse the scan's slice of a stacked leaf: sliced by the
        # scan, every expert's weights are copied once a layer (1.3 ms of a
        # 3.6 ms layer at the 2,048 bucket on the v5e, PR 29). So where the
        # layer routes, the experts stay whole outside the scan and a layer
        # reads its own in place; where it does not, the scan is the one it
        # always was.
        xs = ({name: leaf for name, leaf in blocks.items()
               if name != "experts"}, jnp.arange(blocks["router"].shape[0]))

    def step(h, xs):
        layer_params, layer = xs
        if layer is not None:
            layer_params = dict(layer_params, experts=blocks["experts"])
        return layer_fn(layer_params, h, layer)

    return jax.lax.scan(step, h, xs)


def _train_layer(n_heads: int, attn_core, moe_fn, spec: BlockSpec):
    """``layer_fn`` of the training form: the builder's core with nothing
    kept, its expert layer, ``flat`` handed back."""
    def layer_fn(layer_params, h, layer):
        h, _, flat = _block(layer_params, h, n_heads, spec,
                            lambda q, k, v: (attn_core(q, k, v), None), moe_fn)
        return h, flat

    return layer_fn


def _lm_hidden(params: dict, tokens: Array, n_heads: int, attn_core,
               moe_fn, spec: BlockSpec = FLAGSHIP_SPEC) -> tuple:
    """``lm_forward`` up to the decoder: (h (B, T, d), moe_in)."""
    with jax.named_scope("lm_embed"):
        h = params["embed"][tokens]  # (B, T, d)
    return _stacked_layers(params["blocks"], h,
                           _train_layer(n_heads, attn_core, moe_fn, spec))


def lm_forward(params: dict, tokens: Array, n_heads: int, attn_core,
               moe_fn) -> tuple:
    """tokens: (B, T) int32 → (logits (B, T, V), moe_in (L, B·T, d)).

    ``attn_core(q, k, v) -> out`` and ``moe_fn(router_w, experts, flat)``
    supply the parallel strategy; every projection/norm is strategy-agnostic
    and sharded by GSPMD from the argument shardings. The layer stack runs
    as one scan over the stacked per-layer params (``_stacked_layers``)."""
    h, moe_ins = _lm_hidden(params, tokens, n_heads, attn_core, moe_fn)
    return _lm_head(params, h, FLAGSHIP_SPEC), moe_ins


def _lm_loss_terms(params: dict, h: Array, moe_ins: Array, targets: Array,
                   aux_weight: float) -> tuple:
    """The decoder matmul to V, the NLL and the load-balance aux term, all
    under ``lm_loss``: (loss, task, aux)."""
    with jax.named_scope("lm_loss"):
        logp = jax.nn.log_softmax(_lm_head(params, h, FLAGSHIP_SPEC), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        task = jnp.mean(nll)
        aux = jnp.mean(jax.vmap(load_balance_loss)(
            params["blocks"]["router"], moe_ins))
        return task + aux_weight * aux, task, aux


def lm_loss(params: dict, tokens: Array, targets: Array, n_heads: int,
            attn_core, moe_fn, aux_weight: float = 1e-2) -> Array:
    """Next-token softmax cross-entropy + the Switch load-balance aux
    (averaged over layers, so the weight is depth-independent)."""
    h, moe_ins = _lm_hidden(params, tokens, n_heads, attn_core, moe_fn)
    return _lm_loss_terms(params, h, moe_ins, targets, aux_weight)[0]


def lm_loss_and_metrics(params: dict, tokens: Array, targets: Array,
                        n_heads: int, attn_core, moe_fn,
                        aux_weight: float = 1e-2, top_k: int = 2,
                        moe_drop_fn=None) -> tuple:
    """``lm_loss`` with an in-graph metrics aux: (loss, metrics).

    The loss is computed by the IDENTICAL op sequence as ``lm_loss`` (bit
    parity with the unthreaded step is pinned at 0 ulp in
    tests/test_telemetry.py); the metrics dict only adds reads of
    intermediates the graph already has — task/aux split, the per-expert
    router-load fraction (mean over layers; sums to 1 per step), and — when
    the builder passes ``moe_drop_fn(router_w, moe_in)`` (the composed
    capacity paths do) — the capacity-overflow share ``moe_dropped_frac``."""
    h, moe_ins = _lm_hidden(params, tokens, n_heads, attn_core, moe_fn)
    loss, task, aux = _lm_loss_terms(params, h, moe_ins, targets, aux_weight)
    load = jnp.mean(
        jax.vmap(lambda rw, xin: router_load_fraction(rw, xin, top_k))(
            params["blocks"]["router"], moe_ins), axis=0)  # (E,)
    metrics = {
        "task_loss": task,
        "aux_loss": aux,
        "router_load": load,
    }
    if moe_drop_fn is not None:
        metrics["moe_dropped_frac"] = jnp.mean(
            jax.vmap(moe_drop_fn)(params["blocks"]["router"], moe_ins))
    return loss, metrics


def selected_attn_impl(seq_len: int, attn_impl: Optional[str] = None) -> str:
    """The attention core a step with this sequence length will actually
    run — per-call arg > global/env override > auto shape gate. Host-side
    static metadata for the telemetry step log / run-info gauge."""
    from deeplearning4j_tpu.ops.flash_attention import resolve_attention_impl

    return attn_impl or resolve_attention_impl(seq_len)


def selected_moe_impl(mesh: Mesh, n_tokens: int,
                      moe_impl: Optional[str] = None) -> Optional[str]:
    """The MoE dispatch a composed step with this token count will run —
    per-call arg > set_moe_impl/env override > auto divisibility gate.
    Host-side static metadata (bench detail, telemetry run info); None on
    meshes without an expert axis (dense MoE)."""
    from deeplearning4j_tpu.parallel.moe import resolve_moe_impl

    names = mesh.axis_names
    if EXPERT_AXIS not in names:
        return None
    token_axes = tuple(a for a in (DATA_AXIS, SEQ_AXIS) if a in names)
    rows = 1
    for a in token_axes:
        rows *= mesh.shape[a]
    return resolve_moe_impl(n_tokens, rows * mesh.shape[EXPERT_AXIS],
                            moe_impl)


# --------------------------------------------------------------- builders ----

def dense_loss_fn(n_heads: int, top_k: int = 2, aux_weight: float = 1e-2,
                  attn_impl: Optional[str] = None,
                  with_metrics: bool = False,
                  attn_blocks: Optional[tuple] = None):
    """Single-device reference loss (dense MoE; attention through the core
    seam). ``attn_impl=None`` auto-gates by shape — blockwise flash for long
    T, dense for short — so the flagship bench runs the fast core without
    edits; parity oracles pass ``attn_impl="dense"`` to pin the
    materializing reference. ``with_metrics`` swaps in the
    (loss, metrics)-returning twin for telemetry-threaded steps.
    ``attn_blocks=(block_q, block_k)`` overrides the blockwise tile policy
    (``ops.flash_attention.default_block_policy``) — the autotuner's knob
    (ISSUE 20); ignored by the dense/pallas cores."""
    bq, bk = attn_blocks or (None, None)
    kwargs = dict(
        n_heads=n_heads,
        attn_core=lambda q, k, v: attention_core(q, k, v, causal=True,
                                                 impl=attn_impl,
                                                 block_q=bq, block_k=bk),
        # dense_moe and not moe_ffn, though a 4,096-row step would route: the
        # routed train step trains 1.33 times faster and was refused for its
        # set-up (six Mosaic calls to trace, lower and load: setup_s +4.1 s
        # against a bound of 2.0 s in train-1chip-seq4k; ledger, PR 29)
        moe_fn=lambda rw, ex, x: dense_moe(rw, ex, x, top_k),
        aux_weight=aux_weight,
    )
    if with_metrics:
        return partial(lm_loss_and_metrics, top_k=top_k, **kwargs)
    return partial(lm_loss, **kwargs)


def composed_loss_fn(mesh: Mesh, n_heads: int, capacity: int,
                     top_k: int = 2, aux_weight: float = 1e-2,
                     attn_impl: Optional[str] = None,
                     moe_impl: Optional[str] = None,
                     with_metrics: bool = False,
                     ring_prefetch: bool = True,
                     attn_blocks: Optional[tuple] = None):
    """Loss with the parallel strategies the mesh's axes call for:
    "data" → batch sharding (GSPMD), "sp" → ring attention over the
    sequence, "expert" → expert-parallel MoE dispatch (grouped: any
    ``n_experts`` that is a multiple of the expert-axis size — G experts
    per device). Any subset works: a ("data","expert") mesh composes
    dp×ep; ("data","sp","expert") composes all three. ``attn_impl`` forces
    the attention core on BOTH paths (the ring's per-rotated-block core and
    the unsharded core); ``moe_impl`` forces the MoE dispatch
    ("alltoall" | "alltoall_2d" | "replicated" — the 2D factorization is
    ISSUE 14's hierarchical exchange, parallel/moe.py); both default to
    their override/env/auto chains. ``ring_prefetch`` (ISSUE 14, default
    True) rotates the next K/V block under the current block's tiles —
    ``False`` restores the rotate-after-attend oracle, bit-identical
    values either way. ``with_metrics`` returns the (loss, metrics) twin
    — the router-load fraction is computed on the GLOBAL (GSPMD-sharded)
    activations, so it reports the same global balance the dense oracle
    sees, and the capacity paths add ``moe_dropped_frac`` (the overflow
    share under the resolved dispatch's sub-shard semantics).
    ``attn_blocks=(block_q, block_k)`` overrides the blockwise tile
    policy on the UNSHARDED attention core only (ISSUE 20); the ring
    path's per-rotated-block core keeps ``default_block_policy`` — its
    block shapes are set by the shard geometry, not this knob.
    """
    names = mesh.axis_names
    bq, bk = attn_blocks or (None, None)
    if SEQ_AXIS in names:
        attn_core_fn = lambda q, k, v: ring_attention(  # noqa: E731
            q, k, v, mesh, SEQ_AXIS, causal=True,
            batch_axis=DATA_AXIS if DATA_AXIS in names else None,
            attn_impl=attn_impl, prefetch=ring_prefetch)
    else:
        attn_core_fn = lambda q, k, v: attention_core(  # noqa: E731
            q, k, v, causal=True, impl=attn_impl, block_q=bq, block_k=bk)
    moe_drop_fn = None
    if EXPERT_AXIS in names:
        token_axes = tuple(a for a in (DATA_AXIS, SEQ_AXIS) if a in names)
        moe_fn = lambda rw, ex, x: moe_apply(  # noqa: E731
            rw, ex, x, mesh, expert_fn, capacity, top_k=top_k,
            token_axes=token_axes, impl=moe_impl)
        if with_metrics:
            moe_drop_fn = lambda rw, xin: dropped_route_fraction(  # noqa: E731
                rw, xin, capacity, top_k,
                n_shards=route_shards(mesh, token_axes, EXPERT_AXIS,
                                      xin.shape[0], moe_impl))
    else:
        moe_fn = lambda rw, ex, x: dense_moe(rw, ex, x, top_k)  # noqa: E731
    if with_metrics:
        return partial(lm_loss_and_metrics, n_heads=n_heads,
                       attn_core=attn_core_fn, moe_fn=moe_fn,
                       aux_weight=aux_weight, top_k=top_k,
                       moe_drop_fn=moe_drop_fn)
    return partial(lm_loss, n_heads=n_heads, attn_core=attn_core_fn,
                   moe_fn=moe_fn, aux_weight=aux_weight)


def lm_param_shardings(params: dict, mesh: Mesh) -> dict:
    """Per-leaf NamedSharding pytree for the flagship params on ``mesh``:
    experts onto the expert axis (when present), everything else
    replicated. Block leaves carry a leading layer axis, so the expert dim
    is axis 1 there; with grouped experts (E = G × expert-axis size) each
    device's shard is its contiguous G-expert slab, and the GLOBAL layout
    is G-invariant — a G=4 save restores onto a G=1 mesh (and vice versa)
    purely by re-chunking, no reshape. This is the placement map BOTH
    ``shard_lm_params`` (initial placement) and the checkpoint resharding
    loader (``scaleout.ckpt.restore_sharded``) use, so a restore onto any
    mesh lands exactly where a fresh init would."""
    names = mesh.axis_names
    if EXPERT_AXIS in names:
        n_experts = params["blocks"]["experts"]["w1"].shape[1]
        ep = mesh.shape[EXPERT_AXIS]
        if n_experts % ep:
            raise ValueError(
                f"{n_experts} experts do not shard over the {ep}-device "
                f"{EXPERT_AXIS!r} axis — grouped layout needs "
                "n_experts % axis size == 0")
    rep = NamedSharding(mesh, P())
    out = {k: rep for k in params if k != "blocks"}
    blocks = {k: rep for k in params["blocks"] if k != "experts"}
    espec = P(None, EXPERT_AXIS) if EXPERT_AXIS in names else P()
    esharding = NamedSharding(mesh, espec)
    blocks["experts"] = jax.tree_util.tree_map(
        lambda _: esharding, params["blocks"]["experts"])
    out["blocks"] = blocks
    return out


def shard_lm_params(params: dict, mesh: Mesh) -> dict:
    """Place the params per ``lm_param_shardings``."""
    return jax.tree_util.tree_map(jax.device_put, params,
                                  lm_param_shardings(params, mesh))


def shard_lm_batch(tokens: Array, targets: Array, mesh: Mesh) -> tuple:
    """(B, T) onto ("data", "sp") — whichever of the two axes exist."""
    names = mesh.axis_names
    spec = P(DATA_AXIS if DATA_AXIS in names else None,
             SEQ_AXIS if SEQ_AXIS in names else None)
    sh = NamedSharding(mesh, spec)
    return jax.device_put(tokens, sh), jax.device_put(targets, sh)


def lm_update_sharding(mesh: Mesh):
    """The flagship's ZeRO update-sharding descriptor on ``mesh``
    (optimize/updaters.ZeroSharding): moments shard over the "data" axis;
    expert leaves keep their (layer, expert) prefix so the dp shard nests
    INSIDE the expert shard — moments stay placed exactly like their
    params on the expert axis, and the dp axis splits what was
    replicated."""
    from deeplearning4j_tpu.optimize.updaters import ZeroSharding

    names = mesh.axis_names
    if DATA_AXIS not in names:
        raise ValueError(
            f"update_sharding='sharded' needs the {DATA_AXIS!r} axis on "
            f"the mesh (got {names}) — there is no dp axis to shard the "
            "update over")
    if EXPERT_AXIS in names:
        prefix_fn = lambda ks: ((None, EXPERT_AXIS)  # noqa: E731
                                if "['experts']" in ks else ())
    else:
        prefix_fn = lambda ks: ()  # noqa: E731
    return ZeroSharding(mesh, DATA_AXIS, prefix_fn)


def init_lm_opt_state(optimizer, params, mesh: Optional[Mesh] = None):
    """Optimizer-state constructor matching what the flagship steps
    expect: param-mirroring moments (replicated mode — expert leaves come
    out expert-sharded because the zeros are placed with each param
    leaf's own sharding) or the dp-partitioned ZeRO layout (sharded
    mode, ``mesh`` required). Returns ``{"m", "v", "count"}``."""
    from deeplearning4j_tpu.optimize.updaters import (
        OptimizerConfig,
        init_opt_state,
    )

    cfg = OptimizerConfig.coerce(optimizer)
    if cfg is None:
        raise ValueError("init_lm_opt_state needs an optimizer "
                         "(name or OptimizerConfig)")
    zero = None
    if cfg.sharded:
        if mesh is None:
            raise ValueError(
                "update_sharding='sharded' needs a mesh with a dp axis — "
                "single-device steps run the replicated update")
        zero = lm_update_sharding(mesh)
    return init_opt_state(cfg, params, zero)


def _make_opt_step(loss_fn, lr: float, with_metrics: bool, optimizer,
                   zero, donate: bool = False, guard=None, profile=None,
                   profile_label: str = "lm_step", runprof=None):
    """The optimizer-threaded twin of ``_make_sgd_step``:
    ``step(params, opt_state, tokens, targets) -> (new_params,
    new_opt_state, loss[, metrics/guard block])``. The loss+grad graph is
    IDENTICAL to the SGD step's — only the update differs — and the
    moments are donated alongside the params (``donate=True``), threaded
    through the guard skip-select bitwise, and updated in the ZeRO
    layout when ``zero`` is set (optimize/updaters.opt_update)."""
    from deeplearning4j_tpu.optimize.updaters import (
        guarded_opt_update,
        opt_update,
    )

    donate_argnums = (0, 1) if donate else ()

    def _seam(step):
        from deeplearning4j_tpu.telemetry.runprof import maybe_runprof
        from deeplearning4j_tpu.telemetry.xprofile import maybe_profiled

        return maybe_runprof(maybe_profiled(step, profile, profile_label),
                             runprof, profile_label)

    if not with_metrics:
        @partial(jax.jit, donate_argnums=donate_argnums)
        def step(params, opt_state, tokens, targets):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens,
                                                      targets)
            with jax.named_scope("lm_update"):
                if guard is None:
                    new_params, new_state = opt_update(
                        optimizer, params, grads, opt_state, lr, zero=zero)
                    return new_params, new_state, loss
                new_params, new_state, gm = guarded_opt_update(
                    params, grads, opt_state, loss, lr, optimizer, guard,
                    zero=zero)
                return new_params, new_state, loss, gm

        return _seam(step)

    from deeplearning4j_tpu.telemetry.metrics import train_step_metrics

    @partial(jax.jit, donate_argnums=donate_argnums)
    def step(params, opt_state, tokens, targets):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens, targets)
        with jax.named_scope("lm_update"):
            if guard is None:
                new_params, new_state, om = opt_update(
                    optimizer, params, grads, opt_state, lr, zero=zero,
                    with_metrics=True)
            else:
                new_params, new_state, om = guarded_opt_update(
                    params, grads, opt_state, loss, lr, optimizer, guard,
                    zero=zero, with_metrics=True)
        # optimizer block LAST: its true ‖Δp‖/‖p‖ update_ratio overrides
        # the lr·‖g‖ SGD proxy train_step_metrics emits
        metrics = {**metrics,
                   **train_step_metrics(params, grads, lr, loss=loss),
                   **om}
        return new_params, new_state, loss, metrics

    return _seam(step)


def _make_sgd_step(loss_fn, lr: float, with_metrics: bool,
                   donate: bool = False, guard=None, profile=None,
                   profile_label: str = "lm_step", runprof=None):
    """jitted SGD step; with metrics the loss fn returns (loss, aux) and the
    step appends the grad/param-norm block — the loss+grad graph itself is
    the SAME ops either way (bit-parity pinned in tests/test_telemetry.py).

    ``donate=True`` donates the incoming params buffers to the update
    (halves peak param HBM for hot training loops: bench); the default
    keeps them alive because parity oracles and tests call the step with a
    pytree they reuse afterwards.

    ``guard`` (a ``GuardConfig``; see optimize/guardrails.py) swaps the
    plain SGD update for the guarded one — skip-on-nonfinite (params
    carried unchanged through a NaN/Inf step via an in-graph select) and
    optional global-norm clipping. A guarded step returns its guard block
    (``nonfinite``/``clipped``/``guard_grad_norm`` device scalars) as a
    third output, or merged into the metrics dict when ``with_metrics``;
    on clean batches it is bit-identical to the unguarded step (pinned in
    tests/test_guardrails.py) and remains donate-safe.

    ``profile`` (ISSUE 9; ``True`` or a label string) wraps the jitted
    step in ``telemetry.xprofile.ProfiledStep``: the first call captures a
    :class:`~deeplearning4j_tpu.telemetry.xprofile.StepProfile` (XLA
    cost/memory analysis + HLO collective inventory) on
    ``step.step_profile`` and records it in the default profile store;
    every call executes the same compiled program, so the profiling cost
    is compile-time-only."""
    donate_argnums = (0,) if donate else ()

    def _seam(step):
        from deeplearning4j_tpu.telemetry.runprof import maybe_runprof
        from deeplearning4j_tpu.telemetry.xprofile import maybe_profiled

        return maybe_runprof(maybe_profiled(step, profile, profile_label),
                             runprof, profile_label)

    if guard is not None:
        from deeplearning4j_tpu.optimize.guardrails import guarded_sgd_update
    if not with_metrics:
        if guard is None:
            @partial(jax.jit, donate_argnums=donate_argnums)
            def step(params, tokens, targets):
                loss, grads = jax.value_and_grad(loss_fn)(params, tokens,
                                                          targets)
                with jax.named_scope("lm_update"):
                    return jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                                  params, grads), loss

            return _seam(step)

        @partial(jax.jit, donate_argnums=donate_argnums)
        def step(params, tokens, targets):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens,
                                                      targets)
            with jax.named_scope("lm_update"):
                new_params, gm = guarded_sgd_update(params, grads, loss, lr,
                                                    guard)
            return new_params, loss, gm

        return _seam(step)

    from deeplearning4j_tpu.telemetry.metrics import train_step_metrics

    @partial(jax.jit, donate_argnums=donate_argnums)
    def step(params, tokens, targets):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens, targets)
        with jax.named_scope("lm_update"):
            if guard is None:
                new_params = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                                    params, grads)
                gm = {}
            else:
                new_params, gm = guarded_sgd_update(params, grads, loss, lr,
                                                    guard)
        metrics = {**metrics,
                   **train_step_metrics(params, grads, lr, loss=loss),
                   **gm}
        return new_params, loss, metrics

    return _seam(step)


def make_composed_train_step(mesh: Mesh, n_heads: int, capacity: int,
                             lr: float = 0.1, top_k: int = 2,
                             aux_weight: float = 1e-2,
                             attn_impl: Optional[str] = None,
                             moe_impl: Optional[str] = None,
                             with_metrics: bool = False,
                             donate: bool = False, guard=None,
                             profile=None, optimizer=None,
                             ring_prefetch: bool = True, runprof=None,
                             tuned=None, tune_context=None):
    """SGD step over the composed mesh: step(params, tokens, targets) ->
    (new_params, loss). Shard inputs with shard_lm_params/shard_lm_batch
    first; GSPMD + the shard_map transposes insert every collective
    (grad AllReduce over data/sp, expert-grad reduce over token axes,
    K/V ppermute ring, and the MoE combine — capacity all_to_all exchange
    (flat or the ``"alltoall_2d"`` hierarchical factorization) or dense
    psum per ``moe_impl``; see parallel/moe.py). ``ring_prefetch=False``
    restores the rotate-after-attend ring body (ISSUE 14 A/B oracle;
    bit-identical either way).

    ``with_metrics=True`` returns (new_params, loss, metrics) where metrics
    is an in-graph dict (loss, task/aux split, grad_norm, param_norm,
    update_ratio, (E,) router_load summing to 1, moe_dropped_frac) of
    DEVICE scalars — feed it to telemetry.TrainTelemetry.record, which
    fetches every N steps so the hot path stays one dispatch.

    ``guard=True`` (or a ``GuardConfig``) arms the numerical guardrails:
    skip-on-nonfinite + optional global-norm clip inside the same jitted
    program, returning the guard block as a third output (merged into
    metrics when ``with_metrics``); see optimize/guardrails.py.

    ``profile=True`` (or a label string) captures a compile-time
    ``StepProfile`` on ``step.step_profile`` — cost/memory analysis plus
    the HLO collective inventory, which on this mesh shows the grad
    all-reduces, the ring collective-permutes (when "sp" is present), and
    the MoE all_to_all exchange (when the alltoall dispatch resolves);
    see telemetry/xprofile.py.

    ``runprof=`` (ISSUE 17; ``True``, a label string, or a
    ``telemetry.runprof.RunProfiler``) arms the continuous runtime
    profiler: every call is phase-timed (host gap / dispatch / fenced
    device wall) into ring-buffered ``StepTiming`` records and the
    streaming ``runprof_*`` gauges; composes over ``profile=`` (the
    xprofile FLOPs feed ``runprof_measured_mfu``). The default
    (``None``) stays unwrapped unless ``DL4J_TPU_RUNPROF`` is set;
    ``False`` opts out regardless. NOTE an armed step fences every call
    (that is the measurement), so arm it for measurement, not peak
    throughput.

    ``optimizer=`` (ISSUE 13; a name string — "adam" | "lamb" | "adagrad"
    | "momentum" — or an ``optimize.updaters.OptimizerConfig``) swaps the
    SGD update for the in-graph stateful updater: the step becomes
    ``step(params, opt_state, tokens, targets) -> (new_params,
    new_opt_state, loss[, ...])`` with ``opt_state`` from
    ``init_lm_opt_state``. Moments are sharded like their params
    (expert-sharded MoE leaves); ``update_sharding="sharded"`` (explicit
    > ``DL4J_TPU_UPDATE_SHARDING`` env > replicated) additionally runs
    the ZeRO-style dp-sharded update — each replica updates 1/dp of the
    replicated leaves and the params allgather back, parity ≤1e-6 vs
    replicated pinned in tests/test_updaters.py. Moments donate, thread
    through the ``guard=`` skip-select bitwise, and checkpoint through
    ``updaters.canonical_opt_state``.

    ``tuned=`` (ISSUE 20) adopts autotuner knobs: an explicit config dict
    wins, ``True`` consults the tuning cache under ``tune_context`` (a
    ``tune.seams`` context dict — cache keys are shape-fingerprinted),
    default ``None`` consults it only when ``DL4J_TPU_TUNED`` is set.
    Adopted knobs: ``block_q``/``block_k`` (blockwise attention tiles),
    ``moe_impl`` (only when the ``moe_impl=`` arg is None — an explicit
    arg outranks the cache), ``capacity_factor`` (scales ``capacity``,
    >= 1.0). Every cache adoption is pinned numerically identical to the
    default-config step in tests/test_tune.py — tuning changes speed,
    never losses."""
    import math

    from deeplearning4j_tpu.optimize.guardrails import GuardConfig
    from deeplearning4j_tpu.optimize.updaters import OptimizerConfig
    from deeplearning4j_tpu.tune.cache import resolve_step_tuning

    tuning = resolve_step_tuning(tuned, tune_context,
                                 ("flash_attention", "moe"))
    attn_blocks = ((int(tuning["block_q"]), int(tuning["block_k"]))
                   if "block_q" in tuning else None)
    if moe_impl is None:
        moe_impl = tuning.get("moe_impl")
    capacity = int(math.ceil(
        capacity * float(tuning.get("capacity_factor", 1.0))))

    loss_fn = composed_loss_fn(mesh, n_heads, capacity, top_k, aux_weight,
                               attn_impl=attn_impl, moe_impl=moe_impl,
                               with_metrics=with_metrics,
                               ring_prefetch=ring_prefetch,
                               attn_blocks=attn_blocks)
    label = "lm_composed[" + "x".join(mesh.axis_names) + "]"
    opt_cfg = OptimizerConfig.coerce(optimizer)
    if opt_cfg is not None:
        zero = lm_update_sharding(mesh) if opt_cfg.sharded else None
        return _make_opt_step(loss_fn, lr, with_metrics,
                              opt_cfg.resolved(), zero, donate=donate,
                              guard=GuardConfig.coerce(guard),
                              profile=profile, profile_label=label,
                              runprof=runprof)
    return _make_sgd_step(loss_fn, lr, with_metrics, donate=donate,
                          guard=GuardConfig.coerce(guard), profile=profile,
                          profile_label=label, runprof=runprof)


def make_single_device_train_step(n_heads: int, lr: float = 0.1,
                                  top_k: int = 2, aux_weight: float = 1e-2,
                                  attn_impl: Optional[str] = None,
                                  with_metrics: bool = False,
                                  donate: bool = False, guard=None,
                                  profile=None, optimizer=None,
                                  runprof=None, tuned=None,
                                  tune_context=None):
    """The dense twin of make_composed_train_step (parity oracle when
    called with ``attn_impl="dense"``; the flagship single-chip bench path
    with the default auto core). ``with_metrics``/``donate``/``guard``/
    ``profile``/``optimizer``/``runprof`` as on the composed builder
    (bench hot loops
    pass donate=True; the guardrails bench stage passes guard=True on
    top; the profile stage passes profile=True). With ``optimizer=`` the
    step carries the opt state (``init_lm_opt_state(optimizer, params)``)
    as a second argument/output; there is no dp axis here, so
    ``update_sharding="sharded"`` is rejected rather than silently
    running the replicated update under a ZeRO label.

    ``tuned=`` (ISSUE 20) as on the composed builder; the single-device
    step adopts the ``flash_attention`` seam only (``block_q``/``block_k``
    blockwise tiles), parity <= 1e-5 with ``default_block_policy`` pinned
    in tests/test_flash_attention.py."""
    from deeplearning4j_tpu.optimize.guardrails import GuardConfig
    from deeplearning4j_tpu.optimize.updaters import OptimizerConfig
    from deeplearning4j_tpu.tune.cache import resolve_step_tuning

    tuning = resolve_step_tuning(tuned, tune_context, ("flash_attention",))
    attn_blocks = ((int(tuning["block_q"]), int(tuning["block_k"]))
                   if "block_q" in tuning else None)

    loss_fn = dense_loss_fn(n_heads, top_k, aux_weight, attn_impl=attn_impl,
                            with_metrics=with_metrics,
                            attn_blocks=attn_blocks)
    opt_cfg = OptimizerConfig.coerce(optimizer)
    if opt_cfg is not None:
        if opt_cfg.sharded:
            raise ValueError(
                "update_sharding='sharded' needs a dp mesh axis — the "
                "single-device step has no replicas to shard the update "
                "over (use make_composed_train_step)")
        return _make_opt_step(loss_fn, lr, with_metrics,
                              opt_cfg.resolved(), None, donate=donate,
                              guard=GuardConfig.coerce(guard),
                              profile=profile,
                              profile_label="lm_single_device",
                              runprof=runprof)
    return _make_sgd_step(loss_fn, lr, with_metrics, donate=donate,
                          guard=GuardConfig.coerce(guard), profile=profile,
                          profile_label="lm_single_device",
                          runprof=runprof)


# ----------------------------------------------------------------- dp×pp ----

def make_pp_stages(params: dict, n_heads: int, n_stages: int = 2,
                   top_k: int = 2, attn_impl: Optional[str] = None,
                   moe_fn=None):
    """Split the decoder stack at LAYER BOUNDARIES into ``n_stages``
    pipeline stages — stage i owns layers [i·L/S, (i+1)·L/S) and applies
    them with a local ``lax.scan`` (dense experts: the pipe axis shards
    STAGES, not experts). Requires n_layers % n_stages == 0.

    Returns (per_stage_params, stage_fn) for
    parallel.pipeline.stack_stage_params / pipeline_apply; embed/decoder
    stay outside the pipe (applied before/after), activations are
    (mb, T, d) — uniform, as pipelining requires. Every stage carries the
    same (L/S, ...) param structure, so the stacked pytree is uniform with
    no zero-padded union slots; gradients per layer are exact (the round-5
    union-zero/lax.switch staging is gone with the depth axis).

    ``attn_impl`` forces the attention core of every staged layer; default
    None resolves via the flash_attention override/env/auto chain on the
    microbatch sequence length. ``moe_fn(router_w, experts, flat)``
    overrides the staged FFN (default: the dense top-k MoE — the pipe axis
    shards STAGES, so experts run dense inside each stage regardless of E;
    grouped n_experts > n_devices rides along for free). The seam exists so
    a capacity-matched dense twin (or a future ep-composed dispatch) can be
    staged without re-deriving the stage math."""
    blocks = params["blocks"]
    n_layers = lm_n_layers(params)
    if n_layers % n_stages:
        raise ValueError(
            f"n_layers={n_layers} does not split over {n_stages} pipeline "
            "stages — layer-boundary staging needs n_layers % n_stages == 0")
    per = n_layers // n_stages
    per_stage = [
        jax.tree_util.tree_map(lambda a: a[i * per:(i + 1) * per], blocks)
        for i in range(n_stages)
    ]

    core = lambda q, k, v: attention_core(q, k, v, causal=True,  # noqa: E731
                                          impl=attn_impl)
    moe = moe_fn or (lambda rw, ex, x: dense_moe(rw, ex, x, top_k))

    def stage_fn(p, x):
        layer = _train_layer(n_heads, core, moe, FLAGSHIP_SPEC)
        return _stacked_layers(
            p, x, lambda *args: (layer(*args)[0], None))[0]

    return per_stage, stage_fn


def make_pp_loss(stage_fn, mesh: Mesh, pipe_axis: str,
                 batch_axis: Optional[str] = None,
                 with_metrics: bool = False,
                 overlap: bool = False):
    """Staged-LM task loss for the dp×pp path — embed lookup, the pipeline
    schedule over ``pipe_axis``, decoder, mean NLL. The dense twin is
    ``dense_loss_fn(n_heads, aux_weight=0.0)`` on the flattened
    microbatches (aux is a router-training regularizer, orthogonal to
    pipeline parity). Shared by tests/test_composed.py and the driver's
    dryrun gate so the two can never drift apart.

    loss(trained, toks_mbs, targets_mbs) where trained = (stacked_stage_
    params, embed, dec_w, dec_b) and toks/targets are (n_micro, mb, T).

    ``with_metrics`` returns (loss, metrics) with the per-microbatch NLL
    means — the pipeline-health signal (a diverging microbatch shows up as
    one hot row) for telemetry-threaded dp×pp steps
    (parallel.pipeline.make_pipeline_train_step(with_metrics=True))."""
    from deeplearning4j_tpu.parallel.pipeline import pipeline_apply

    def loss(trained, toks_mbs, tgt_mbs):
        stacked, embed, dec_w, dec_b = trained
        with jax.named_scope("lm_embed"):
            x_mbs = embed[toks_mbs]  # (M, mb, T, d)
        outs = pipeline_apply(stacked, x_mbs, stage_fn, mesh, pipe_axis,
                              batch_axis=batch_axis, overlap=overlap)
        with jax.named_scope("lm_loss"):
            logits = outs @ dec_w + dec_b
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, tgt_mbs[..., None], -1)[..., 0]
            if with_metrics:
                return jnp.mean(nll), {
                    "microbatch_loss": jnp.mean(nll, axis=tuple(
                        range(1, nll.ndim))),  # (M,)
                }
            return jnp.mean(nll)

    return loss


# ---------------------------------------------------------------- serving ----
#
# ISSUE 10: the decode-mode forward behind deeplearning4j_tpu/serve/. Two
# entry points run the training model's block (``_block``) with another
# attention and the one-chip expert layer handed in:
#
# - ``lm_prefill``: the full-prompt pass through the attn_impl seam (dense
#   or blockwise flash — the long-prompt path), additionally returning every
#   layer's projected K/V so the serving engine can seed a request's cache
#   row in one dispatch.
# - ``lm_decode_step``: one token per slot attending over the per-slot KV
#   cache with a position mask — O(1) work per token instead of the O(t)
#   full recompute ``cli predict`` used to do.
#
# The cache is a fixed-size paged buffer: leaf shape (L, S, H, T_max, Dh)
# where S is the engine's slot count; slot s's page is overwritten on
# readmission (eviction costs nothing — the mask hides stale positions).
# Through the layer loop of decode, verify and chunked prefill
# (``_cached_layers``) both leaves travel WHOLE as the loop's carry: layer
# l writes its new rows into the carry in place and reads its own slab
# back out of it, so a step moves the rows it stores and the positions it
# attends, never a copy of the cache.
# Sampling (greedy vs temperature, selected IN-GRAPH from a per-slot
# temperature vector so one executable serves both) is fused into the same
# jitted step as the forward — one dispatch per decode iteration.

def init_kv_cache(n_layers: int, n_slots: int, n_kv_heads: int,
                  head_dim: int, max_len: int, dtype=jnp.float32) -> dict:
    """Zeroed paged KV cache for ``n_slots`` concurrent requests:
    ``{"k","v"}`` leaves of shape (L, S, H_kv, T_max, Dh), H_kv the K/V
    head count (the query head count unless the spec groups them). Zeros
    (not garbage) so masked-out positions can never inject non-finite
    values through the 0-weight attention terms. The layer axis leads
    because the serving programs carry each leaf whole through their layer
    loop and index it by layer there (``_cached_layers``); the donated
    leaves are updated in place, so one cache is all the memory a step
    needs for it."""
    shape = (n_layers, n_slots, n_kv_heads, max_len, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _prefill_core(spec: BlockSpec, attn_impl: Optional[str]):
    """``attn_core(q, k, v)`` of the prompt pass: the selection seam's
    causal core, or under the block mask (i sees j iff j // B <= i // B)
    the dense masked one; grouped K/V heads are repeated to the query
    heads here, so what the block hands the cache stays H_kv wide."""
    def core(q, k, v):
        group = q.shape[1] // k.shape[1]
        if group > 1:
            k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        if spec.attn_mask != "block":
            if spec.attention_multiplier is not None:
                # the seam's cores scale by 1/sqrt(Dh): the spec's instead
                q = q * (spec.attention_multiplier * q.shape[-1] ** 0.5)
            return attention_core(q, k, v, causal=True, impl=attn_impl)
        blk = jnp.arange(q.shape[2]) // spec.block_length
        scores = jnp.where(blk[None, :] <= blk[:, None],
                           _scores(q, k, spec), -1e30)
        return jnp.einsum("shqk,shkd->shqd", jax.nn.softmax(scores, -1), v)

    return core


def _prefill_hidden(params: dict, tokens: Array, n_heads: int, top_k: int,
                    attn_impl: Optional[str], spec: BlockSpec) -> tuple:
    """The prompt pass up to the head: (h (B, T_pad, d), (ks, vs)), the
    block with the one-chip expert layer (``moe_ffn``), every layer's
    projected K/V (B, H_kv, T_pad, Dh) kept to seed the cache."""
    core = _prefill_core(spec, attn_impl)
    with jax.named_scope("lm_embed"):
        h = params["embed"][tokens]

    def layer_fn(layer_params, h, layer):
        h, kv, _ = _block(
            layer_params, h, n_heads, spec,
            lambda q, k, v: (core(q, k, v), (k, v)),
            partial(moe_ffn, top_k=top_k, spec=spec, layer=layer))
        return h, kv

    blocks = params["blocks"]
    return _stacked_layers(
        blocks, h, layer_fn,
        experts_whole=_routes(tokens.size, top_k,
                              blocks["router"].shape[-1]))


def lm_prefill(params: dict, tokens: Array, n_heads: int, top_k: int = 2,
               attn_impl: Optional[str] = None,
               spec: BlockSpec = FLAGSHIP_SPEC) -> tuple:
    """Prompt pass: tokens (B, T_pad) → (logits (B, T_pad, V), ks, vs) with
    ks/vs (L, B, H_kv, T_pad, Dh) — every layer's projected K/V, ready to
    seed cache pages. Attention routes through the core-selection seam
    exactly like the training paths (``attn_impl`` forces
    dense/blockwise/flash); causal masking makes right-padding exact:
    positions >= the real length produce garbage K/V that decode's position
    mask never reads. Under the block mask the same holds block for block:
    no block sees a later one."""
    if spec.layer_pattern is not None:
        raise ValueError("a layer pattern's prompt pass keeps recurrent "
                         "state as well: make_prefill_step")
    h, (ks, vs) = _prefill_hidden(params, tokens, n_heads, top_k, attn_impl,
                                  spec)
    return _lm_head(params, h, spec), ks, vs


# ---- a layer pattern (ISSUE 37): attention and state-space layers in one
# model, each kind's layers stacked apart, one scan over the periods ----

def _period_scan(blocks: dict, spec: BlockSpec, carry, layer_fn) -> tuple:
    """The layer loop of a layer pattern: ONE ``lax.scan`` over the
    periods, the period's layers in pattern order in its body, so that
    compile time stays O(1) in depth. Layer j of a kind in period p is the
    ``p * (that kind's count a period) + j``-th of its kind's stack, read
    in place by index as ``_stacked_layers``' scan reads a layer.
    ``layer_fn(kind, layer params, carry, index among its kind) -> (carry,
    kept)``; returns (carry, {kind: kept stacked over that kind's layers
    in depth order}) for the kinds that keep something."""
    pattern = spec.layer_pattern
    per = {kind: pattern.count(kind) for kind in pattern}
    first = pattern[0]
    periods = jax.tree_util.tree_leaves(blocks[first])[0].shape[0] \
        // per[first]

    def body(carry, period):
        seen = dict.fromkeys(per, 0)
        kept = {kind: [] for kind in per}
        for kind in pattern:
            index = period * per[kind] + seen[kind]
            seen[kind] += 1
            layer_params = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, index, 0,
                                                       keepdims=False),
                blocks[kind])
            carry, out = layer_fn(kind, layer_params, carry, index)
            kept[kind].append(out)
        return carry, {kind: jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *outs)
            for kind, outs in kept.items() if outs[0] is not None}

    carry, kept = jax.lax.scan(body, carry,
                               jnp.arange(periods, dtype=jnp.int32))
    return carry, jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), kept)


def _hybrid_prefill_hidden(params: dict, tokens: Array, n_heads: int,
                           attn_impl: Optional[str], spec: BlockSpec,
                           lengths) -> tuple:
    """The prompt pass of a layer pattern up to the head: (h (B, T_pad, d),
    {"attention": (ks, vs) (L_a, B, H_kv, T_pad, Dh), "mamba": (conv
    (L_m, B, K - 1, conv_dim), state (L_m, B, H, P, N))}). ``lengths`` are
    the real rows of each prompt: causal masking makes right-padding exact
    for K/V, and the mixer zeroes dt and x past them so that the state it
    keeps is the one at the last real row (models/ssm.py)."""
    from deeplearning4j_tpu.models import ssm

    core = _prefill_core(spec, attn_impl)
    mixer = partial(ssm.mixer_prefill, spec=spec, lengths=lengths)

    def layer_fn(kind, layer_params, h, _):
        if kind == "mamba":
            h, kept, _ = _block(layer_params, h, n_heads, spec, mixer, None,
                                kind=kind)
        else:
            h, kept, _ = _block(layer_params, h, n_heads, spec,
                                lambda q, k, v: (core(q, k, v), (k, v)), None)
        return h, kept

    return _period_scan(params["blocks"], spec, _embed(params, tokens, spec),
                        layer_fn)


def init_hybrid_cache(spec: BlockSpec, n_layers: int, n_slots: int,
                      n_kv_heads: int, head_dim: int, max_len: int,
                      dtype=jnp.float32) -> dict:
    """The serving state of a layer pattern, zeroed: ``"k"``/``"v"`` (L_a,
    S, H_kv, T_max, Dh) for its attention layers as ``init_kv_cache`` lays
    them out, and for its state-space layers ``"conv"`` (L_m, S, K - 1,
    conv_dim) in ``dtype`` and ``"ssm"`` (L_m, S, H, P, N) in float32 (the
    state accumulates over a whole request). Every leaf is carried whole
    through the cached step's loop and written in place."""
    from deeplearning4j_tpu.models import ssm

    counts = _pattern_counts(spec, n_layers)
    w = ssm.widths(spec)
    cache = init_kv_cache(counts.get("attention", 0), n_slots, n_kv_heads,
                          head_dim, max_len, dtype)
    cache["conv"] = jnp.zeros((counts.get("mamba", 0), n_slots,
                               w["kernel"] - 1, w["conv_dim"]), dtype)
    cache["ssm"] = jnp.zeros((counts.get("mamba", 0), n_slots, w["heads"],
                              w["head_dim"], w["state"]), jnp.float32)
    return cache


def _hybrid_cached_layers(params: dict, cache: dict, h: Array,
                          positions: Array, n_heads: int,
                          spec: BlockSpec) -> tuple:
    """``_cached_layers`` of a layer pattern, one new row a slot: h (S, 1,
    d) through every layer, the carry ``(h, k, v, conv, ssm)`` with every
    cache leaf whole. An attention layer attends over its pages
    (``_attend_cache``, its rows written first); a state-space layer reads
    its conv and recurrent state of every slot, updates both with the row
    and writes them back in place (``ssm.mixer_step``)."""
    from deeplearning4j_tpu.models import ssm

    slot0 = jnp.zeros((), jnp.int32)
    rows = lambda: (positions[:, None]  # noqa: E731
                    + jnp.arange(h.shape[1])[None, :])

    def layer_fn(kind, layer_params, carry, index):
        h, ck, cv, conv, state = carry
        if kind == "mamba":
            h, (conv, state), _ = _block(
                layer_params, h, n_heads, spec,
                partial(ssm.mixer_step, spec=spec, conv_cache=conv,
                        ssm_cache=state, layer=index), None, kind=kind)
        else:
            h, (ck, cv), _ = _block(
                layer_params, h, n_heads, spec,
                partial(_attend_cache, ck=ck, cv=cv, layer=index, slot0=slot0,
                        positions=positions, rows=rows, spec=spec,
                        layouts=_POSITIONS_MINOR),
                None, rows)
        return (h, ck, cv, conv, state), None

    (h, ck, cv, conv, state), _ = _period_scan(
        params["blocks"], spec,
        (h, cache["k"], cache["v"], cache["conv"], cache["ssm"]), layer_fn)
    return {"k": ck, "v": cv, "conv": conv, "ssm": state}, h


_CACHE_LAYOUT = Layout(major_to_minor=(0, 1, 2, 3, 4))
# the layer pattern's K/V leaves and chunks, positions minor
_POSITIONS_MINOR = (Layout(major_to_minor=(0, 1, 2, 4, 3)),
                    Layout(major_to_minor=(0, 1, 3, 2)))


def _write_cache_rows(ck: Array, cv: Array, k_new: Array, v_new: Array,
                      layer: Array, slot0: Array, positions: Array,
                      layout: Layout = _CACHE_LAYOUT) -> tuple:
    """Stores k_new/v_new (n, H, W, Dh) into the whole cache leaves ck/cv
    (L, S, H, T_max, Dh) at ``[layer, slot0 + i, :, positions[i] :
    positions[i] + W, :]``: one ``dynamic_update_slice`` a slot and leaf
    (start clamped so the window fits, as ever), each in place in the
    buffer it is handed. The layout constraint keeps the leaves in the
    row-major layout the cache arrives and leaves in. Left free, the v5e
    compiler re-lays a loop-carried cache with positions major to heads,
    which makes a one-row update contiguous and costs four whole-cache
    transposing copies a step round the layer loop. ``layout`` is the one
    the leaves arrive in where that is another (``_hybrid_cached_layers``)."""
    def one_slot(i, leaves):
        at = (layer, slot0 + i, 0, positions[i], 0)
        return tuple(
            with_layout_constraint(
                jax.lax.dynamic_update_slice(
                    c, jax.lax.dynamic_slice_in_dim(new, i, 1)[None]
                    .astype(c.dtype), at),
                layout)
            for c, new in zip(leaves, (k_new, v_new)))

    return jax.lax.fori_loop(0, positions.shape[0], one_slot, (ck, cv))


def _attend_cache(q: Array, k_new: Array, v_new: Array, ck: Array, cv: Array,
                  layer: Array, slot0: Array, positions: Array, rows,
                  spec: BlockSpec, layouts: Optional[tuple] = None) -> tuple:
    """``attend`` of the cached step, W new tokens a slot: q (S, H, W, Dh)
    and the new rows (S, H_kv, W, Dh) of slots ``slot0``..``slot0 + S - 1``
    (every slot from 0 in decode and verify, the one slot of a prefill
    chunk); ck/cv the WHOLE cache leaves (L, n_slots, H_kv, T_max, Dh), of
    which this touches those slots' pages of layer ``layer`` alone. Writes
    the new K/V at ``positions``..``positions + W - 1`` FIRST, in place
    (``_write_cache_rows``), then slices the pages back out and attends
    with the per-query mask ``index <= rows()`` — so every freshly written
    position is visible to the queries at or after it and stale cache
    beyond them never is. Under the block mask a query sees up to the last
    row of its own block of B: the block-diffusion step (W = B,
    ``positions`` a block's first row) attends to the whole block it has
    just written, and what it wrote stays only until the same block's next
    forward overwrites it. The attention math mirrors
    ring_attention.reference_attention (same score scale, same -1e30 mask,
    jax.nn.softmax): the masked terms underflow to exact zeros, so the
    padded reduction is bitwise the oracle's unpadded one. W=1 is the
    decode hot path; W=k+1 is the speculative verify step (ISSUE 16) —
    the same math, so verify logits at offset i are exactly what i
    sequential decode steps over the same tokens would produce. A cache of
    ``attn_chunks`` chunks is read by ``_attend_chunks`` instead, only up
    to the chunk of the furthest row a query sees. ``layouts`` (the leaves',
    a chunk's) pins the layout the leaves arrive in through the writes and
    the chunk loop where it is not the row-major one (None). Returns
    (out (S, H, W, Dh), (ck, cv))."""
    n_slots, n_heads = q.shape[:2]
    with jax.named_scope("lm_cache_write"):
        ck, cv = _write_cache_rows(ck, cv, k_new, v_new, layer, slot0,
                                   positions,
                                   layouts[0] if layouts else _CACHE_LAYOUT)
    n_kv, n_chunks = ck.shape[2], attn_chunks(ck.shape[3])
    group = n_heads // n_kv

    def visible():
        """(S, G*W): the last cache row each query row sees."""
        seen = pos_q = rows()
        if spec.attn_mask == "block":
            seen = pos_q // spec.block_length * spec.block_length \
                + (spec.block_length - 1)
        return jnp.tile(seen, (1, group)) if group > 1 else seen

    if n_chunks:
        if group > 1:
            q = q.reshape(n_slots, n_kv, -1, q.shape[-1])
        o = _attend_chunks(q, ck, cv, layer, slot0, visible(), n_chunks,
                           spec, layouts)
    else:
        pages = lambda c: jax.lax.dynamic_slice(  # noqa: E731
            c, (layer, slot0, 0, 0, 0), (1, n_slots) + c.shape[2:])[0]
        ck_l, cv_l = pages(ck), pages(cv)              # (S, H_kv, T_max, Dh)
        if group > 1:
            # the query heads of one K/V head as further query rows of it
            q = q.reshape(n_slots, n_kv, -1, q.shape[-1])
        scores = _scores(q, ck_l, spec)                # (S, H_kv, G*W, T_max)
        seen = visible()
        mask = (jnp.arange(ck_l.shape[2])[None, None, None, :]
                <= seen[:, None, :, None])
        scores = jnp.where(mask, scores, -1e30)
        o = jnp.einsum("shqk,shkd->shqd", jax.nn.softmax(scores, -1), cv_l)
    if group > 1:
        o = o.reshape(n_slots, n_heads, -1, o.shape[-1])
    return o, (ck, cv)


def _attend_chunks(q: Array, ck: Array, cv: Array, layer: Array,
                   slot0: Array, seen: Array, n_chunks: int,
                   spec: BlockSpec, layouts: Optional[tuple] = None) -> Array:
    """``_attend_cache``'s masked softmax read in chunks: q (S, H_kv, R, Dh)
    against layer ``layer``'s pages of slots ``slot0``..``slot0 + S - 1``,
    query row r seeing cache rows ``<= seen[:, r]``. A ``fori_loop`` takes
    one (S, H_kv, ATTN_CHUNK, Dh) chunk of K and of V a trip, as many trips
    as the chunk of the furthest row any query sees, counted on the device;
    the chunks past it are never read. Scores with the spec's scale and the
    same -1e30 mask, then the online softmax in float32 (running max, sum
    and output, rescaled by exp(m_old - m_new)), normalised once at the end
    and cast to the dtype the whole-slab read gives. A chunk a query cannot
    see at all adds exactly nothing (its terms are exact zeros and the
    rescale exactly 1), so a query's output does not depend on how many
    chunks its step reads: verify and decode, which read different numbers
    of them, still compute the same thing.

    ``layouts`` (the leaves', a chunk's) pins both inside the loop: the
    layer pattern's step, whose leaves arrive with positions minor (the
    v5e's own layout for a head size of 64), re-lays both whole leaves
    before the loop and back after it otherwise."""
    n_slots, n_kv, n_rows, head_dim = q.shape
    f32 = jnp.float32
    dtype = jnp.result_type(f32 if spec.accum_f32 else
                            jnp.result_type(q.dtype, ck.dtype), cv.dtype)
    trips = jnp.minimum(jnp.max(seen) // ATTN_CHUNK + 1, n_chunks)
    index = jnp.arange(ATTN_CHUNK)[None, None, None, :]

    def chunk(j, carry):
        m, total, acc = carry
        start = j * ATTN_CHUNK
        k, v = (jax.lax.dynamic_slice(
            c, (layer, slot0, 0, start, 0),
            (1, n_slots, n_kv, ATTN_CHUNK, head_dim))[0] for c in (ck, cv))
        if layouts:
            k, v = (with_layout_constraint(c, layouts[1]) for c in (k, v))
        scores = jnp.where(start + index <= seen[:, None, :, None],
                           _scores(q, k, spec).astype(f32), -1e30)
        m_new = jnp.maximum(m, scores.max(-1))
        p = jnp.exp(scores - m_new[..., None])
        rescale = jnp.exp(m - m_new)
        total = rescale * total + p.sum(-1)
        acc = rescale[..., None] * acc + jnp.einsum(
            "shqk,shkd->shqd", p.astype(v.dtype), v,
            preferred_element_type=f32)
        return m_new, total, acc

    rows = (n_slots, n_kv, n_rows)
    if layouts:
        ck, cv = (with_layout_constraint(c, layouts[0]) for c in (ck, cv))
    _, total, acc = jax.lax.fori_loop(
        0, trips, chunk, (jnp.full(rows, -jnp.inf, f32), jnp.zeros(rows, f32),
                          jnp.zeros(rows + (head_dim,), f32)))
    return (acc / total[..., None]).astype(dtype)


def _cached_layers(params: dict, cache: dict, h: Array, positions: Array,
                   n_heads: int, top_k: int, slot0=0,
                   spec: BlockSpec = FLAGSHIP_SPEC) -> tuple:
    """The one layer loop of decode, verify, chunked prefill and the
    block-diffusion step: h (S, W, d), the rows of slots
    ``slot0``..``slot0 + S - 1``, through every block, each attending over
    the cache (``_attend_cache``). The loop scans the stacked block params
    with the layer's index and CARRIES ``(h, cache k, cache v)``, both
    leaves whole: a scanned cache would be sliced a layer at a time on the
    way in and stacked into a second cache on the way out, six whole-cache
    copies a step for a few rows stored. Returns (cache, h)."""
    slot0 = jnp.asarray(slot0, jnp.int32)
    rows = lambda: (positions[:, None]  # noqa: E731
                    + jnp.arange(h.shape[1])[None, :])            # (S, W)

    def step(carry, xs):
        h, ck, cv = carry
        layer_params, layer = xs
        h, (ck, cv), _ = _block(
            layer_params, h, n_heads, spec,
            partial(_attend_cache, ck=ck, cv=cv, layer=layer, slot0=slot0,
                    positions=positions, rows=rows, spec=spec),
            partial(moe_ffn, top_k=top_k, spec=spec), rows)
        return (h, ck, cv), None

    layers = jnp.arange(cache["k"].shape[0], dtype=jnp.int32)
    (h, ck, cv), _ = jax.lax.scan(
        step, (h, cache["k"], cache["v"]), (params["blocks"], layers))
    return {"k": ck, "v": cv}, h


def lm_decode_step(params: dict, cache: dict, tokens: Array,
                   positions: Array, n_heads: int, top_k: int = 2,
                   spec: BlockSpec = FLAGSHIP_SPEC) -> tuple:
    """One decode iteration over every slot: tokens (S,) int32 land at
    ``positions`` (S,) in the cache and next-token logits (S, V) come back
    with the updated cache. The cache rides through the layer loop as its
    carry (``_cached_layers``): layer l stores S rows at ``[l, s, :,
    positions[s], :]`` and every other element comes back untouched, in
    the same buffers when the caller donated them. Under a layer pattern
    the cache holds each slot's recurrent state too, updated in place
    (``_hybrid_cached_layers``)."""
    if spec.layer_pattern is not None:
        cache, h = _hybrid_cached_layers(
            params, cache, _embed(params, tokens, spec)[:, None, :],
            positions, n_heads, spec)
        return cache, _lm_head(params, h, spec)[:, 0, :]
    with jax.named_scope("lm_embed"):
        h = params["embed"][tokens][:, None, :]           # (S, 1, d)
    cache, h = _cached_layers(params, cache, h, positions, n_heads, top_k,
                              spec=spec)
    return cache, _lm_head(params, h, spec)[:, 0, :]


def sample_tokens(logits: Array, key: Array, temperature: Array) -> Array:
    """Fused sampling: greedy argmax where ``temperature <= 0``, else
    temperature-scaled categorical — selected in-graph so ONE compiled
    step serves any mix of greedy and sampling requests (per-slot
    temperature vector; no retrace when the mix changes)."""
    with jax.named_scope("lm_sample"):
        greedy = jnp.argmax(logits, axis=-1)
        scaled = logits / jnp.maximum(temperature, 1e-6)[..., None]
        sampled = jax.random.categorical(key, scaled)
        return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def make_decode_step(n_heads: int, top_k: int = 2, donate_cache: bool = True,
                     params_transform=None,
                     spec: BlockSpec = FLAGSHIP_SPEC):
    """The serving engine's hot executable:
    ``step(params, cache, tokens, positions, temps, key, step_idx) ->
    (cache, next_tokens)``. Shapes are FIXED at the slot count — occupancy
    changes never retrace (0-compile steady state pinned in
    tests/test_serve.py); ``step_idx`` is folded into the key in-graph so
    the host never advances RNG state. ``donate_cache`` donates the old
    cache buffers into the update (the engine always rebinds).
    ``params_transform`` runs inside the jit — the serve_dtype seam's
    int8→bf16 dequantization hook (serve/quant.py); None = identity."""
    transform = params_transform or (lambda p: p)

    @partial(jax.jit, donate_argnums=(1,) if donate_cache else ())
    def step(params, cache, tokens, positions, temps, key, step_idx):
        params = transform(params)
        cache, logits = lm_decode_step(params, cache, tokens, positions,
                                       n_heads, top_k, spec)
        k = jax.random.fold_in(key, step_idx)
        return cache, sample_tokens(logits, k, temps)

    return step


def make_prefill_step(n_heads: int, top_k: int = 2,
                      attn_impl: Optional[str] = None,
                      donate_cache: bool = True, params_transform=None,
                      spec: BlockSpec = FLAGSHIP_SPEC):
    """Admission executable: ``prefill(params, cache, tokens, last_idx,
    slot, temp, key, step_idx) -> (cache, first_token)`` — the prompt pass
    (through the attn_impl seam), the cache-page write at ``slot``, and the
    first sampled token fused into one dispatch. ``tokens`` is (1, T_pad)
    right-padded to the engine's bucket, so compiles are bounded by the
    bucket count (slot/last_idx are traced). Under block-diffusion
    generation the prompt pass only stores: the head is not run and the
    token that comes back is 0, nothing to accept. Under a layer pattern
    the slot's recurrent state is written beside its K/V pages
    (``_make_hybrid_prefill``)."""
    transform = params_transform or (lambda p: p)
    if spec.layer_pattern is not None:
        return _make_hybrid_prefill(n_heads, attn_impl, donate_cache,
                                    transform, spec)
    stores_only = spec.generation == "block_diffusion"

    @partial(jax.jit, donate_argnums=(1,) if donate_cache else ())
    def prefill(params, cache, tokens, last_idx, slot, temp, key, step_idx):
        params = transform(params)
        if stores_only:
            _, (ks, vs) = _prefill_hidden(params, tokens, n_heads, top_k,
                                          attn_impl, spec)
        else:
            logits, ks, vs = lm_prefill(params, tokens, n_heads, top_k,
                                        attn_impl, spec)
        with jax.named_scope("lm_cache_write"):
            ck = jax.lax.dynamic_update_slice(
                cache["k"], ks.astype(cache["k"].dtype), (0, slot, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], vs.astype(cache["v"].dtype), (0, slot, 0, 0, 0))
        if stores_only:
            return {"k": ck, "v": cv}, jnp.zeros((), jnp.int32)
        last = jax.lax.dynamic_index_in_dim(logits[0], last_idx, 0,
                                            keepdims=False)
        k = jax.random.fold_in(key, step_idx)
        return {"k": ck, "v": cv}, sample_tokens(last, k, temp)

    return prefill


def _make_hybrid_prefill(n_heads: int, attn_impl: Optional[str],
                         donate_cache: bool, transform, spec: BlockSpec):
    """``make_prefill_step`` of a layer pattern, the same signature: the
    prompt pass with ``last_idx + 1`` real rows, the slot's K/V pages and
    its conv and recurrent state written (every state row of the slot, so
    nothing of an earlier request is left to read), the head on the last
    real row alone."""
    @partial(jax.jit, donate_argnums=(1,) if donate_cache else ())
    def prefill(params, cache, tokens, last_idx, slot, temp, key, step_idx):
        params = transform(params)
        h, kept = _hybrid_prefill_hidden(params, tokens, n_heads, attn_impl,
                                         spec, last_idx + 1)
        fresh = dict(zip(("k", "v"), kept.get("attention", ())),
                     **dict(zip(("conv", "ssm"), kept.get("mamba", ()))))
        out = dict(cache)
        with jax.named_scope("lm_cache_write"):
            for name, new in fresh.items():
                at = (0, slot) + (0,) * (cache[name].ndim - 2)
                out[name] = jax.lax.dynamic_update_slice(
                    cache[name], new.astype(cache[name].dtype), at)
        last = jax.lax.dynamic_index_in_dim(h[0], last_idx, 0)
        logits = _lm_head(params, last[None], spec)[0, 0]
        return out, sample_tokens(logits, jax.random.fold_in(key, step_idx),
                                  temp)

    return prefill


def lm_verify_step(params: dict, cache: dict, tokens: Array,
                   positions: Array, n_heads: int, top_k: int = 2,
                   spec: BlockSpec = FLAGSHIP_SPEC) -> tuple:
    """Speculative verify forward (ISSUE 16): W tokens per slot — tokens
    (S, W) int32 land at ``positions``..``positions + W - 1`` in the cache
    and per-position next-token logits (S, W, V) come back with the
    updated cache. Column 0 is the slot's pending token, columns 1..W-1
    the draft's proposals; because ``_attend_cache`` computes offset i's
    query against exactly the cache a sequential decode at position
    ``positions + i`` would see, logits[:, i] are token-identical to i
    single-token decode steps over the same inputs — ONE dispatch verifies
    all k proposals. The caller must guarantee ``positions + W <=
    T_max`` (``dynamic_update_slice`` clamps out-of-range starts, which
    would silently overwrite live earlier positions)."""
    with jax.named_scope("lm_embed"):
        h = params["embed"][tokens]                       # (S, W, d)
    cache, h = _cached_layers(params, cache, h, positions, n_heads, top_k,
                              spec=spec)
    return cache, _lm_head(params, h, spec)               # (S, W, V)


def make_verify_step(n_heads: int, top_k: int = 2, donate_cache: bool = True,
                     params_transform=None,
                     spec: BlockSpec = FLAGSHIP_SPEC):
    """The speculative-decoding flagship executable:
    ``verify(params, cache, tokens, positions, temps, key, step_idx) ->
    (cache, toks)`` with tokens (S, W) → toks (S, W) int32. toks[:, i] is
    ``sample_tokens`` over the logits at offset i (greedy argmax for
    ``temps <= 0`` — the value the acceptance rule compares draft
    proposals against, and the value a plain decode step at that position
    would emit). Shapes are fixed at (S, W = k+1), so one executable per
    configured k and the 0-compile steady state holds. Sampling keys fold
    in both ``step_idx`` and the offset, so the W positions draw
    independent streams."""
    transform = params_transform or (lambda p: p)

    @partial(jax.jit, donate_argnums=(1,) if donate_cache else ())
    def verify(params, cache, tokens, positions, temps, key, step_idx):
        params = transform(params)
        cache, logits = lm_verify_step(params, cache, tokens, positions,
                                       n_heads, top_k, spec)
        k = jax.random.fold_in(key, step_idx)
        toks = jnp.stack(
            [sample_tokens(logits[:, i, :], jax.random.fold_in(k, i), temps)
             for i in range(tokens.shape[1])], axis=1)
        return cache, toks

    return verify


def lm_block_step(params: dict, cache: dict, tokens: Array, starts: Array,
                  masked: Array, n_heads: int, top_k: int,
                  spec: BlockSpec) -> tuple:
    """One forward of a block of B positions a slot, the block-diffusion
    sibling of ``lm_verify_step`` (W = B): tokens (S, B) int32, of which
    the positions ``masked`` (S, B) bool enter as the mask token's
    embedding, land at rows ``starts``..``starts + B - 1`` of the cache and
    logits (S, B, V) come back with the updated cache. Under the spec's
    block mask every query sees the cache up to its block's last row, the
    rows this forward has just written included."""
    with jax.named_scope("lm_embed"):
        h = params["embed"][jnp.where(masked, spec.mask_token_id, tokens)]
    cache, h = _cached_layers(params, cache, h, starts, n_heads, top_k,
                              spec=spec)
    return cache, _lm_head(params, h, spec)


def unmask_most_confident(logits: Array, tokens: Array, masked: Array,
                          n: int, key: Array, temps: Array) -> tuple:
    """The choice a denoising forward makes, on the device: at every masked
    position a token (``sample_tokens``) and its confidence, the softmax
    probability of that token in float32; the ``n`` masked positions of
    highest confidence (all that are left, if fewer; the earlier position
    on a tie) take their token and leave the mask. Returns (tokens (S, B),
    masked (S, B)) after the forward; a slot with nothing masked comes back
    as it went in."""
    with jax.named_scope(LM_UNMASK_SCOPE):
        width = tokens.shape[1]
        toks = jnp.stack(
            [sample_tokens(logits[:, i, :], jax.random.fold_in(key, i),
                           temps) for i in range(width)], axis=1)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        conf = jnp.take_along_axis(logp, toks[..., None], axis=-1)[..., 0]
        order = jnp.argsort(jnp.where(masked, -conf, jnp.inf), axis=1,
                            stable=True)
        rank = jnp.argsort(order, axis=1, stable=True)
        take = masked & (rank < n)
        return jnp.where(take, toks, tokens), masked & ~take


def make_block_step(n_heads: int, top_k: int, spec: BlockSpec,
                    donate_cache: bool = True, params_transform=None):
    """The block-diffusion engine's hot executable, ``jit_block_step``:
    ``block_step(params, cache, tokens, starts, masked, temps, key,
    step_idx) -> (cache, tokens, masked)``, shapes fixed at (S, B). One
    program for both kinds of forward. A denoising forward (some position
    of the slot masked) writes the block's K/V rows, provisional because
    masked inputs made them, and unmasks ``B // D`` positions
    (``unmask_most_confident``); a commit forward (none masked) writes the
    block's final rows and changes nothing else. Both write first and read
    back, as ``_attend_cache`` does, so no second cache holds the
    provisional rows: the same block's next forward overwrites them."""
    transform = params_transform or (lambda p: p)
    n = max(1, spec.block_length // spec.denoising_steps)

    @partial(jax.jit, donate_argnums=(1,) if donate_cache else ())
    def block_step(params, cache, tokens, starts, masked, temps, key,
                   step_idx):
        params = transform(params)
        cache, logits = lm_block_step(params, cache, tokens, starts, masked,
                                      n_heads, top_k, spec)
        tokens, masked = unmask_most_confident(
            logits, tokens, masked, n, jax.random.fold_in(key, step_idx),
            temps)
        return cache, tokens, masked

    return block_step


def make_chunk_prefill_step(n_heads: int, top_k: int = 2,
                            donate_cache: bool = True,
                            params_transform=None,
                            spec: BlockSpec = FLAGSHIP_SPEC):
    """Chunked/suffix prefill executable (ISSUE 16): ``chunk(params,
    cache, tokens, start, last_idx, slot, temp, key, step_idx) -> (cache,
    tok)`` — ONE slot's tokens (1, W) written at absolute positions
    ``start``..``start + W - 1``, each query attending the slot's cache
    at ``index <= start + offset`` (so a chunk sees every earlier chunk
    AND any prefix-cache-seeded pages — the same write-then-mask math as
    ``_attend_cache``, token-identical to the one-shot ``lm_prefill``
    path). ``tok`` samples the logits at in-chunk index ``last_idx``; the
    engine uses it only from the final chunk (last_idx = prompt_len - 1 -
    start) and ignores it from earlier ones. Compiles are keyed by W
    alone (start/last_idx/slot traced), so a fixed ``prefill_chunk``
    costs one executable. The caller must keep ``start + W <= T_max``
    (the engine shifts the final chunk left to overlap — recomputing a
    position from the same tokens rewrites the same values)."""
    transform = params_transform or (lambda p: p)

    @partial(jax.jit, donate_argnums=(1,) if donate_cache else ())
    def chunk(params, cache, tokens, start, last_idx, slot, temp, key,
              step_idx):
        params = transform(params)
        with jax.named_scope("lm_embed"):
            h = params["embed"][tokens]                   # (1, W, d)
        pos = jnp.asarray(start, jnp.int32)[None]         # (1,)
        cache, h = _cached_layers(params, cache, h, pos, n_heads, top_k,
                                  slot0=slot, spec=spec)
        logits = _lm_head(params, h, spec)[0]             # (W, V)
        last = jax.lax.dynamic_index_in_dim(logits, last_idx, 0,
                                            keepdims=False)
        k = jax.random.fold_in(key, step_idx)
        return cache, sample_tokens(last, k, temp)

    return chunk


def draft_truncate_params(params: dict, n_layers: int) -> dict:
    """Layer-truncated draft LM (ISSUE 16): the flagship's first
    ``n_layers`` decoder blocks with the SAME embedding and decoder head —
    the zero-training draft for speculative decoding (proposals need only
    be cheap and correlated; the verify step keeps outputs exact). Shares
    the flagship's leaves (no copy), so a draft costs no extra weight
    memory beyond its own cache."""
    total = lm_n_layers(params)
    if not (1 <= n_layers <= total):
        raise ValueError(
            f"draft n_layers must be in [1, {total}], got {n_layers}")
    blocks = jax.tree_util.tree_map(lambda x: x[:n_layers],
                                    params["blocks"])
    return {"embed": params["embed"], "blocks": blocks,
            "dec_w": params["dec_w"], "dec_b": params["dec_b"]}


def draft_distill_loss(teacher_params: dict, n_heads: int, top_k: int = 2,
                       attn_impl: Optional[str] = None):
    """Self-distillation objective for a TRAINED draft (ISSUE 16 — the
    serving half feeding the training half): ``loss(draft_params, tokens)``
    is the mean KL(teacher ‖ draft) over every position, with the teacher
    (flagship) forward under ``stop_gradient``. Plug it into the existing
    trainers exactly like ``dense_loss_fn`` — e.g. distill
    ``draft_truncate_params(flagship, n)`` into a higher-acceptance draft
    on the serving corpus, then hand the result to
    ``DecodeEngine(speculative=SpeculativeConfig(draft_params=...))``."""
    def loss(draft_params: dict, tokens: Array) -> Array:
        core = lambda q, k, v: attention_core(q, k, v, causal=True,  # noqa: E731
                                              impl=attn_impl)
        t_logits, _ = lm_forward(teacher_params, tokens, n_heads, core,
                                 partial(dense_moe, top_k=top_k))
        t_logp = jax.nn.log_softmax(
            jax.lax.stop_gradient(t_logits), axis=-1)
        d_logits, _ = lm_forward(draft_params, tokens, n_heads, core,
                                 partial(dense_moe, top_k=top_k))
        d_logp = jax.nn.log_softmax(d_logits, axis=-1)
        return jnp.mean(jnp.sum(jnp.exp(t_logp) * (t_logp - d_logp),
                                axis=-1))

    return loss


def lm_dims(params: dict) -> dict:
    """Model dimensions recoverable from the params pytree alone (serving
    needs them to size caches and validate requests): everything except
    ``n_heads``, which the head-split erases — that one travels in
    checkpoint meta (``lm_checkpoint_meta``) or a CLI flag."""
    vocab, d_model = params["embed"].shape
    if "experts" not in params["blocks"]:  # a layer pattern: dense FFNs
        d_ff = next(iter(params["blocks"].values()))["mlp"]["w_out"].shape[1]
        return {"vocab": int(vocab), "d_model": int(d_model),
                "n_layers": lm_n_layers(params), "n_experts": 0,
                "d_ff": int(d_ff)}
    experts = params["blocks"]["experts"]
    w_in = experts["wg"] if "wg" in experts else experts["w1"]
    n_layers, n_experts, _, d_ff = w_in.shape
    return {"vocab": int(vocab), "d_model": int(d_model),
            "n_layers": int(n_layers), "n_experts": int(n_experts),
            "d_ff": int(d_ff)}


def lm_checkpoint_meta(params: dict, n_heads: int, top_k: int = 2,
                       spec: BlockSpec = FLAGSHIP_SPEC) -> dict:
    """Checkpoint ``meta`` block letting ``DecodeEngine.from_checkpoint``
    rebuild the decode path with zero side-channel config: pass as
    ``meta=lm_checkpoint_meta(...)`` (or merge the dict) to
    ``Checkpointer.save``. A block of another kind than the flagship's
    travels as its spec's fields, the way it generates among them; a
    flagship checkpoint's meta is what it always was."""
    meta = {**lm_dims(params), "n_heads": int(n_heads), "top_k": int(top_k)}
    if spec != FLAGSHIP_SPEC:
        meta["spec"] = dict(spec._asdict())
    return {"lm": meta}


def spec_from_meta(lm_meta: dict) -> BlockSpec:
    """The spec a checkpoint's ``meta["lm"]`` names (the flagship's where
    it names none, as every checkpoint written before specs does)."""
    return BlockSpec(**lm_meta.get("spec", {}))


def lm_replay(n_heads: int, top_k: int = 2, aux_weight: float = 1e-2,
              attn_impl: Optional[str] = None):
    """``tools/step_replay.py`` factory for flagship-LM replay bundles
    (``--factory deeplearning4j_tpu.models.transformer_lm:lm_replay``).

    Returns ``run(payload) -> dict`` re-executing the faulting step's loss
    + grad from a bundle whose payload is ``{"params": <lm params>,
    "batch": {"tokens", "targets"}}`` — deterministic (the forward has no
    RNG), so a non-finite loss reproduces exactly."""
    loss_fn = dense_loss_fn(n_heads, top_k, aux_weight, attn_impl=attn_impl)

    def run(payload: dict) -> dict:
        from deeplearning4j_tpu.telemetry.metrics import global_norm

        params = jax.tree_util.tree_map(jnp.asarray, payload["params"])
        toks = jnp.asarray(payload["batch"]["tokens"], jnp.int32)
        tgts = jnp.asarray(payload["batch"]["targets"], jnp.int32)
        loss, grads = jax.value_and_grad(loss_fn)(params, toks, tgts)
        return {"loss": float(loss), "grad_norm": float(global_norm(grads))}

    return run


def pp_trained_to_lm_params(trained) -> dict:
    """The dp×pp training carry — (stacked stage params, embed, dec_w,
    dec_b) — back to the CANONICAL params dict ``init_lm_params`` produces:
    stage axis (S, L/S, ...) merged to the (L, ...) block axis.

    This is the checkpoint boundary for pipeline runs: snapshots persist
    the canonical layout, so a dp×pp save restores onto dp×sp×ep, dp×ep,
    or a single device without knowing it was ever staged (the resharding
    matrix in README "Checkpointing")."""
    from deeplearning4j_tpu.parallel.pipeline import merge_stage_axis

    stacked, embed, dec_w, dec_b = trained
    return {"embed": embed, "blocks": merge_stage_axis(stacked),
            "dec_w": dec_w, "dec_b": dec_b}


def lm_params_to_pp_trained(params: dict, mesh: Mesh, n_heads: int,
                            n_stages: int, pipe_axis: str = "pipe",
                            top_k: int = 2,
                            attn_impl: Optional[str] = None):
    """Canonical params → the dp×pp carry: (trained tuple, stage_fn). The
    resume path of a pipeline run — restore the canonical dict (any
    save-time mesh), then re-stage it onto the current pipe axis."""
    from deeplearning4j_tpu.parallel.pipeline import (
        shard_stage_params,
        stack_stage_params,
    )

    per_stage, stage_fn = make_pp_stages(params, n_heads, n_stages=n_stages,
                                         top_k=top_k, attn_impl=attn_impl)
    stacked = shard_stage_params(stack_stage_params(per_stage), mesh,
                                 pipe_axis)
    trained = (stacked, params["embed"], params["dec_w"], params["dec_b"])
    return trained, stage_fn
