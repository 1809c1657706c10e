"""Expert parallelism: grouped multi-expert capacity MoE with two dispatches.

The reference has no MoE (SURVEY.md §2.5); this completes the framework's
parallelism axes (dp/tp/sp/pp/ep). Experts live on the "expert" mesh axis in
GROUPS: ``n_experts = G × n_devices`` with G ≥ 1 — expert e's parameters are
rows [e] of the stacked (E, ...) param leaves, device d owns the contiguous
local group [d·G, (d+1)·G), and expert compute is a batched ``vmap`` over the
local group (the Switch-Transformer scaling move: more experts than chips).

Three dispatch implementations behind one seam (``moe_apply(impl=...)``):

- ``"alltoall"`` — the GShard shape (arXiv:2006.16668; the portable
  collective-redistribution pattern of Zhuang et al., arXiv:2112.01075).
  Tokens stay sharded over the token axes AND the expert axis end to end:
  each device routes only its own n_local tokens, builds a per-expert
  capacity buffer (position-in-expert computed by the cumsum-of-one-hot
  sort-free ranking), exchanges the (n_dev, G, C, d) buffer via
  ``lax.all_to_all``, computes its local experts on the received slabs, and
  returns results by the inverse all_to_all. Per-device exchange volume is
  O(E·C·d) — proportional to how many tokens the experts actually accept —
  and router FLOPs are O(n_local·E).
- ``"alltoall_2d"`` (ISSUE 14) — the hierarchical factorization of the
  flat exchange per arXiv:2112.01075: the p-device expert axis is split
  into a ``(outer, inner)`` grid (``factor_expert_axis`` — balanced, and
  LOUDLY rejected when p has no nontrivial factorization) and each flat
  all_to_all becomes two grouped phases, intra-group over the ``inner``
  consecutive devices then inter-group over the ``outer`` stride-``inner``
  peers (``lax.all_to_all(axis_index_groups=...)``). The routed VALUES are
  bit-identical to the flat dispatch — only the wire schedule changes.

  Wire-byte model (ring convention, B = E·C·d·itemsize the per-device
  exchange buffer; checked against the xprofile HLO inventory in
  tests/test_xprofile.py):

      flat          (p−1)/p · B     in p−1 messages of B/p
      2d intra      (i−1)/i · B     in i−1 messages of B/i   (fast links)
      2d inter      (o−1)/o · B     in o−1 messages of B/o   (slow links)

  Per HLO collective the factorized ops are strictly smaller — group size
  i (resp. o) < p and per-op wire bytes (i−1)/i·B < (p−1)/p·B. The
  cross-group (slow-link) traffic is byte-identical to the flat op's
  ((p−i)/p·B = (o−1)/o·B) but aggregated into i× fewer, i×-larger
  messages — the multi-pod win: intra-pod ICI absorbs an extra
  (i−1)/i·B so the DCN hop count drops from p−i to o−1 per device.
- ``"replicated"`` — the historical path: tokens replicated along the
  expert axis, every device runs the router over its whole token row, each
  device gathers the first C tokens routed to each of its experts, and a
  dense ``psum`` over the expert axis combines the (n_row, d) output — an
  allreduce whose O(n_row·d) cost is independent of expert occupancy. Kept
  selectable so the bench can A/B the two and as the fallback when the
  token count does not subdivide over the expert axis.

Selection precedence (mirrors ops/flash_attention's ``attn_impl`` chain):
per-call ``impl=`` > ``set_moe_impl`` > the ``DL4J_TPU_MOE_IMPL`` env var >
auto (alltoall whenever the token dim divides over token_axes × the expert
axis, else replicated — ``alltoall_2d`` is always an explicit opt-in, the
auto gate never guesses a topology).

Capacity math: capacity C bounds tokens PER (expert, token-sub-shard);
overflow routes are dropped (outputs exactly zero — callers add their own
residual). The sub-shard is the unit that routes independently: for
``"replicated"`` it is one token ROW (prod(token_axes) shards), for
``"alltoall"`` one device (prod(token_axes) × n_dev shards) — so the same
numeric C admits n_dev× more global routes on the alltoall path, and with
C ≥ n_local the alltoall dispatch can NEVER drop (each token contributes at
most one route per expert). ``route_shards`` reports the resolved sub-shard
count; ``moe_reference`` reproduces either semantics exactly for tests.

Training quality: without pressure toward uniform routing a trained router
collapses onto one expert; ``load_balance_loss`` is the Switch-Transformer
auxiliary (E · Σ_e f_e·P_e, f = dispatch fraction, P = mean router prob —
minimized at uniform routing, where it equals 1). Add it to the task loss
with a small weight (~1e-2). ``router_load_fraction`` (per-expert load,
sums to 1/step) and ``dropped_route_fraction`` (capacity overflow share)
are the in-graph telemetry twins threaded through the composed train steps.

Everything is differentiable (gather/scatter/psum/all_to_all transpose
cleanly), so ``jax.grad`` trains router and experts together; parity and
gradient tests pin BOTH dispatches against dense references
(tests/test_moe.py, tests/test_composed.py).
"""

from __future__ import annotations

import math
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array

EXPERT_AXIS = "expert"

# dispatch-impl seam (same precedence shape as ops/flash_attention):
# per-call impl= > set_moe_impl > DL4J_TPU_MOE_IMPL env > auto
MOE_IMPL_ENV = "DL4J_TPU_MOE_IMPL"
_IMPLS = ("alltoall", "alltoall_2d", "replicated")
_impl_override: Optional[str] = None


def set_moe_impl(impl: Optional[str]) -> None:
    """Force the MoE dispatch: "alltoall" (capacity-buffer exchange,
    tokens sharded over the expert axis too), "alltoall_2d" (the same
    exchange factorized into intra+inter grouped phases — module
    docstring), "replicated" (replicated tokens + dense psum combine), or
    None for auto."""
    if impl not in (None,) + _IMPLS:
        raise ValueError(f"unknown moe impl {impl!r}; "
                         "options: alltoall, alltoall_2d, replicated, None")
    global _impl_override
    _impl_override = impl


def get_moe_impl() -> Optional[str]:
    """The effective global override: set_moe_impl's value, else the
    ``DL4J_TPU_MOE_IMPL`` environment variable, else None (auto)."""
    if _impl_override is not None:
        return _impl_override
    env = os.environ.get(MOE_IMPL_ENV)
    if env:
        if env not in _IMPLS:
            raise ValueError(
                f"{MOE_IMPL_ENV}={env!r}; options: " + ", ".join(_IMPLS))
        return env
    return None


def resolve_moe_impl(n_tokens: Optional[int] = None,
                     n_shards_alltoall: Optional[int] = None,
                     impl: Optional[str] = None) -> Optional[str]:
    """Collapse the precedence chain to the dispatch that will run:
    per-call > programmatic override > env var > (given the static token
    count and the alltoall shard count) the auto shape gate — alltoall
    whenever the token dim subdivides evenly, replicated otherwise."""
    impl = impl or get_moe_impl()
    if impl is None and n_tokens is not None and n_shards_alltoall:
        impl = ("alltoall" if n_tokens % n_shards_alltoall == 0
                else "replicated")
    return impl


def route_shards(mesh: Mesh, token_axes: tuple = (), axis: str = EXPERT_AXIS,
                 n_tokens: Optional[int] = None,
                 impl: Optional[str] = None) -> int:
    """Number of token sub-shards that route independently (the unit
    capacity applies per — see module docstring) under the RESOLVED impl.
    Host-side static metadata for references and telemetry."""
    rows = math.prod(mesh.shape[a] for a in token_axes) if token_axes else 1
    n_dev = mesh.shape[axis]
    eff = resolve_moe_impl(n_tokens, rows * n_dev, impl)
    # alltoall_2d routes per device exactly like the flat exchange — only
    # the wire schedule differs, never the capacity semantics
    return rows * n_dev if (eff or "").startswith("alltoall") else rows


def factor_expert_axis(n_dev: int) -> tuple:
    """The balanced ``(outer, inner)`` grid the 2D dispatch factorizes a
    p-device expert axis into: ``inner`` is the largest divisor of p with
    inner² ≤ p (so inner ≤ outer and outer·inner = p). A prime (or < 4)
    axis size has no nontrivial grid and raises LOUDLY — the caller must
    fall back to the flat ``"alltoall"`` dispatch, never a silently
    degenerate 1×p factorization."""
    n_dev = int(n_dev)
    inner = 0
    for d in range(2, int(math.isqrt(n_dev)) + 1):
        if n_dev % d == 0:
            inner = d
    if n_dev < 4 or inner == 0:
        raise ValueError(
            f"expert axis size {n_dev} is not factorizable into an "
            "(outer, inner) grid with both factors >= 2 — alltoall_2d "
            "needs a composite axis size; use impl='alltoall' instead")
    return n_dev // inner, inner


def _a2a_hierarchical(x, axis_name: str, outer: int, inner: int,
                      scope: str):
    """Two-phase factorized all_to_all of a per-device ``(n_dev, ...)``
    buffer (``x[dst]`` destined for device ``dst``; returns ``y[src]``
    received from device ``src``) — bit-compatible with the flat tiled
    ``lax.all_to_all(split_axis=0, concat_axis=0)``.

    Device d sits at grid position (o, i) = (d // inner, d % inner).
    Phase 1 exchanges within each run of ``inner`` consecutive devices
    (moving every chunk to its destination's inner coordinate); phase 2
    exchanges across the ``outer`` stride-``inner`` peers (delivering to
    the destination's outer coordinate). See the module docstring for the
    per-phase wire model."""
    n_dev = outer * inner
    intra = [[o * inner + i for i in range(inner)] for o in range(outer)]
    inter = [[o * inner + i for o in range(outer)] for i in range(inner)]
    s = x.reshape((outer, inner) + x.shape[1:])
    with jax.named_scope(f"{scope}_intra"):
        s = jax.lax.all_to_all(s, axis_name, split_axis=1, concat_axis=1,
                               tiled=True, axis_index_groups=intra)
    with jax.named_scope(f"{scope}_inter"):
        s = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                               tiled=True, axis_index_groups=inter)
    return s.reshape((n_dev,) + x.shape[1:])


def _routing(logits, top_k: int, renormalize: bool = True):
    """(N, E) logits → (idx (N,k), gates (N,k)). Gates are softmax probs of
    the chosen experts, renormalized to sum to 1 when k > 1 (GShard) unless
    the model's spec says its gates are not (``norm_topk_prob`` false). They
    come in the logits' type: a spec that wants them in float32 hands
    float32 logits."""
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(logits, top_k)  # (N, k)
    g = jnp.take_along_axis(probs, idx, axis=1)  # (N, k)
    if top_k > 1 and renormalize:
        g = g / jnp.maximum(g.sum(-1, keepdims=True), 1e-9)
    return idx, g


# ------------------------------------------------------ replicated dispatch ----

def _dispatch_replicated(local_params, router_w, x, capacity: int,
                         axis_name: str, expert_fn: Callable, top_k: int,
                         group: int):
    """Per-device body under shard_map. x: (n_row, d) tokens replicated
    along the expert axis; local_params: this device's (G, ...) expert
    group. Combine is a dense psum over the expert axis."""
    my = jax.lax.axis_index(axis_name)
    n, d = x.shape

    logits = x @ router_w  # (n, E) — router replicated, computed locally
    idx, gates = _routing(logits, top_k)
    eids = my * group + jnp.arange(group)  # this device's expert ids

    def slots_of(e):
        mine_k = idx == e  # (n, k): which of the token's choices is expert e
        mine = mine_k.any(-1)  # a token picks each expert at most once
        gate_here = jnp.sum(gates * mine_k, axis=-1)  # (n,)
        # positions of the first `capacity` tokens routed to e: rank tokens
        # by (not-mine, position) so mine-in-order come first, then slice C
        order = jnp.argsort(jnp.where(mine, jnp.arange(n), n + jnp.arange(n)))
        slots = order[:capacity]  # (C,) token index per slot
        return slots, mine[slots], gate_here

    slots, valid, gate_here = jax.vmap(slots_of)(eids)  # (G,C),(G,C),(G,n)
    tokens = x[slots] * valid[..., None]  # (G, C, d)
    y = jax.vmap(expert_fn)(local_params, tokens)  # the O(G·C) expert compute
    g = jnp.take_along_axis(gate_here, slots, axis=1) * valid  # (G, C)
    y = y * g[..., None]

    out = jnp.zeros((n, d), x.dtype).at[slots.reshape(-1)].add(
        y.reshape(-1, d))
    # combine expert shards; a top-2 token sums its two experts' outputs
    return jax.lax.psum(out, axis_name)


# -------------------------------------------------------- alltoall dispatch ----

def _dispatch_alltoall(local_params, router_w, x, capacity: int,
                       axis_name: str, expert_fn: Callable, top_k: int,
                       group: int, n_dev: int, split: Optional[tuple] = None):
    """Per-device body under shard_map. x: (n_local, d) — this device's OWN
    token slice (sharded over token_axes AND the expert axis); experts
    exchange capacity buffers instead of psumming dense outputs.

    Route ranking is the GShard cumsum-of-one-hot: rank r of a (token,
    choice) route within its expert = how many earlier routes chose the
    same expert; routes with r ≥ C are dropped (gate zeroed, output zero).

    ``split=(outer, inner)`` swaps each flat exchange for the two-phase
    hierarchical factorization (``_a2a_hierarchical``) — identical values,
    grouped wire schedule (the "alltoall_2d" impl).
    """
    n, d = x.shape
    n_experts = n_dev * group

    logits = x @ router_w  # (n_local, E): the dp-factor router-FLOP saving
    idx, gates = _routing(logits, top_k)

    flat_e = idx.reshape(-1)  # (n·k,) expert id per route, position order
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    rank = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1  # (n·k,)
    keep = rank < capacity
    # slot in the (E, C) dispatch buffer; dropped routes park in a dump row
    slot = jnp.where(keep, flat_e * capacity + rank, n_experts * capacity)
    tok_ids = jnp.repeat(jnp.arange(n), top_k)  # token index per route

    buf = jnp.zeros((n_experts * capacity + 1, d), x.dtype)
    buf = buf.at[slot].add(x[tok_ids])  # kept slots are unique: add == set
    send = buf[: n_experts * capacity].reshape(n_dev, group, capacity, d)
    with jax.named_scope("moe_all2all_dispatch"):
        if split is not None:
            recv = _a2a_hierarchical(send, axis_name, split[0], split[1],
                                     "moe_all2all_dispatch")
        else:
            recv = jax.lax.all_to_all(send, axis_name, split_axis=0,
                                      concat_axis=0, tiled=True)
    # recv[s, g]: source device s's capacity slab for my local expert g
    toks = recv.transpose(1, 0, 2, 3).reshape(group, n_dev * capacity, d)
    y = jax.vmap(expert_fn)(local_params, toks)  # O(G·n_dev·C) compute
    y = y.reshape(group, n_dev, capacity, d).transpose(1, 0, 2, 3)
    with jax.named_scope("moe_all2all_return"):
        if split is not None:
            back = _a2a_hierarchical(y, axis_name, split[0], split[1],
                                     "moe_all2all_return")
        else:
            back = jax.lax.all_to_all(y, axis_name, split_axis=0,
                                      concat_axis=0, tiled=True)
    # back reshaped (E·C, d) lines up with `slot`: back[dst, g, r] is the
    # output of my route parked at slot (dst·G + g)·C + r
    ybuf = jnp.concatenate([back.reshape(n_experts * capacity, d),
                            jnp.zeros((1, d), x.dtype)])  # dump row → zeros
    route_y = ybuf[slot]  # (n·k, d); dropped routes gather the zero row
    w = gates.reshape(-1) * keep  # gate, zeroed for dropped routes
    return jnp.zeros((n, d), x.dtype).at[tok_ids].add(route_y * w[:, None])


def moe_apply(router_w: Array, expert_params, x: Array, mesh: Mesh,
              expert_fn: Callable, capacity: int,
              axis: str = EXPERT_AXIS, top_k: int = 1,
              token_axes: tuple = (), impl: Optional[str] = None) -> Array:
    """Top-k (k ∈ {1, 2}) MoE over grouped experts sharded on ``axis``.

    router_w: (d, E) replicated; expert_params: pytree with a leading
    expert axis of size E = G · mesh.shape[axis] (sharded onto ``axis`` —
    each device holds its contiguous local group of G experts); x: (N, d).
    Returns (N, d); tokens beyond an expert's per-sub-shard capacity
    contribute zeros (count with ``expected_dropped`` / the in-graph
    ``dropped_route_fraction``). For training, add
    ``load_balance_loss(router_w, x)`` to the task loss (weight ~1e-2) or
    the router collapses experts.

    ``token_axes`` composes dp/sp×ep on a multi-axis mesh: the token dim N
    is sharded over those mesh axes, so each token shard routes its own
    tokens to the full expert set. ``impl`` selects the dispatch for THIS
    call — "alltoall", "alltoall_2d" (the hierarchical two-phase
    factorization; expert-axis size must be composite), or "replicated" —
    else the set_moe_impl/env/auto chain (see module docstring for the
    paths' comm shapes and capacity semantics). Expert-param gradients
    are psummed over the token axes automatically by shard_map's
    transpose.
    """
    if top_k not in (1, 2):
        raise ValueError(f"top_k must be 1 or 2, got {top_k}")
    if impl is not None and impl not in _IMPLS:
        raise ValueError(f"unknown moe impl {impl!r}; "
                         "options: " + ", ".join(_IMPLS))
    n_dev = mesh.shape[axis]
    n_experts = router_w.shape[1]
    if n_experts % n_dev:
        raise ValueError(
            f"router_w has {n_experts} experts but mesh axis {axis!r} has "
            f"{n_dev} devices — grouped dispatch needs n_experts to be a "
            "multiple of the axis size (G experts per device)")
    group = n_experts // n_dev
    if top_k > n_experts:
        raise ValueError(f"top_k={top_k} > {n_experts} experts")
    for leaf in jax.tree_util.tree_leaves(expert_params):
        if leaf.shape[0] != n_experts:
            raise ValueError(
                f"expert param leading dim {leaf.shape[0]} != n_experts "
                f"{n_experts} (= {group} × mesh axis size {n_dev})")

    n_tokens = x.shape[0]
    rows = math.prod(mesh.shape[a] for a in token_axes) if token_axes else 1
    eff = resolve_moe_impl(n_tokens, rows * n_dev, impl)
    param_spec = jax.tree_util.tree_map(lambda _: P(axis), expert_params)

    if eff in ("alltoall", "alltoall_2d"):
        if n_tokens % (rows * n_dev):
            raise ValueError(
                f"{eff} dispatch needs the token dim ({n_tokens}) to "
                f"divide over token_axes × {axis!r} ({rows}×{n_dev}); pass "
                "impl='replicated' or pad the token stream")
        # alltoall_2d: resolve the (outer, inner) grid HERE — a prime
        # axis size fails the call loudly, not inside the traced body
        split = factor_expert_axis(n_dev) if eff == "alltoall_2d" else None
        tok_spec = P(tuple(token_axes) + (axis,))

        def body(params, rw, xs):
            return _dispatch_alltoall(params, rw, xs, capacity, axis,
                                      expert_fn, top_k, group, n_dev,
                                      split=split)
    else:
        tok_spec = P(tuple(token_axes) if token_axes else None)

        def body(params, rw, xs):
            return _dispatch_replicated(params, rw, xs, capacity, axis,
                                        expert_fn, top_k, group)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_spec, P(), tok_spec), out_specs=tok_spec,
        check_vma=False,
    )(expert_params, router_w, x)


def load_balance_loss(router_w: Array, x: Array) -> Array:
    """Switch-Transformer auxiliary load-balancing loss: E · Σ_e f_e · P_e
    with f_e the fraction of tokens whose TOP-1 choice is e (stop-gradient
    through the argmax, as in the paper) and P_e the mean router
    probability. Equals 1 at perfectly uniform routing; add to the task
    loss with a small weight (1e-2 is the standard setting)."""
    logits = x @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    n_experts = router_w.shape[1]
    f = jnp.mean(jax.nn.one_hot(jnp.argmax(logits, -1), n_experts), axis=0)
    p_mean = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * p_mean)


def router_load_fraction(router_w: Array, x: Array, top_k: int = 1) -> Array:
    """(E,) fraction of (token, choice) routes landing on each expert —
    sums to EXACTLY 1 per step (each of the N·k routes counts once). The
    in-graph telemetry twin of ``expert_load``: differentiation-free
    (one-hot of the routing argtop), cheap enough to ride every train step,
    and the balance gauge the step log / Prometheus export surface as
    ``router_load{expert=...}``."""
    idx, _ = _routing(x @ router_w, top_k)
    n_experts = router_w.shape[1]
    onehot = jax.nn.one_hot(idx, n_experts)  # (N, k, E)
    return jnp.mean(onehot, axis=(0, 1))


def dropped_route_fraction(router_w: Array, x: Array, capacity: int,
                           top_k: int = 1, n_shards: int = 1) -> Array:
    """In-graph fraction of (token, choice) routes that overflow the
    per-(expert, sub-shard) capacity — the drop gauge threaded through the
    composed train steps' metrics (``moe_dropped_frac``). ``n_shards`` is
    the independent-routing sub-shard count of the ACTIVE dispatch (see
    ``route_shards``); x splits into that many contiguous chunks, matching
    shard_map's layout. Differentiation-free."""
    n = x.shape[0]
    idx, _ = _routing(x @ router_w, top_k)  # (n, k)
    n_experts = router_w.shape[1]
    per = n // n_shards
    onehot = jax.nn.one_hot(idx, n_experts)  # (n, k, E)
    counts = jnp.sum(onehot.reshape(n_shards, per, top_k, n_experts),
                     axis=(1, 2))  # (n_shards, E)
    dropped = jnp.sum(jnp.maximum(counts - capacity, 0.0))
    return dropped / (n * top_k)


def expert_load(router_w: Array, x: Array, top_k: int = 1) -> Array:
    """(E,) count of tokens routed to each expert (any of their k choices)
    — the balance diagnostic used by tests and capacity tuning."""
    idx, _ = _routing(x @ router_w, top_k)
    n_experts = router_w.shape[1]
    return jnp.bincount(idx.reshape(-1), length=n_experts)


def expected_dropped(router_w: Array, x: Array, capacity: int,
                     top_k: int = 1, n_shards: int = 1) -> int:
    """How many (token, expert) routes overflow an expert's capacity, under
    ``n_shards`` independent routing sub-shards (see module docstring;
    1 = the replicated path on an unsharded token stream)."""
    n = x.shape[0]
    per = n // n_shards
    total = 0
    for s in range(n_shards):
        counts = expert_load(router_w, x[s * per:(s + 1) * per], top_k)
        total += int(jnp.sum(jnp.maximum(counts - capacity, 0)))
    return total


def moe_reference(router_w: Array, expert_params_list, x: Array,
                  expert_fn: Callable, capacity: int,
                  top_k: int = 1, n_token_shards: int = 1) -> Array:
    """Dense single-device reference with IDENTICAL routing + capacity
    semantics (for tests). ``n_token_shards`` replays the sharded layout:
    x splits into that many contiguous chunks, each routing independently
    with its own per-expert capacity — pass ``route_shards(...)`` of the
    dispatch under test (replicated: the token rows; alltoall: rows × the
    expert-axis size)."""
    import numpy as np

    n = x.shape[0]
    per = n // n_token_shards
    out = np.zeros(np.asarray(x).shape, np.float32)
    for s in range(n_token_shards):
        xs = np.asarray(x)[s * per:(s + 1) * per]
        logits = xs @ np.asarray(router_w)
        idx, gates = _routing(jnp.asarray(logits), top_k)
        idx, gates = np.asarray(idx), np.asarray(gates)
        for e, params in enumerate(expert_params_list):
            routed_here = (idx == e)  # (per, k)
            tok = np.nonzero(routed_here.any(-1))[0][:capacity]
            if tok.size == 0:
                continue
            y = np.asarray(expert_fn(params, jnp.asarray(xs[tok])))
            g = (gates[tok] * routed_here[tok]).sum(-1)
            out[s * per + tok] += y * g[:, None]
    return jnp.asarray(out)


def stack_expert_params(per_expert: list):
    """[{k: array}, ...] → {k: (E, ...) array} for moe_apply."""
    from deeplearning4j_tpu.parallel.sharding import stack_along_leading_axis

    return stack_along_leading_axis(per_expert)


def shard_expert_params(stacked, mesh: Mesh, axis: str = EXPERT_AXIS):
    """Place stacked expert params with the expert axis on ``axis`` — the
    (E, ...) leading dim shards into contiguous G-expert groups per
    device (E must be a multiple of the axis size)."""
    from deeplearning4j_tpu.parallel.sharding import shard_leading_axis

    return shard_leading_axis(stacked, mesh, axis)
