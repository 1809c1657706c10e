"""Expert-parallel MoE tests: grouped (G experts per device) capacity
dispatch, BOTH impls (GShard all_to_all exchange and the replicated-psum
path) pinned against shard-aware dense references — loss AND gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.parallel.moe import (
    EXPERT_AXIS,
    _routing,
    dropped_route_fraction,
    expected_dropped,
    expert_load,
    factor_expert_axis,
    load_balance_loss,
    moe_apply,
    moe_reference,
    resolve_moe_impl,
    route_shards,
    set_moe_impl,
    shard_expert_params,
    stack_expert_params,
)

D = 8
N_EXPERTS = 8
N_TOKENS = 64


def _mesh(n_dev=N_EXPERTS):
    return Mesh(np.array(jax.devices()[:n_dev]), (EXPERT_AXIS,))


def _expert_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _setup(seed=0, n_experts=N_EXPERTS):
    ks = jax.random.split(jax.random.PRNGKey(seed), n_experts + 2)
    per_expert = [
        {"w": jax.random.normal(k, (D, D)) / np.sqrt(D), "b": jnp.zeros((D,))}
        for k in ks[:n_experts]
    ]
    router_w = jax.random.normal(ks[-2], (D, n_experts)) / np.sqrt(D)
    x = jax.random.normal(ks[-1], (N_TOKENS, D))
    return router_w, per_expert, x


def _shards(mesh, impl):
    return route_shards(mesh, (), EXPERT_AXIS, N_TOKENS, impl)


def _dense_jax(router_w, stacked, x, capacity, top_k=1, n_shards=1):
    """Pure-JAX single-device replica of the sharded dispatch math (same
    capacity/ordering semantics, per-sub-shard routing) — differentiable,
    for gradient parity against EITHER impl (pass its route_shards)."""
    n = x.shape[0]
    n_experts = router_w.shape[1]
    per = n // n_shards
    out = jnp.zeros_like(x)
    for s in range(n_shards):
        xs = x[s * per:(s + 1) * per]
        idx, gates = _routing(xs @ router_w, top_k)
        for e in range(n_experts):
            mine_k = idx == e
            mine = mine_k.any(-1)
            gate = jnp.sum(gates * mine_k, axis=-1)
            order = jnp.argsort(
                jnp.where(mine, jnp.arange(per), per + jnp.arange(per)))
            slots = order[:capacity]
            valid = mine[slots]
            params_e = jax.tree_util.tree_map(lambda a: a[e], stacked)
            y = _expert_fn(params_e, xs[slots] * valid[:, None])
            out = out.at[s * per + slots].add(
                y * (gate[slots] * valid)[:, None])
    return out


@pytest.mark.parametrize("impl", ["replicated", "alltoall", "alltoall_2d"])
def test_moe_matches_dense_reference(impl):
    router_w, per_expert, x = _setup()
    mesh = _mesh()
    stacked = shard_expert_params(stack_expert_params(per_expert), mesh)
    capacity = N_TOKENS  # ample: nothing dropped on either dispatch
    out = moe_apply(router_w, stacked, x, mesh, _expert_fn, capacity,
                    impl=impl)
    ref = moe_reference(router_w, per_expert, x, _expert_fn, capacity,
                        n_token_shards=_shards(mesh, impl))
    assert jnp.allclose(out, ref, atol=1e-5), float(
        jnp.max(jnp.abs(out - ref)))
    assert expected_dropped(router_w, x, capacity) == 0


@pytest.mark.parametrize("impl", ["replicated", "alltoall"])
def test_capacity_overflow_drops_tokens(impl):
    """Overflow semantics per impl: capacity binds per (expert, sub-shard)
    — the whole replicated token row vs each alltoall source device — and
    the shard-aware reference reproduces either exactly."""
    router_w, per_expert, x = _setup(1)
    mesh = _mesh()
    stacked = shard_expert_params(stack_expert_params(per_expert), mesh)
    n_shards = _shards(mesh, impl)
    # half the mean load per (expert, sub-shard), at least 1: busy experts
    # must overflow whatever the installed jax draws for this seed
    capacity = max(1, N_TOKENS // n_shards // (2 * N_EXPERTS))
    dropped = expected_dropped(router_w, x, capacity, n_shards=n_shards)
    assert dropped > 0
    assert abs(float(dropped_route_fraction(
        router_w, x, capacity, n_shards=n_shards)) - dropped / N_TOKENS) < 1e-6
    out = moe_apply(router_w, stacked, x, mesh, _expert_fn, capacity,
                    impl=impl)
    ref = moe_reference(router_w, per_expert, x, _expert_fn, capacity,
                        n_token_shards=n_shards)
    assert jnp.allclose(out, ref, atol=1e-5)
    # dropped tokens contribute exactly zero
    n_zero_rows = int(jnp.sum(jnp.all(out == 0, axis=-1)))
    assert n_zero_rows >= dropped


@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("top_k", [1, 2])
def test_grouped_alltoall_matches_dense_with_grads(group, top_k):
    """The tentpole parity matrix: grouped all_to_all dispatch
    (n_experts = G × devices) vs the differentiable dense oracle with
    IDENTICAL per-device capacity semantics — loss AND router/expert
    gradients to 1e-5, at a capacity tight enough to force overflow
    drops."""
    n_dev = 4
    mesh = _mesh(n_dev)
    n_experts = group * n_dev
    router_w, per_expert, x = _setup(seed=2 + group, n_experts=n_experts)
    sharded = shard_expert_params(stack_expert_params(per_expert), mesh)
    local = stack_expert_params(per_expert)
    tgt = jax.random.normal(jax.random.PRNGKey(9), (N_TOKENS, D))
    # n_local = 16 tokens/device: cap 3 overflows whenever >3 of a device's
    # tokens pick one expert (guaranteed-ish at G=1: 16 tokens, 4 experts)
    capacity = 3
    n_shards = n_dev  # alltoall routes per device

    def sharded_loss(rw, params):
        out = moe_apply(rw, params, x, mesh, _expert_fn, capacity,
                        top_k=top_k, impl="alltoall")
        return jnp.mean((out - tgt) ** 2), out

    def dense_loss(rw, params):
        out = _dense_jax(rw, params, x, capacity, top_k, n_shards)
        return jnp.mean((out - tgt) ** 2), out

    (ls, out_s), (gr_s, ge_s) = jax.value_and_grad(
        sharded_loss, argnums=(0, 1), has_aux=True)(router_w, sharded)
    (ld, out_d), (gr_d, ge_d) = jax.value_and_grad(
        dense_loss, argnums=(0, 1), has_aux=True)(router_w, local)
    assert abs(float(ls) - float(ld)) < 1e-5
    assert jnp.allclose(out_s, out_d, atol=1e-5)
    assert jnp.allclose(gr_s, gr_d, atol=1e-5), float(
        jnp.max(jnp.abs(gr_s - gr_d)))
    for k in ("w", "b"):
        err = float(jnp.max(jnp.abs(jnp.asarray(ge_s[k]) - ge_d[k])))
        assert err < 1e-5, (k, err)


@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("top_k", [1, 2])
def test_alltoall_2d_matches_flat(group, top_k):
    """ISSUE 14 tentpole parity: the hierarchical 2-phase dispatch vs the
    flat exchange at G ∈ {1, 4} × top-k ∈ {1, 2}, at a capacity tight
    enough to force overflow drops — loss, outputs AND router/expert
    gradients within 1e-5 (the values are bit-identical by construction:
    only the wire schedule differs, pinned exact here)."""
    mesh = _mesh()  # 8 devices → (outer, inner) = (4, 2)
    n_experts = group * N_EXPERTS
    router_w, per_expert, x = _setup(seed=20 + group, n_experts=n_experts)
    stacked = shard_expert_params(stack_expert_params(per_expert), mesh)
    capacity = 2  # 8 tokens/device: busy experts overflow
    assert expected_dropped(router_w, x, capacity, top_k,
                            n_shards=_shards(mesh, "alltoall")) > 0
    tgt = jnp.tanh(jax.random.normal(jax.random.PRNGKey(21), (N_TOKENS, D)))

    def loss_fn(rw, ps, impl):
        out = moe_apply(rw, ps, x, mesh, _expert_fn, capacity, top_k=top_k,
                        impl=impl)
        return jnp.mean((out - tgt) ** 2), out

    (l_f, o_f), g_f = jax.value_and_grad(
        lambda rw, ps: loss_fn(rw, ps, "alltoall"),
        argnums=(0, 1), has_aux=True)(router_w, stacked)
    (l_2, o_2), g_2 = jax.value_and_grad(
        lambda rw, ps: loss_fn(rw, ps, "alltoall_2d"),
        argnums=(0, 1), has_aux=True)(router_w, stacked)
    assert abs(float(l_f) - float(l_2)) <= 1e-5
    assert jnp.array_equal(o_f, o_2)  # same routed values, bitwise
    for a, b in zip(jax.tree_util.tree_leaves(g_f),
                    jax.tree_util.tree_leaves(g_2)):
        assert float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b)))) \
            <= 1e-5


def test_alltoall_2d_rejects_non_factorizable_axis():
    """A prime (or < 4) expert-axis size has no (outer, inner) grid: the
    seam rejects alltoall_2d LOUDLY at the call, through every selection
    layer (factor helper, per-call impl, env override)."""
    for bad in (2, 3, 5, 7):
        with pytest.raises(ValueError, match="not factorizable"):
            factor_expert_axis(bad)
    assert factor_expert_axis(4) == (2, 2)
    assert factor_expert_axis(8) == (4, 2)
    router_w, per_expert, x = _setup(1)
    mesh = _mesh(2)  # 2-device expert axis: prime
    stacked = shard_expert_params(
        stack_expert_params(per_expert[:2]), mesh)
    with pytest.raises(ValueError, match="not factorizable"):
        moe_apply(router_w[:, :2], stacked, x, mesh, _expert_fn, 8,
                  impl="alltoall_2d")


def test_moe_impl_seam_accepts_alltoall_2d(monkeypatch):
    """The precedence chain carries the new impl: env var, setter, and
    per-call all resolve "alltoall_2d"; its routing sub-shard semantics
    match the flat alltoall (route_shards equal), and the dispatched
    output matches the alltoall reference at drop-discriminating
    capacity."""
    router_w, per_expert, x = _setup(1)
    mesh = _mesh()
    stacked = shard_expert_params(stack_expert_params(per_expert), mesh)
    capacity = 4

    def run(**kw):
        return moe_apply(router_w, stacked, x, mesh, _expert_fn, capacity,
                         **kw)

    ref = moe_reference(router_w, per_expert, x, _expert_fn, capacity,
                        n_token_shards=_shards(mesh, "alltoall_2d"))
    assert _shards(mesh, "alltoall_2d") == _shards(mesh, "alltoall")
    # env override resolves the 2D impl
    monkeypatch.setenv("DL4J_TPU_MOE_IMPL", "alltoall_2d")
    assert resolve_moe_impl(N_TOKENS, 8) == "alltoall_2d"
    assert jnp.allclose(run(), ref, atol=1e-5)
    monkeypatch.delenv("DL4J_TPU_MOE_IMPL")
    # setter
    set_moe_impl("alltoall_2d")
    try:
        assert resolve_moe_impl(N_TOKENS, 8) == "alltoall_2d"
        assert jnp.allclose(run(), ref, atol=1e-5)
    finally:
        set_moe_impl(None)
    # per-call
    assert jnp.allclose(run(impl="alltoall_2d"), ref, atol=1e-5)
    # bogus env value still rejected loudly
    monkeypatch.setenv("DL4J_TPU_MOE_IMPL", "alltoall_3d")
    with pytest.raises(ValueError, match="alltoall_3d"):
        resolve_moe_impl(N_TOKENS, 8)
    monkeypatch.delenv("DL4J_TPU_MOE_IMPL")


def test_alltoall_2d_step_retrace_budget(retrace_budget):
    """A warmed jitted SGD step through the 2-phase dispatch holds the
    same 0-compile steady budget as the flat exchange (ISSUE 14
    acceptance: the factorization must not introduce per-step retraces)."""
    router_w, per_expert, x = _setup(7)
    mesh = _mesh()
    params = shard_expert_params(stack_expert_params(per_expert), mesh)
    tgt = jnp.tanh(jax.random.normal(jax.random.PRNGKey(13), (N_TOKENS, D)))
    jax.block_until_ready(
        moe_apply(router_w, params, x, mesh, _expert_fn, 16,
                  impl="alltoall_2d"))  # collective warmup, see test_moe_trains

    @jax.jit
    def step(rw, ps):
        def loss_fn(rw, ps):
            out = moe_apply(rw, ps, x, mesh, _expert_fn, 16, top_k=2,
                            impl="alltoall_2d")
            return jnp.mean((out - tgt) ** 2)

        loss, (gr, ge) = jax.value_and_grad(loss_fn, argnums=(0, 1))(rw, ps)
        return rw - 0.5 * gr, jax.tree_util.tree_map(
            lambda p, g: p - 0.5 * g, ps, ge), loss

    for _ in range(2):  # compile + committed-sharding warmup
        router_w, params, loss = step(router_w, params)
        jax.block_until_ready(loss)
    with retrace_budget(0, label="alltoall_2d moe step steady state"):
        for _ in range(2):
            router_w, params, loss = step(router_w, params)
            jax.block_until_ready(loss)
    assert np.isfinite(float(loss))


def test_grouped_replicated_matches_dense():
    """The generalized replicated path at G=2: per-row capacity semantics
    with a local expert GROUP per device (vmap'd compute, one psum)."""
    n_dev = 4
    mesh = _mesh(n_dev)
    router_w, per_expert, x = _setup(seed=6, n_experts=2 * n_dev)
    stacked = shard_expert_params(stack_expert_params(per_expert), mesh)
    for capacity in (N_TOKENS, 5):
        out = moe_apply(router_w, stacked, x, mesh, _expert_fn, capacity,
                        top_k=2, impl="replicated")
        ref = moe_reference(router_w, per_expert, x, _expert_fn, capacity,
                            top_k=2, n_token_shards=1)
        assert jnp.allclose(out, ref, atol=1e-5), float(
            jnp.max(jnp.abs(out - ref)))


def test_moe_impl_seam_precedence(monkeypatch):
    """per-call impl > set_moe_impl > DL4J_TPU_MOE_IMPL env > auto — the
    same chain as the attention core seam. Observable discriminator: the
    two impls drop DIFFERENT tokens at a tight capacity, so each resolved
    impl is verified against its own reference."""
    router_w, per_expert, x = _setup(1)
    mesh = _mesh()
    stacked = shard_expert_params(stack_expert_params(per_expert), mesh)
    capacity = 4

    def run(**kw):
        return moe_apply(router_w, stacked, x, mesh, _expert_fn, capacity,
                         **kw)

    def ref(impl):
        return moe_reference(router_w, per_expert, x, _expert_fn, capacity,
                             n_token_shards=_shards(mesh, impl))

    # the two semantics genuinely differ at this capacity (else no signal)
    assert not jnp.allclose(ref("alltoall"), ref("replicated"), atol=1e-5)
    # auto (divisible tokens) → alltoall
    assert resolve_moe_impl(N_TOKENS, 8) == "alltoall"
    assert jnp.allclose(run(), ref("alltoall"), atol=1e-5)
    # env var outranks auto
    monkeypatch.setenv("DL4J_TPU_MOE_IMPL", "replicated")
    assert resolve_moe_impl(N_TOKENS, 8) == "replicated"
    assert jnp.allclose(run(), ref("replicated"), atol=1e-5)
    # setter outranks env
    set_moe_impl("alltoall")
    try:
        assert resolve_moe_impl(N_TOKENS, 8) == "alltoall"
        assert jnp.allclose(run(), ref("alltoall"), atol=1e-5)
        # per-call outranks everything
        assert jnp.allclose(run(impl="replicated"), ref("replicated"),
                            atol=1e-5)
    finally:
        set_moe_impl(None)
    monkeypatch.delenv("DL4J_TPU_MOE_IMPL")


def test_moe_validation_errors():
    router_w, per_expert, x = _setup()
    mesh = _mesh()
    stacked = shard_expert_params(stack_expert_params(per_expert), mesh)
    # n_experts not a multiple of the axis size
    with pytest.raises(ValueError, match="multiple"):
        moe_apply(router_w[:, :6], stacked, x, mesh, _expert_fn, 8)
    # forced alltoall on a token count that does not subdivide
    with pytest.raises(ValueError, match="divide"):
        moe_apply(router_w, stacked, x[:60], mesh, _expert_fn, 8,
                  impl="alltoall")
    # auto falls back to replicated on the same shape (60 % 8 != 0)
    out = moe_apply(router_w, stacked, x[:60], mesh, _expert_fn, N_TOKENS)
    ref = moe_reference(router_w, per_expert, x[:60], _expert_fn, N_TOKENS)
    assert jnp.allclose(out, ref, atol=1e-5)


def test_alltoall_step_retrace_budget(retrace_budget):
    """A warmed jitted SGD step through the all_to_all dispatch holds a
    0-compile steady budget — the exchange/scatter shapes are static, so
    per-step retraces would be a regression."""
    router_w, per_expert, x = _setup(7)
    mesh = _mesh()
    params = shard_expert_params(stack_expert_params(per_expert), mesh)
    tgt = jnp.tanh(jax.random.normal(jax.random.PRNGKey(13), (N_TOKENS, D)))
    # collective warmup: see the comment in test_moe_trains
    jax.block_until_ready(
        moe_apply(router_w, params, x, mesh, _expert_fn, 16,
                  impl="alltoall"))

    @jax.jit
    def step(rw, ps):
        def loss_fn(rw, ps):
            out = moe_apply(rw, ps, x, mesh, _expert_fn, 16, top_k=2,
                            impl="alltoall")
            return jnp.mean((out - tgt) ** 2)

        loss, (gr, ge) = jax.value_and_grad(loss_fn, argnums=(0, 1))(rw, ps)
        return rw - 0.5 * gr, jax.tree_util.tree_map(
            lambda p, g: p - 0.5 * g, ps, ge), loss

    # two warm steps: the first compiles; the second compiles ONCE more
    # against the committed shardings the first update's outputs carry
    # (host-placed inputs became device-committed outputs — same warmup
    # the dp×pp parity harness documents in test_composed.py)
    for _ in range(2):
        router_w, params, loss = step(router_w, params)
        jax.block_until_ready(loss)
    with retrace_budget(0, label="alltoall moe step steady state"):
        for _ in range(2):
            router_w, params, loss = step(router_w, params)
            jax.block_until_ready(loss)
    assert np.isfinite(float(loss))


def test_moe_trains():
    """Router + experts train jointly through the sharded dispatch (smoke:
    loss strictly decreases; gradient EXACTNESS is pinned by
    test_moe_gradients_match_dense)."""
    router_w, per_expert, x = _setup(3)
    mesh = _mesh()
    params = shard_expert_params(stack_expert_params(per_expert), mesh)
    tgt = jnp.tanh(jax.random.normal(jax.random.PRNGKey(11), (N_TOKENS, D)))
    capacity = 16

    # Warm the runtime with a forward-only dispatch first: on a single-core
    # host, XLA CPU's 8-thread all-reduce rendezvous can spuriously hit its
    # 40 s termination timeout when the very first collective program in
    # the process is this fused fwd+bwd step (observed deterministic abort
    # in rendezvous.cc; never once any collective has run first). Pure
    # CPU-runtime scheduling quirk — TPU doesn't use CPU collectives.
    jax.block_until_ready(
        moe_apply(router_w, params, x, mesh, _expert_fn, capacity))

    @jax.jit
    def step(rw, ps):
        def loss_fn(rw, ps):
            out = moe_apply(rw, ps, x, mesh, _expert_fn, capacity)
            return jnp.mean((out - tgt) ** 2)

        loss, (gr, ge) = jax.value_and_grad(loss_fn, argnums=(0, 1))(rw, ps)
        rw = rw - 1.0 * gr
        ps = jax.tree_util.tree_map(lambda p, g: p - 1.0 * g, ps, ge)
        return rw, ps, loss

    _, _, first = step(router_w, params)
    for _ in range(60):
        router_w, params, loss = step(router_w, params)
        # serialize dispatch: queuing 60 async multi-device executions on a
        # single-core host can starve one rendezvous participant past XLA
        # CPU's 40 s collective termination timeout (observed flaky abort)
        jax.block_until_ready(loss)
    # top-1 gating scales outputs by ~1/E at init, so MSE to an O(1) target
    # moves slowly; assert a real monotone improvement, not a large one
    assert float(loss) < float(first) * 0.99, (float(first), float(loss))


def test_top2_matches_reference():
    """Top-2 dispatch parity: a token's two experts both contribute, gates
    renormalized — sharded == dense reference, with and without overflow
    (auto resolves the impl; the reference follows its shard semantics)."""
    router_w, per_expert, x = _setup(4)
    mesh = _mesh()
    stacked = shard_expert_params(stack_expert_params(per_expert), mesh)
    for capacity in (N_TOKENS, 5):
        out = moe_apply(router_w, stacked, x, mesh, _expert_fn, capacity,
                        top_k=2)
        ref = moe_reference(router_w, per_expert, x, _expert_fn, capacity,
                            top_k=2, n_token_shards=_shards(mesh, None))
        assert jnp.allclose(out, ref, atol=1e-5), float(
            jnp.max(jnp.abs(out - ref)))
    # with ample capacity every token got BOTH experts: no zero rows and
    # outputs differ from the top-1 dispatch
    out_ample = moe_apply(router_w, stacked, x, mesh, _expert_fn, N_TOKENS,
                          top_k=2)
    out1 = moe_apply(router_w, stacked, x, mesh, _expert_fn, N_TOKENS)
    assert not jnp.allclose(out_ample, out1)
    assert int(jnp.sum(jnp.all(out_ample == 0, axis=-1))) == 0


def test_top2_validation():
    router_w, per_expert, x = _setup(5)
    mesh = _mesh()
    stacked = shard_expert_params(stack_expert_params(per_expert), mesh)
    import pytest

    with pytest.raises(ValueError, match="top_k"):
        moe_apply(router_w, stacked, x, mesh, _expert_fn, 8, top_k=3)


def test_load_balance_loss_uniform_and_collapsed():
    x = jax.random.normal(jax.random.PRNGKey(0), (N_TOKENS, D))
    # zero router → uniform probs and (tie-broken) assignments: loss == 1
    uniform = float(load_balance_loss(jnp.zeros((D, N_EXPERTS)), x))
    assert abs(uniform - 1.0) < 1e-5
    # a router collapsed onto expert 0: f0≈1, P0≈1 → loss ≈ E
    rw = jnp.zeros((D, N_EXPERTS)).at[:, 0].set(5.0)
    x_pos = jnp.abs(x)  # make column-0 logits strictly dominant
    collapsed = float(load_balance_loss(rw, x_pos))
    assert collapsed > 4.0, collapsed
    loads = expert_load(rw, x_pos)
    assert int(loads[0]) == N_TOKENS


def test_aux_loss_rebalances_collapsed_router():
    """Training on the aux loss alone un-collapses a router that starts
    with every token on one expert — the dynamics the Switch loss exists
    for (no-aux top-1 routing collapses; VERDICT r04 weak #6)."""
    key = jax.random.PRNGKey(6)
    # positive features make the +2.0 column-0 weights act like a large
    # constant bias: every token's top-1 is expert 0 at start
    x = jnp.abs(jax.random.normal(key, (256, D)))
    rw = (jax.random.normal(jax.random.PRNGKey(7), (D, N_EXPERTS)) * 0.02
          ).at[:, 0].add(2.0)  # heavily biased toward expert 0
    start_max = int(jnp.max(expert_load(rw, x)))
    assert start_max > 200  # collapsed at start

    grad_fn = jax.jit(jax.grad(load_balance_loss, argnums=0))
    # pure-aux dynamics oscillate (argmax in f jumps between experts), so a
    # single late iterate can land on an oscillation peak; evaluate the
    # trailing-average (Polyak) iterate, which averages the oscillation out
    avg = jnp.zeros_like(rw)
    for i in range(600):
        rw = rw - 0.5 * grad_fn(rw, x)
        if i >= 300:
            avg = avg + rw
    avg = avg / 300.0
    loads = expert_load(avg, x)
    max_share = float(jnp.max(loads)) / 256.0
    # assert the mechanism's guarantees — the loss leaves the collapsed
    # regime (≈E) for near-uniform (≈1) and no expert dominates — rather
    # than exact uniformity, which only task-gradient noise provides
    assert float(load_balance_loss(avg, x)) < 2.0
    assert max_share < 0.7, f"still collapsed: {np.asarray(loads)}"


def test_moe_trains_balanced_with_aux():
    """Joint training (task + 1e-2·aux, top-2) keeps expert load spread
    across the mesh over a short run; the identical run WITHOUT the aux
    term ends more concentrated."""
    router_w, per_expert, x = _setup(8)
    mesh = _mesh()
    params0 = shard_expert_params(stack_expert_params(per_expert), mesh)
    tgt = jnp.tanh(jax.random.normal(jax.random.PRNGKey(12), (N_TOKENS, D)))
    capacity = 16
    jax.block_until_ready(
        moe_apply(router_w, params0, x, mesh, _expert_fn, capacity, top_k=2))

    def train(aux_weight):
        rw, ps = router_w, params0

        @jax.jit
        def step(rw, ps):
            def loss_fn(rw, ps):
                out = moe_apply(rw, ps, x, mesh, _expert_fn, capacity,
                                top_k=2)
                task = jnp.mean((out - tgt) ** 2)
                return task + aux_weight * load_balance_loss(rw, x)

            loss, (gr, ge) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(rw, ps)
            return rw - 1.0 * gr, jax.tree_util.tree_map(
                lambda p, g: p - 1.0 * g, ps, ge), loss

        first = None
        for _ in range(60):
            rw, ps, loss = step(rw, ps)
            jax.block_until_ready(loss)  # see test_moe_trains comment
            first = first if first is not None else float(loss)
        return rw, first, float(loss)

    rw_aux, first_aux, last_aux = train(1e-2)
    rw_noaux, _, _ = train(0.0)
    assert last_aux < first_aux  # still learns the task
    max_aux = float(jnp.max(expert_load(rw_aux, x, top_k=2))) / (2 * N_TOKENS)
    max_noaux = float(jnp.max(expert_load(rw_noaux, x, top_k=2))) / (2 * N_TOKENS)
    assert max_aux < 0.4, f"aux run concentrated: {max_aux}"
    assert max_aux <= max_noaux + 0.05, (max_aux, max_noaux)
