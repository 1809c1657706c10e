"""The routed form of the serving programs' expert layer (ISSUE 30):
``routed_moe`` against ``dense_moe``, the all-experts oracle, for values and
for gradients with respect to the input, the router and every expert leaf;
the chooser ``moe_ffn`` that picks between them from the static shapes of
the call; the prefill, which calls it at a shape that engages the routed
form; and the train step, which does not call it at all."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer_lm as lm
from deeplearning4j_tpu.ops.flash_attention import attention_core

N, D, E, DFF, K = 512, 16, 8, 32, 2  # 128 rows an expert, 4 experts a route


def _layer(spec, dtype, seed=0):
    """(router, experts, x) of one layer under ``spec``; the biases, which
    init leaves at zero, drawn so that a bias gathered from the wrong expert
    shows."""
    key = jax.random.PRNGKey(seed)
    p = lm._init_block(key, D, 2, E, DFF, spec)
    ex = dict(p["experts"])
    for i, name in enumerate(("b1", "b2")):
        if name in ex:
            ex[name] = 0.5 * jax.random.normal(jax.random.fold_in(key, 7 + i),
                                               ex[name].shape)
    x = jax.random.normal(jax.random.fold_in(key, 3), (N, D))
    cast = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(dtype), t)
    return cast(p["router"]), cast(ex), cast(x)


def _close(got, want, dtype):
    """float32: to rounding. bfloat16: the two forms round different partial
    sums, so the error is held against the size of the whole array."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    else:
        scale = np.linalg.norm(want) + 1e-6
        assert np.linalg.norm(got - want) / scale < 3e-2


def _value_and_grads(fn, spec, router, experts, x):
    def loss(router, experts, x):
        return jnp.sum(jnp.sin(fn(router, experts, x, K, spec)
                               .astype(jnp.float32)))

    out = jax.jit(lambda *a: fn(*a, K, spec))(router, experts, x)
    return out, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(router, experts, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("accum_f32", [False, True])
@pytest.mark.parametrize("norm_topk_prob", [True, False])
@pytest.mark.parametrize("ffn,bias", [("relu", True), ("relu", False),
                                      ("swiglu", False)])
def test_routed_equals_dense_in_values_and_gradients(ffn, bias,
                                                     norm_topk_prob,
                                                     accum_f32, dtype):
    spec = lm.BlockSpec(ffn=ffn, bias=bias, norm_topk_prob=norm_topk_prob,
                        accum_f32=accum_f32)
    router, experts, x = _layer(spec, dtype)
    want, want_g = _value_and_grads(lm.dense_moe, spec, router, experts, x)
    got, got_g = _value_and_grads(lm.routed_moe, spec, router, experts, x)
    assert got.dtype == want.dtype and got.shape == want.shape
    _close(got, want, dtype)
    flat_w, tree_w = jax.tree_util.tree_flatten(want_g)
    flat_g, tree_g = jax.tree_util.tree_flatten(got_g)
    assert tree_w == tree_g
    for g, w in zip(flat_g, flat_w):
        assert g.dtype == w.dtype and g.shape == w.shape
        _close(g, w, dtype)


@pytest.mark.parametrize("ffn,bias", [("relu", True), ("swiglu", False)])
def test_an_expert_with_every_row_and_one_with_none(ffn, bias):
    """A constant feature that the router weighs +10 for expert 0 and -10
    for the last: every token routes to the first, none to the last, and
    the groups are (N, the rest over the middle, 0)."""
    spec = lm.BlockSpec(ffn=ffn, bias=bias)
    router, experts, x = _layer(spec, jnp.float32, seed=1)
    x = x.at[:, 0].set(1.0)
    router = router.at[0].set(jnp.zeros(E).at[0].set(10.0).at[-1].set(-10.0))
    idx, _ = lm._routing(x @ router, K)
    counts = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    assert counts[0] == N and counts[-1] == 0 and counts.sum() == N * K
    want, want_g = _value_and_grads(lm.dense_moe, spec, router, experts, x)
    got, got_g = _value_and_grads(lm.routed_moe, spec, router, experts, x)
    _close(got, want, jnp.float32)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        _close(g, w, jnp.float32)
    # the expert nobody chose gets no gradient at all
    for leaf in jax.tree_util.tree_leaves(got_g[1]):
        assert not np.asarray(leaf[-1]).any()


@pytest.mark.parametrize("ffn,bias", [("relu", True), ("swiglu", False)])
@pytest.mark.parametrize("layer", [0, 2])
def test_a_layer_read_in_place_is_that_layer_sliced(ffn, bias, layer):
    """With ``layer``, the experts are every layer's, stacked, and the
    grouped matmul reads layer ``layer``'s out of the whole: values and every
    gradient against ``dense_moe`` on the slice, and no gradient at all in
    the layers that were not read."""
    spec = lm.BlockSpec(ffn=ffn, bias=bias)
    layers = [_layer(spec, jnp.float32, seed=s) for s in range(3)]
    router, _, x = layers[layer]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                     *(ex for _, ex, _ in layers))
    in_place = lambda r, ex, x, k, sp: lm.routed_moe(  # noqa: E731
        r, ex, x, k, sp, jnp.int32(layer))
    sliced = lambda r, ex, x, k, sp: lm.dense_moe(  # noqa: E731
        r, jax.tree_util.tree_map(lambda a: a[layer], ex), x, k, sp)
    want, want_g = _value_and_grads(sliced, spec, router, stacked, x)
    got, got_g = _value_and_grads(in_place, spec, router, stacked, x)
    _close(got, want, jnp.float32)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert g.shape == w.shape
        _close(g, w, jnp.float32)
    for leaf in jax.tree_util.tree_leaves(got_g[1]):
        assert not np.asarray(jnp.delete(leaf, layer, axis=0)).any()


def _lowered(n_rows: int, top_k: int = K) -> str:
    spec = lm.FLAGSHIP_SPEC
    router, experts, x = _layer(spec, jnp.float32)
    x = jnp.zeros((n_rows, D))
    return jax.jit(lambda r, e, x: lm.moe_ffn(r, e, x, top_k)).lower(
        router, experts, x).as_text(debug_info=True)


SCOPES = ("moe_routed_sort", "moe_routed_experts", "moe_routed_combine")


def test_the_chooser_reads_rows_an_expert_off_the_shapes():
    """Dense below ``ROUTED_MIN_ROWS_PER_EXPERT`` rows an expert, routed at
    it: read from the lowered text, which names the routed form's parts."""
    r = lm.ROUTED_MIN_ROWS_PER_EXPERT
    at, below = r * E // K, (r * E - 1) // K
    assert at * K // E == r and below * K // E == r - 1
    for scope in SCOPES:
        assert scope in _lowered(at), scope
        assert scope not in _lowered(below), scope
    # and the parts do not pose as scopes of their own layer
    assert not any(s.startswith("moe_routed") for s in lm.LM_SCOPES)


def test_the_chooser_keeps_all_experts_where_a_route_spares_few():
    """Rows in plenty, but a token's routes take more than one expert in
    ``ROUTED_MIN_EXPERTS_PER_ROUTE``: every expert on every token computes
    at most that many times the required work on dense matmuls, which the
    chip runs faster than the grouped ones (4 of 8 experts a token here)."""
    assert E < lm.ROUTED_MIN_EXPERTS_PER_ROUTE * 4
    text = _lowered(4096, top_k=4)
    assert not any(scope in text for scope in SCOPES)
    assert lm._routes(4096, 2, 8) and not lm._routes(4096, 2, 7)


def test_the_chooser_is_the_form_it_picks():
    spec = lm.FLAGSHIP_SPEC
    router, experts, x = _layer(spec, jnp.float32)
    few = x[:8]
    assert np.array_equal(
        jax.jit(lambda *a: lm.moe_ffn(*a, K))(router, experts, few),
        jax.jit(lambda *a: lm.dense_moe(*a, K))(router, experts, few))
    assert np.array_equal(
        jax.jit(lambda *a: lm.moe_ffn(*a, K))(router, experts, x),
        jax.jit(lambda *a: lm.routed_moe(*a, K))(router, experts, x))


V, H, L, T = 61, 2, 2, 512


@pytest.fixture(scope="module")
def params():
    return lm.init_lm_params(jax.random.PRNGKey(0), V, D, H, E, DFF,
                             n_layers=L)


def _tokens(seed=5):
    return jnp.asarray(np.random.RandomState(seed).randint(0, V, (1, T)),
                       jnp.int32)


def test_prefill_stays_bit_identical_to_the_forward_where_it_routes(params):
    """``lm_prefill`` is the training forward plus K/V at a shape that
    engages the routed form too (512 rows over 8 experts, 2 a token), where
    the prefill reads the stacked experts in place by layer index and the
    forward takes the scan's slices: both call the one chooser."""
    assert lm._routes(T, K, E)
    toks = _tokens()
    core = lambda q, k, v: attention_core(q, k, v, causal=True,  # noqa: E731
                                          impl="dense")
    moe = lambda rw, ex, x: lm.moe_ffn(rw, ex, x, K)  # noqa: E731
    want = jax.jit(lambda p, t: lm.lm_forward(p, t, H, core, moe)[0])(
        params, toks)
    text = jax.jit(lambda p, t: lm.lm_prefill(p, t, H, top_k=K,
                                              attn_impl="dense")).lower(
        params, toks).as_text(debug_info=True)
    assert "lm_moe/moe_routed_experts" in text
    got, _, _ = jax.jit(lambda p, t: lm.lm_prefill(
        p, t, H, top_k=K, attn_impl="dense"))(params, toks)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # and the routed forward is the all-experts forward to rounding
    dense = lambda rw, ex, x: lm.dense_moe(rw, ex, x, K)  # noqa: E731
    oracle = jax.jit(lambda p, t: lm.lm_forward(p, t, H, core, dense)[0])(
        params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               rtol=2e-4, atol=2e-5)


def test_a_loss_through_the_chooser_matches_the_all_experts_loss(params):
    """``lm_loss`` with ``moe_ffn`` handed in, at a shape that engages: loss
    and every gradient leaf against the same loss with ``dense_moe``. No
    training call site hands it in (the next test); the routed form stays
    differentiable for the one that will."""
    toks = _tokens(6)
    tgts = jnp.roll(toks, -1, axis=1)
    core = lambda q, k, v: attention_core(q, k, v, causal=True,  # noqa: E731
                                          impl="dense")
    loss = lambda moe: lambda p, t, y: lm.lm_loss(  # noqa: E731
        p, t, y, H, core, lambda rw, ex, x: moe(rw, ex, x, K))
    assert "moe_routed_experts" in jax.jit(jax.grad(loss(lm.moe_ffn))).lower(
        params, toks, tgts).as_text(debug_info=True)
    got, got_g = jax.jit(jax.value_and_grad(loss(lm.moe_ffn)))(
        params, toks, tgts)
    want, want_g = jax.jit(jax.value_and_grad(loss(lm.dense_moe)))(
        params, toks, tgts)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=2e-6)


def _train_programs():
    """The one-chip training programs at the train cell's experts, routes a
    token and rows (64, 8 and one sequence of 4,096: 512 rows an expert, a
    shape the chooser routes) with the widths cut small: lowered, not run."""
    e, k, t = 64, 8, 4096
    assert lm._routes(t, k, e)
    shapes = jax.eval_shape(lambda key: lm.init_lm_params(
        key, V, D, H, e, DFF, n_layers=L), jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((1, t), jnp.int32)
    step = lm.make_single_device_train_step(H, top_k=k, tuned=False,
                                            runprof=False)
    yield "train step", getattr(step, "jitted", step).lower(shapes, tok, tok)
    yield "loss", jax.jit(jax.grad(lm.dense_loss_fn(H, top_k=k))).lower(
        shapes, tok, tok)
    yield "draft distillation", jax.jit(jax.grad(
        lambda draft, teacher, t: lm.draft_distill_loss(teacher, H, k)(
            draft, t))).lower(shapes, shapes, tok)


def test_no_training_program_reaches_the_routed_form():
    """The train step, its loss and the draft's distillation keep
    ``dense_moe`` at their own call sites, at shapes the chooser would route:
    the routed train step's set-up was refused (ledger, PR 29), and the
    train cells' programs are to lower to the text they had."""
    for name, lowered in _train_programs():
        text = lowered.as_text(debug_info=True)
        assert "moe_routed" not in text and "tpu_custom_call" not in text, name
