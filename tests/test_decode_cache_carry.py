"""ISSUE 26: the KV cache rides through the layer loop of decode, verify and
chunked prefill as the loop's carry and is written in place. The form it
replaced — the cache's layer axis scanned as an INPUT and stacked back as an
OUTPUT — lives on here as the oracle: same block math, so logits and both
cache leaves must come out bit for bit. The second half pins what "in
place" means to a caller: nothing outside the written rows changes, the
donated cache is consumed, and new positions compile nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer_lm as lm

V, D, H, E, DFF, L, TOP_K = 61, 32, 4, 4, 64, 3, 2
SLOTS, MAXLEN = 5, 32
CHUNK_SLOT = 2                                    # a slot in the middle
WIDTH = {"decode": 1, "verify": 3, "chunk": 8}
PROGRAMS = sorted(WIDTH)


# ------------------------------------------------------------- the oracle ----

def _oracle_block(layer_params, h, ck, cv, positions):
    """The decode block over ONE layer's slab ck/cv (S, H, T_max, Dh): the
    program as it stood before ISSUE 26."""
    hn = lm._norm(layer_params, "ln", h, lm.FLAGSHIP_SPEC)
    q = lm._split_heads(hn @ layer_params["wq"], H)
    k_new = lm._split_heads(hn @ layer_params["wk"], H)
    v_new = lm._split_heads(hn @ layer_params["wv"], H)
    write = jax.vmap(
        lambda c, kn, p: jax.lax.dynamic_update_slice_in_dim(
            c, kn.astype(c.dtype), p, axis=1))
    ck = write(ck, k_new, positions)
    cv = write(cv, v_new, positions)
    scores = jnp.einsum("shqd,shkd->shqk", q, ck) / jnp.sqrt(
        q.shape[-1] * 1.0)
    pos_q = positions[:, None] + jnp.arange(h.shape[1])[None, :]
    mask = (jnp.arange(ck.shape[2])[None, None, None, :]
            <= pos_q[:, None, :, None])
    scores = jnp.where(mask, scores, -1e30)
    o = jnp.einsum("shqk,shkd->shqd", jax.nn.softmax(scores, -1), cv)
    h = h + (lm._merge_heads(o) @ layer_params["wo"]).astype(h.dtype)
    h2 = lm._norm(layer_params, "ln2", h, lm.FLAGSHIP_SPEC)
    flat = h2.reshape(-1, h2.shape[-1])
    out = lm.moe_ffn(layer_params["router"], layer_params["experts"], flat,
                     TOP_K)
    return h + out.reshape(h.shape).astype(h.dtype), ck, cv


def _oracle_layers(params, cache, h, positions, slot=None):
    """The scan-as-input layer loop: slabs in as ``xs``, slabs out as
    stacked ``ys``; with ``slot`` the block sees that slot's page alone."""
    def step(h, xs):
        layer_params, ck, cv = xs
        if slot is None:
            h, ck, cv = _oracle_block(layer_params, h, ck, cv, positions)
        else:
            ck_s = jax.lax.dynamic_index_in_dim(ck, slot, 0, keepdims=True)
            cv_s = jax.lax.dynamic_index_in_dim(cv, slot, 0, keepdims=True)
            h, ck_s, cv_s = _oracle_block(layer_params, h, ck_s, cv_s,
                                          positions)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, ck_s, slot, axis=0)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, cv_s, slot, axis=0)
        return h, (ck, cv)

    h, (cks, cvs) = jax.lax.scan(
        step, h, (params["blocks"], cache["k"], cache["v"]))
    return {"k": cks, "v": cvs}, h @ params["dec_w"] + params["dec_b"]


@jax.jit
def _oracle(params, cache, tokens, positions, slot=None):
    """(cache, logits (S, W, V)) of tokens (S, W) by the old form."""
    return _oracle_layers(params, cache, params["embed"][tokens], positions,
                          slot)


# --------------------------------------------------------------- fixtures ----

def _params(dtype):
    p = lm.init_lm_params(jax.random.PRNGKey(0), V, D, H, E, DFF, n_layers=L)
    return jax.tree_util.tree_map(lambda w: w.astype(dtype), p)


def _stale_cache(dtype):
    """A cache full of stale rows (no zeros to hide behind): what an engine
    holds after slots have been retired and readmitted."""
    shape = jax.eval_shape(
        lambda: lm.init_kv_cache(L, SLOTS, H, D // H, MAXLEN, dtype))
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    return {name: jax.random.normal(k, shape[name].shape,
                                    jnp.float32).astype(dtype)
            for name, k in zip(("k", "v"), keys)}


def _inputs(program, seed=5):
    """Tokens (S, W) and positions (S,) of one step: slots at unequal
    positions, the first row (0) and the last window that fits (T_max - W)
    among them. The chunk program has the one slot ``CHUNK_SLOT``."""
    w = WIDTH[program]
    n = 1 if program == "chunk" else SLOTS
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (n, w), 0, V)
    positions = jnp.asarray([0, 7, MAXLEN - w, 3, 11][:n], jnp.int32)
    return tokens, positions


def _run(program, params, cache, tokens, positions):
    """The program under test through its public entry: (cache, logits (S,
    W, V)). The chunk executable samples in-graph, so its logits come from
    the shared loop it is built on and its token is compared as well."""
    if program == "decode":
        step = jax.jit(lambda p, c, t, q: lm.lm_decode_step(
            p, c, t, q, H, TOP_K))
        cache, logits = step(params, cache, tokens[:, 0], positions)
        return cache, logits[:, None, :]
    if program == "verify":
        step = jax.jit(lambda p, c, t, q: lm.lm_verify_step(
            p, c, t, q, H, TOP_K))
        return step(params, cache, tokens, positions)
    loop = jax.jit(lambda p, c, t, q: lm._cached_layers(
        p, c, p["embed"][t], q, H, TOP_K, slot0=CHUNK_SLOT))
    cache, h = loop(params, cache, tokens, positions)
    return cache, h @ params["dec_w"] + params["dec_b"]


# ------------------------------------------------------------------ tests ----

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("program", PROGRAMS)
def test_carried_cache_is_bit_equal_to_the_scanned_cache(program, dtype):
    dtype = jnp.dtype(dtype)
    params, cache = _params(dtype), _stale_cache(dtype)
    tokens, positions = _inputs(program)
    slot = jnp.int32(CHUNK_SLOT) if program == "chunk" else None
    want_cache, want_logits = _oracle(params, cache, tokens, positions, slot)
    got_cache, got_logits = _run(program, params, cache, tokens, positions)
    assert got_logits.dtype == want_logits.dtype
    np.testing.assert_array_equal(np.asarray(got_logits, np.float32),
                                  np.asarray(want_logits, np.float32))
    for leaf in ("k", "v"):
        assert got_cache[leaf].dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(got_cache[leaf], np.float32),
            np.asarray(want_cache[leaf], np.float32))
    if program == "chunk":
        # the jitted executable itself: cache and greedy token at every
        # in-chunk index
        chunk = lm.make_chunk_prefill_step(H, TOP_K, donate_cache=False)
        key = jax.random.PRNGKey(9)
        for last in range(WIDTH["chunk"]):
            c, tok = chunk(params, cache, tokens, positions[0],
                           np.int32(last), np.int32(CHUNK_SLOT),
                           np.float32(0), key, 0)
            assert int(tok) == int(jnp.argmax(want_logits[0, last]))
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(c[leaf], np.float32),
                np.asarray(want_cache[leaf], np.float32))


def _executable(program):
    """The donating executable the engine holds, and a caller of it with
    this file's (tokens, positions)."""
    key = jax.random.PRNGKey(9)
    if program == "chunk":
        fn = lm.make_chunk_prefill_step(H, TOP_K)
        return lambda p, c, t, q: fn(
            p, c, t, q[0], np.int32(t.shape[1] - 1), np.int32(CHUNK_SLOT),
            np.float32(0), key, 0)
    temps = np.zeros(SLOTS, np.float32)
    if program == "decode":
        fn = lm.make_decode_step(H, TOP_K)
        return lambda p, c, t, q: fn(p, c, t[:, 0], q, temps, key, 0)
    fn = lm.make_verify_step(H, TOP_K)
    return lambda p, c, t, q: fn(p, c, t, q, temps, key, 0)


@pytest.mark.parametrize("program", PROGRAMS)
def test_step_writes_its_rows_in_place_and_nothing_else(program,
                                                        retrace_budget):
    """One step of the donating executable: the rows ``[l, s, :,
    positions[s] : positions[s] + W, :]`` of every layer change, every
    other element — stale rows of idle slots, the other positions — is bit
    for bit what it was, the input cache is consumed, and a second call at
    other positions finds its program compiled."""
    dtype = jnp.dtype("bfloat16")
    params, cache = _params(dtype), _stale_cache(dtype)
    before = {leaf: np.asarray(cache[leaf], np.float32) for leaf in cache}
    tokens, positions = _inputs(program)
    call = _executable(program)
    w = WIDTH[program]
    slots = [CHUNK_SLOT] if program == "chunk" else range(SLOTS)

    new_cache, _ = call(params, cache, tokens, positions)
    assert cache["k"].is_deleted() and cache["v"].is_deleted()
    written = np.zeros(before["k"].shape, bool)
    for i, s in enumerate(slots):
        p = int(positions[i])
        written[:, s, :, p:p + w, :] = True
    for leaf in ("k", "v"):
        after = np.asarray(new_cache[leaf], np.float32)
        np.testing.assert_array_equal(after[~written],
                                      before[leaf][~written])
        # random normals: a fresh row equal to the stale one would be luck
        assert (after[written] != before[leaf][written]).mean() > 0.9

    tokens2, _ = _inputs(program, seed=6)
    positions2 = jnp.asarray([5, 0, 2, MAXLEN - w, 9][:len(positions)],
                             jnp.int32)
    with retrace_budget(0, label=f"{program} at other positions"):
        newer, _ = call(params, new_cache, tokens2, positions2)
    assert new_cache["k"].is_deleted() and not newer["k"].is_deleted()
