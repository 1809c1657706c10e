"""The plain reference of Granite-4.0-H (HF ``GraniteMoeHybrid`` with no
experts), in straightforward jax.numpy.

``h = embed[tok] * embedding_multiplier``; each layer l adds
``residual_multiplier * mixer_l(RMSNorm(h))``, the mixer a Mamba-2 layer or
causal grouped-query attention (no positions, softmax scale
``attention_multiplier``) as ``layer_types[l]`` says, then
``residual_multiplier * W_out(silu(a) * b)`` with ``[a, b] = W_in(RMSNorm(h))``;
``logits = RMSNorm(h) @ embed^T / logits_scaling``.

The Mamba-2 layer is its recurrence one token at a time (``mamba``): no
chunks, no state handed between programs. Attention is the whole causal
softmax. Everything runs in float32 with matmuls at ``highest``; the weights
come from the same seed through ``init_*`` below, which repeat the draws of
``models/transformer_lm.py`` (``_init_hybrid``, ``_init_hybrid_layer``) and
``models/ssm.py`` (``init_mixer``) call for call, and are rounded through the
type the program serves them in (``weights_via``). It imports nothing of
the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _period(kinds: list) -> tuple:
    """The shortest prefix of ``kinds`` that repeats through all of it."""
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and all(k == kinds[i % p]
                                       for i, k in enumerate(kinds)):
            return tuple(kinds[:p])
    raise AssertionError("unreachable")


def dims_of(config: dict) -> dict:
    """The model's sizes from a configuration file's published key names
    (hashable: a jit takes them as a static argument)."""
    n_layers = int(config["num_hidden_layers"])
    kinds = list(config["layer_types"])
    pattern = _period(kinds)
    n_heads = int(config["num_attention_heads"])
    return {
        "vocab": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_heads": n_heads,
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["hidden_size"]) // n_heads,
        "d_ff": int(config["shared_intermediate_size"]),
        "n_layers": n_layers,
        "pattern": pattern,
        "kinds": tuple(pattern[i % len(pattern)] for i in range(n_layers)),
        "ssm_heads": int(config["mamba_n_heads"]),
        "ssm_head_dim": int(config["mamba_d_head"]),
        "ssm_state": int(config["mamba_d_state"]),
        "ssm_groups": int(config["mamba_n_groups"]),
        "ssm_conv": int(config["mamba_d_conv"]),
        "ssm_chunk": int(config["mamba_chunk_size"]),
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual_multiplier": float(config["residual_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "eps": float(config["rms_norm_eps"]),
        "embed_scale": float(config["weights"]["embed_scale"]),
        # what the harness's generic readers ask of a serving model
        "n_experts": 0, "top_k": 0,
    }


def seed_key(seed: int):
    """One key from --seed, which may pass 2**31: fold its two halves."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class _Frozen(dict):
    """A dict jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


# ----------------------------------------------------------------- weights ----

def _mixer(key, dims: dict) -> dict:
    h, p = dims["ssm_heads"], dims["ssm_head_dim"]
    g, n, k, d = dims["ssm_groups"], dims["ssm_state"], dims["ssm_conv"], \
        dims["d_model"]
    d_inner = h * p
    conv_dim = d_inner + 2 * g * n
    ks = jax.random.split(key, 5)
    a = jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    lo, hi = jnp.log(0.001), jnp.log(0.1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(ks[3], (h,)) * (hi - lo)
                             + lo), 1e-4)
    return {
        "in_proj": jax.random.normal(ks[0], (d, d_inner + conv_dim + h))
        / (d ** 0.5),
        "conv_w": jax.random.normal(ks[1], (k, conv_dim)) / (k ** 0.5),
        "conv_b": jnp.zeros((conv_dim,)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(a),
        "D": jnp.ones((h,)),
        "norm_g": jnp.ones((d_inner,)),
        "out_proj": jax.random.normal(ks[4], (d_inner, d)) / (d_inner ** 0.5),
    }


def init_layer(key, dims: dict, layer, kind: str) -> dict:
    """Layer ``layer``'s weights (a ``kind`` layer), from the seed's key."""
    d, f = dims["d_model"], dims["d_ff"]
    d_q = dims["n_heads"] * dims["head_dim"]
    d_kv = dims["n_kv_heads"] * dims["head_dim"]
    top = jax.random.split(key, 3 + dims["n_layers"])
    ks = jax.random.split(top[3 + layer], 3)
    p = {"ln_g": jnp.ones((d,))}
    if kind == "mamba":
        p["mixer"] = _mixer(ks[0], dims)
    else:
        kq, kk, kv, ko = jax.random.split(ks[0], 4)
        p["wq"] = jax.random.normal(kq, (d, d_q)) / (d ** 0.5)
        p["wk"] = jax.random.normal(kk, (d, d_kv)) / (d ** 0.5)
        p["wv"] = jax.random.normal(kv, (d, d_kv)) / (d ** 0.5)
        p["wo"] = jax.random.normal(ko, (d_q, d)) / (d_q ** 0.5)
    p["ln2_g"] = jnp.ones((d,))
    p["mlp"] = {"w_in": jax.random.normal(ks[1], (d, 2 * f)) / (d ** 0.5),
                "w_out": jax.random.normal(ks[2], (f, d)) / (f ** 0.5)}
    return p


def init_ends(key, dims: dict) -> dict:
    top = jax.random.split(key, 3 + dims["n_layers"])
    return {"embed": jax.random.normal(top[0], (dims["vocab"],
                                                dims["d_model"]))
            * dims["embed_scale"],
            "lnf_g": jnp.ones((dims["d_model"],))}


def round_weights(tree, via: str):
    """The weights as a narrower type holds them, back in float32."""
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.dtype(via)).astype(jnp.float32), tree)


def decay_rates(seed: int, dims: dict, layer: int):
    """Mamba layer ``layer``'s ``softplus(dt_bias) * exp(A_log)`` (H,): how
    fast each head's state forgets at its bias, weights as drawn."""
    p = _init_layer(seed_key(seed), _Frozen(dims), layer, "mamba")["mixer"]
    return jax.nn.softplus(p["dt_bias"]) * jnp.exp(p["A_log"])


# the layer's index traced: one program a kind, not one a layer
_init_layer = jax.jit(init_layer, static_argnums=(1, 3))
_init_ends = jax.jit(init_ends, static_argnums=(1,))


# ------------------------------------------------------------------- model ----

def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def mamba(p: dict, u, dims: dict, keep_at=None):
    """The Mamba-2 mixer over u (R, T, d), its recurrence a token at a
    time from a zero state; returns (out, state), the state (R, H, P, N)
    after the last row. With ``keep_at`` (R,) the recurrence takes no input
    after a row's position ``keep_at`` (dt 0 there: ``exp(0) = 1``, input
    0), so the state is the one after that position, and the outputs after
    it are not the forward's."""
    h, pd = dims["ssm_heads"], dims["ssm_head_dim"]
    g, n, k = dims["ssm_groups"], dims["ssm_state"], dims["ssm_conv"]
    d_inner = h * pd
    r, t, _ = u.shape
    zxbcdt = mm(u, p["in_proj"])
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * g * n]
    dt = zxbcdt[..., 2 * d_inner + 2 * g * n:]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = p["conv_b"] + sum(p["conv_w"][i] * padded[:, i:i + t]
                             for i in range(k))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_inner].reshape(r, t, h, pd)
    group = jnp.arange(h) // (h // g)          # the group each head reads
    b = xbc[..., d_inner:d_inner + g * n].reshape(r, t, g, n)
    c = xbc[..., d_inner + g * n:].reshape(r, t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])    # (R, T, H)
    if keep_at is not None:
        dt = jnp.where(jnp.arange(t)[None, :, None] <= keep_at[:, None, None],
                       dt, 0.0)
    a = -jnp.exp(p["A_log"])

    def one(s, row):
        xt, dtt, bt, ct = row                  # (R,H,P) (R,H) (R,G,N) (R,G,N)
        bt, ct = bt[:, group], ct[:, group]    # (R, H, N)
        s = jnp.exp(dtt * a)[..., None, None] * s \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return s, jnp.einsum("rhpn,rhn->rhp", s, ct, precision=HIGHEST)

    state, y = jax.lax.scan(one, jnp.zeros((r, h, pd, n)),
                            tuple(jnp.moveaxis(v, 1, 0)
                                  for v in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x
    y = y.reshape(r, t, d_inner) * jax.nn.silu(z)
    return mm(rmsnorm(y, p["norm_g"], dims["eps"]), p["out_proj"]), state


def attention(p: dict, u, dims: dict):
    """Causal grouped-query attention over one row u (T, d), no positions."""
    t = u.shape[0]
    hq, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    q = mm(u, p["wq"]).reshape(t, hq, hd)
    k = jnp.repeat(mm(u, p["wk"]).reshape(t, hkv, hd), hq // hkv, axis=1)
    v = jnp.repeat(mm(u, p["wv"]).reshape(t, hkv, hd), hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * dims["attention_multiplier"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
    return mm(o.reshape(t, hq * hd), p["wo"])


def mlp(p: dict, u):
    a, b = jnp.split(mm(u, p["w_in"]), 2, axis=-1)
    return mm(jax.nn.silu(a) * b, p["w_out"])


def state_probe(dims: dict, k: int = 8):
    """The ``k`` fixed directions (k, P, N) along which a state (H, P, N)
    is read, the same for the program's states and the reference's: a
    read of 8 numbers a head instead of 8,192."""
    return jax.random.normal(jax.random.PRNGKey(0x57A7E),
                             (k, dims["ssm_head_dim"], dims["ssm_state"]))


@partial(jax.jit, static_argnames=("dims", "kind"))
def layer(p: dict, h, dims: dict, kind: str, keep_at=None):
    """One decoder layer over h (R, T, d). Attention and the FFN go a row
    at a time, so that the scores of one row are all that is held. With
    ``keep_at`` (R,), a Mamba layer also returns its state after each
    row's token at that position read along ``state_probe``, (R, H, k)."""
    r = dims["residual_multiplier"]
    u = rmsnorm(h, p["ln_g"], dims["eps"])
    read = None
    if kind == "mamba":
        out, state = mamba(p["mixer"], u, dims, keep_at)
        h = h + r * out
        if keep_at is not None:
            read = jnp.einsum("rhpn,kpn->rhk", state, state_probe(dims),
                              precision=HIGHEST)
    else:
        h = h + r * jax.lax.map(lambda row: attention(p, row, dims), u)
    return h + r * jax.lax.map(
        lambda row: mlp(p["mlp"], rmsnorm(row, p["ln2_g"], dims["eps"])),
        h), read


def hidden(seed: int, dims: dict, tokens, via: str = "bfloat16",
           keep_at=None):
    """(ends, h (R, T, d), states) after the last layer, teacher-forced
    over ``tokens`` (R, T), layer by layer on weights rounded through
    ``via``. ``states`` is None, or with ``keep_at`` (R,) each row's state
    after its token at that position in every Mamba layer, read along
    ``state_probe``: (R, L_mamba, H, k)."""
    key, frozen = seed_key(seed), _Frozen(dims)
    ends = round_weights(_init_ends(key, frozen), via)
    h = ends["embed"][jnp.asarray(tokens, jnp.int32)] \
        * dims["embedding_multiplier"]
    if keep_at is not None:
        keep_at = jnp.asarray(keep_at, jnp.int32)
    reads = []
    for i, kind in enumerate(dims["kinds"]):
        h, read = layer(round_weights(_init_layer(key, frozen, i, kind), via),
                        h, frozen, kind, keep_at)
        if read is not None:
            reads.append(read)
    return ends, h, (jnp.stack(reads, 1) if reads else None)


@partial(jax.jit, static_argnames=("dims",))
def head(ends: dict, h, dims: dict):
    """Logits of h (..., d) over the whole vocabulary."""
    return mm(rmsnorm(h, ends["lnf_g"], dims["eps"]), ends["embed"].T) \
        / dims["logits_scaling"]


def logits(seed: int, dims: dict, tokens, via: str = "float32"):
    """The full forward's logits (R, T, V): the tests' comparison."""
    ends, h, _ = hidden(seed, dims, tokens, via)
    return head(ends, h, _Frozen(dims))


def serve_logit_gaps(seed: int, dims: dict, tokens, lengths, prompt_lens,
                     weights_via: str = "bfloat16", control_via=None,
                     span=None, keep_at=None):
    """Teacher-forced forward over ``tokens`` (R, T) int32, each row a
    prompt followed by its served tokens and right-padded (the recurrence
    and causal attention make right-padding exact for every row before it).
    Returns ``(gaps, states, judged_states)``: per row, the array over its
    served positions of how far the served token's logit lies below the
    row's best logit there; and with ``keep_at`` (R,), each row's state
    after its token at that position in every Mamba layer read along
    ``state_probe``, (R, L_mamba, H, k), else None. With ``control_via``
    the tokens judged, and ``judged_states``, are those of the reference on
    weights rounded through that type (else ``judged_states`` is None).
    ``span`` is the most served tokens a row can have, so that one compiled
    shape serves every seed."""
    frozen = _Frozen(dims)
    tokens = jnp.asarray(tokens, jnp.int32)
    ends, h, states = hidden(seed, dims, tokens, weights_via, keep_at)
    judged_ends, judged_h, judged_states = (
        hidden(seed, dims, tokens, control_via, keep_at) if control_via
        else (ends, h, None))
    if span is None:
        span = int(max(n - p for n, p in zip(lengths, prompt_lens)))
    span = min(-(-int(span) // 64) * 64, tokens.shape[1] - 1)
    # the logits at position t predict token t + 1: start a step early
    starts = jnp.minimum(jnp.asarray(prompt_lens) - 1,
                         tokens.shape[1] - 1 - span)
    got = jax.device_get(_gaps(ends, judged_ends, h, judged_h, tokens,
                               starts, span, control_via is not None, frozen))
    out = []
    for j in range(tokens.shape[0]):
        first = int(prompt_lens[j]) - 1 - int(starts[j])
        out.append(got[j, first:first + int(lengths[j])
                       - int(prompt_lens[j])])
    return out, jax.device_get(states), jax.device_get(judged_states)


@partial(jax.jit, static_argnames=("span", "control", "dims"))
def _gaps(ends, judged_ends, h, judged_h, tokens, starts, span, control,
          dims):
    def row(hr, hj, toks, start):
        lg = head(ends, jax.lax.dynamic_slice_in_dim(hr, start, span), dims)
        if control:
            judged = jnp.argmax(head(judged_ends, jax.lax.dynamic_slice_in_dim(
                hj, start, span), dims), axis=-1)
        else:
            judged = jax.lax.dynamic_slice_in_dim(toks, start + 1, span)
        got = jnp.take_along_axis(lg, judged[:, None], 1)[:, 0]
        return jnp.max(lg, axis=-1) - got

    return jax.lax.map(lambda a: row(*a), (h, judged_h, tokens, starts))
