"""The plain reference of the flagship block, in straightforward jax.numpy.

Pre-LN decoder blocks (LayerNorm with bias, full causal multi-head attention
without positions, top-k softmax router over two-matrix ReLU experts with the
gates renormalised over the chosen k), between an embedding and an output head
with a bias. The training loss is the mean next-token cross-entropy plus
``aux_weight`` times the Switch load-balance term averaged over the layers.

It imports nothing of the program and takes nothing the program made: the
weights come from the same seed through ``init_*`` below, which repeat the
draws of ``models/transformer_lm.py`` (``_init_block``, ``init_lm_params``)
call for call, because a reference on other weights compares nothing.

Everything runs in float32 with matmuls at ``highest`` unless a control asks
for less: ``weights_via`` rounds the weights through a narrower type (the
serve control), ``compute_dtype`` runs the whole train step in bfloat16 (the
train control).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims_of(config: dict) -> dict:
    """The block's sizes from a configuration file's published key names."""
    return {
        "vocab": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_experts": int(config.get("num_experts",
                                    config.get("num_local_experts", 0))),
        "d_ff": int(config["intermediate_size"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_layers": int(config["num_hidden_layers"]),
    }


def seed_key(seed: int):
    """One key from --seed, which may pass 2**31: fold its two halves."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


# ----------------------------------------------------------------- weights ----

def _top_keys(key, n_layers: int):
    return jax.random.split(key, 3 + n_layers)


def init_block(key, d: int, n_experts: int, d_ff: int) -> dict:
    ks = jax.random.split(key, 6)
    n = jax.random.normal
    s_d = 1.0 / (d ** 0.5)
    return {
        "ln_g": jnp.ones((d,)), "ln_b": jnp.zeros((d,)),
        "wq": n(ks[0], (d, d)) * s_d, "wk": n(ks[1], (d, d)) * s_d,
        "wv": n(ks[2], (d, d)) * s_d, "wo": n(ks[3], (d, d)) * s_d,
        "ln2_g": jnp.ones((d,)), "ln2_b": jnp.zeros((d,)),
        "router": n(ks[4], (d, n_experts)) * s_d,
        "experts": {
            "w1": n(ks[5], (n_experts, d, d_ff)) * s_d,
            "b1": jnp.zeros((n_experts, d_ff)),
            "w2": n(jax.random.fold_in(ks[5], 1),
                    (n_experts, d_ff, d)) / (d_ff ** 0.5),
            "b2": jnp.zeros((n_experts, d)),
        },
    }


def init_ends(key, dims: dict) -> dict:
    ks = _top_keys(key, dims["n_layers"])
    n = jax.random.normal
    d, v = dims["d_model"], dims["vocab"]
    return {"embed": n(ks[0], (v, d)) * 0.1,
            "dec_w": n(ks[1], (d, v)) / (d ** 0.5),
            "dec_b": jnp.zeros((v,))}


def init_layer(key, dims: dict, layer: int) -> dict:
    ks = _top_keys(key, dims["n_layers"])
    return init_block(ks[3 + layer], dims["d_model"], dims["n_experts"],
                      dims["d_ff"])


def init_params(key, dims: dict) -> dict:
    blocks = [init_layer(key, dims, i) for i in range(dims["n_layers"])]
    out = init_ends(key, dims)
    out["blocks"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return out


def round_weights(tree, via: str):
    """The weights as a narrower type holds them, back in float32.
    ``bfloat16`` is what the serve configuration states; ``float8_e4m3fn``
    and ``int8`` (symmetric, one scale per output channel) are controls."""
    def one(w):
        if via == "int8":
            if w.ndim < 2:
                return w.astype(jnp.bfloat16).astype(jnp.float32)
            amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
            scale = jnp.maximum(amax, 1e-8) / 127.0
            return jnp.clip(jnp.round(w / scale), -127, 127) * scale
        return w.astype(jnp.dtype(via)).astype(jnp.float32)
    return jax.tree_util.tree_map(one, tree)


# ------------------------------------------------------------------- block ----

def layernorm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * g + b


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def attention(p: dict, h, n_heads: int):
    b, t, d = h.shape
    hd = d // n_heads
    x = layernorm(h, p["ln_g"], p["ln_b"])

    def heads(w):
        return mm(x, w).reshape(b, t, n_heads, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.asarray(hd, h.dtype))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=HIGHEST)
    return h + mm(o.transpose(0, 2, 1, 3).reshape(b, t, d), p["wo"])


def router_gates(router_w, x, top_k: int):
    """(N, E) combine weights: softmax over all experts, kept for the top k
    and renormalised over them. Also returns the logits for the aux loss."""
    logits = mm(x, router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(logits, top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, logits.shape[-1], dtype=x.dtype), 1)
    g = probs * chosen
    if top_k > 1:
        g = g / jnp.maximum(jnp.sum(g, axis=-1, keepdims=True), 1e-9)
    return g, logits


def moe(p: dict, x, top_k: int, lost_experts: int = 0):
    """x: (N, d). One expert after another, each on every row, weighted by
    its gate (0 for rows that did not choose it). ``lost_experts`` plants
    the fault "the exchange between chips left out": what the last that
    many experts would add never arrives."""
    g, logits = router_gates(p["router"], x, top_k)
    if lost_experts:
        g = g.at[:, -lost_experts:].set(0)

    def one(acc, ew):
        w1, b1, w2, b2, ge = ew
        y = mm(jax.nn.relu(mm(x, w1) + b1), w2) + b2
        return acc + ge[:, None] * y, None

    ex = p["experts"]
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (ex["w1"], ex["b1"], ex["w2"], ex["b2"], g.T))
    return out, logits


def block(p: dict, h, n_heads: int, top_k: int, lost_experts: int = 0):
    h = attention(p, h, n_heads)
    x = layernorm(h, p["ln2_g"], p["ln2_b"]).reshape(-1, h.shape[-1])
    out, logits = moe(p, x, top_k, lost_experts)
    return h + out.reshape(h.shape), logits


def load_balance(logits):
    """Switch aux: E * sum_e f_e * P_e, f from the top-1 choice."""
    e = logits.shape[-1]
    f = jnp.mean(jax.nn.one_hot(jnp.argmax(logits, -1), e), axis=0)
    return e * jnp.sum(f * jnp.mean(jax.nn.softmax(logits, -1), axis=0))


# ------------------------------------------------------------------- serve ----

@partial(jax.jit, static_argnames=("n_heads", "top_k"))
def _serve_layer(p, h, n_heads, top_k):
    return block(p, h, n_heads, top_k)[0]


def serve_logit_gaps(seed: int, dims: dict, tokens, lengths, prompt_lens,
                     weights_via: str = "bfloat16", control_via=None,
                     row_block: int = 4, span=None):
    """Teacher-forced forward over ``tokens`` (R, T) int32, each row a
    prompt followed by its served tokens and right-padded (causal attention
    makes the padding exact). Layer by layer, rows in blocks, so that it
    fits beside nothing else on the chip.

    Returns, per row, the array over its served positions of how far the
    served token's logit lies below the row's best logit there. With
    ``control_via`` the tokens judged are not the served ones but those a
    second pass, on weights rounded through that type, puts first.
    ``span`` is the most served tokens any row can have, so that one
    compiled shape serves every seed.
    """
    key = seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)

    def forward(via):
        ends = round_weights(init_ends(key, dims), via)
        outs = []
        for r0 in range(0, tokens.shape[0], row_block):
            outs.append(ends["embed"][tokens[r0:r0 + row_block]])
        for layer in range(dims["n_layers"]):
            p = round_weights(_init_layer(key, _Frozen(dims), layer), via)
            outs = [_serve_layer(p, h, dims["n_heads"], dims["top_k"])
                    for h in outs]
        return ends, outs

    ends, hs = forward(weights_via)
    ends_c, judged_hs = forward(control_via) if control_via else (None, None)
    if span is None:  # the most served tokens of a row
        span = int(max(n - p for n, p in zip(lengths, prompt_lens)))
    span = min(-(-int(span) // 64) * 64, tokens.shape[1] - 1)
    gaps = []
    for bi, h in enumerate(hs):
        r0 = bi * row_block
        # the logits at position t predict token t + 1: start a step early
        starts = jnp.minimum(jnp.asarray(prompt_lens[r0:r0 + h.shape[0]]) - 1,
                             tokens.shape[1] - 1 - span)
        got = _block_gaps(h, judged_hs[bi] if control_via else h, ends,
                          ends_c if control_via else ends,
                          tokens[r0:r0 + h.shape[0]], starts, span,
                          control_via is not None)
        got = jax.device_get(got)
        for j in range(h.shape[0]):
            first = int(prompt_lens[r0 + j]) - 1 - int(starts[j])
            n = int(lengths[r0 + j]) - int(prompt_lens[r0 + j])
            gaps.append(got[j, first:first + n])
    return gaps


@partial(jax.jit, static_argnames=("span", "control"))
def _block_gaps(h, h_judge, ends, ends_judge, tokens, starts, span, control):
    """(rows, span) gaps: from ``starts`` on, how far below the row's best
    logit lies the logit of the judged token: the next served token, or
    with ``control`` the token the second pass puts first."""
    def row(hr, hj, toks, start):
        logits = mm(jax.lax.dynamic_slice_in_dim(hr, start, span),
                    ends["dec_w"]) + ends["dec_b"]
        if control:
            lc = mm(jax.lax.dynamic_slice_in_dim(hj, start, span),
                    ends_judge["dec_w"]) + ends_judge["dec_b"]
            judged = jnp.argmax(lc, axis=-1)
        else:
            judged = jax.lax.dynamic_slice_in_dim(toks, start + 1, span)
        got = jnp.take_along_axis(logits, judged[:, None], 1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    return jax.vmap(row)(h, h_judge, tokens, starts)


class _Frozen(dict):
    """A dict jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


_init_layer = jax.jit(init_layer, static_argnums=(1, 2))


# ------------------------------------------------------------------- train ----

def loss_fn(params, tokens, targets, n_heads, top_k, aux_weight,
            lost_experts=0):
    h = params["embed"][tokens]

    @jax.checkpoint  # the same values, a layer's activations at a time
    def step(h, p):
        h, logits = block(p, h, n_heads, top_k, lost_experts)
        return h, load_balance(logits)

    h, aux = jax.lax.scan(step, h, params["blocks"])
    logits = mm(h, params["dec_w"]) + params["dec_b"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux_weight * jnp.mean(aux).astype(jnp.float32)


def leaf_norms(tree) -> dict:
    """{leaf path: Frobenius norm}, computed in float32."""
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(
        leaf.astype(jnp.float32))))
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def change_norms(params, key, init_fn) -> dict:
    """Per-leaf norm of ``params`` minus what ``init_fn(key)`` makes: the
    start is made again leaf by leaf and never held whole."""
    return leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, init_fn(key)))


def train_reference(seed: int, dims: dict, batches, lr: float,
                    aux_weight: float, compute_dtype=jnp.float32,
                    place=None, drop_half: bool = False,
                    lost_experts: int = 0):
    """Three plain SGD steps from the seed's weights on ``batches`` (a list
    of (tokens, targets)). Returns the losses, the per-leaf norm of the first
    gradient (as the update shows it: change after one step over lr) and the
    per-leaf norm of the change after all steps.

    ``compute_dtype=bfloat16`` is the control: weights, activations and the
    update all in bfloat16. ``drop_half`` plants the fault "half of the batch
    left out, the mean taken over the rest", ``lost_experts`` the fault "the
    exchange between chips left out" (see ``moe``). ``place`` puts the weights and
    each batch on several chips (a placement, not another computation).
    """
    key = seed_key(seed)
    frozen = _Frozen(dims)

    def make(k):
        return jax.tree_util.tree_map(lambda w: w.astype(compute_dtype),
                                      init_params(k, frozen))

    init = jax.jit(make, out_shardings=place["params"] if place else None)

    @partial(jax.jit, donate_argnums=(0,))
    def step(params, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, targets, dims["n_heads"], dims["top_k"],
            aux_weight, lost_experts)
        new = jax.tree_util.tree_map(
            lambda p, g: (p - lr * g).astype(p.dtype), params, grads)
        return new, loss

    norms = jax.jit(lambda p, k: change_norms(p, k, make))
    params = init(key)
    losses, grad_norms = [], None
    for i, (tokens, targets) in enumerate(batches):
        if drop_half and len(tokens) > 1:
            tokens, targets = tokens[:len(tokens) // 2], \
                targets[:len(targets) // 2]
        elif drop_half:  # one row: its second half left out
            tokens, targets = tokens[:, :tokens.shape[1] // 2], \
                targets[:, :targets.shape[1] // 2]
        if place:
            rows = place["batch"] if len(tokens) % place["chips"] == 0 \
                else place["whole"]  # too few rows to split: each chip all
            tokens = jax.device_put(tokens, rows)
            targets = jax.device_put(targets, rows)
        params, loss = step(params, tokens, targets)
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(v) / lr
                          for k, v in norms(params, key).items()}
    change = {k: float(v) for k, v in norms(params, key).items()}
    del params
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
