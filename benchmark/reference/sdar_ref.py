"""The plain reference of the SDAR block and of generation by diffusion over
blocks, in straightforward jax.numpy: float32, matmuls at ``highest``, no
cache, no batching. It imports nothing of the program.

The block, for layer input x (T, d), with n(x; g) = x / sqrt(mean(x^2) + eps)
* g over the last axis:

- h = n(x; g1); q = h Wq as (H, T, Dh), k = h Wk and v = h Wv as (H_kv, T,
  Dh); q = n(q; gq), k = n(k; gk) over each head's Dh (one gain vector for all
  heads); rotary positions on q and k (rotate-half over Dh, theta, no
  scaling); query head i reads K/V head i // (H / H_kv); scores q k^T /
  sqrt(Dh) under a mask, softmax; x' = x + concat_heads(softmax(.) v) Wo. No
  bias anywhere.
- h2 = n(x'; g2); p = softmax(h2 Wr) over all E experts; the k largest; gates
  p_e / sum_topk p (``norm_topk_prob``); x'' = x' + sum_e gate_e ((silu(h2
  Wg_e) * (h2 Wu_e)) Wd_e).
- logits n(x_L; gf) W_head, the head untied from the embedding.

Generation. A sequence is laid out in blocks of B from position 0 and position
i sees j iff j // B <= i // B. A prompt of P tokens fills P // B blocks; its
last P % B tokens sit, known, at the head of the first generated block, whose
other positions are masked, as are all B of every later block. A masked
position's input is the mask token's embedding. Until no position of the
current block is masked: one forward (the clean blocks before it and the block
as it stands); at every masked position the token (greedy: the largest logit)
and its confidence, the softmax probability of that token; the B // D masked
positions of highest confidence (the earlier on a tie; all that are left, if
fewer) take their token. Then, unless the request has its ``max_new_tokens``,
one commit forward of the finished block, whose logits nobody reads (a cache
would store its K/V; here the next block's forward recomputes them from the
clean tokens, which is the same), and the next block. What a block generates
past ``max_new_tokens`` is dropped.

``dims`` carries every size and every switch (``dims_of`` reads them from a
configuration file). Each part of the block can be switched to the flagship's
(``norm`` "layernorm", ``rope_theta`` None, ``n_kv_heads`` = ``n_heads``,
``qk_norm`` False, ``ffn`` "relu", ``norm_topk_prob`` False, ``block_length``
1, which is the causal mask) for the tests that add one part at a time; the
flagship's biases are zero at init and are left out.

The weights come from the seed by ``init_*`` below, which repeat the draws of
``models/transformer_lm.py`` (``_init_block``, ``init_lm_params`` under the
spec, at ``init_scale``) call for call: a reference on other weights
compares nothing. ``weights_via`` rounds them through a narrower type
(bfloat16 is what the configuration serves; float8_e4m3fn is the control).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims_of(config: dict) -> dict:
    """Sizes and switches from a configuration file's published key names
    and its ``generation`` group."""
    gen = config["generation"]
    return {
        "vocab": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_heads": int(config["num_attention_heads"]),
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "n_experts": int(config["num_experts"]),
        "d_ff": int(config["moe_intermediate_size"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_layers": int(config["num_hidden_layers"]),
        "norm": "rmsnorm", "eps": float(config["rms_norm_eps"]),
        "rope_theta": float(config["rope_theta"]), "qk_norm": True,
        "ffn": "swiglu", "norm_topk_prob": bool(config["norm_topk_prob"]),
        "final_norm": True,
        "block_length": int(gen["block_length"]),
        "denoising_steps": int(gen["denoising_steps"]),
        "mask_token_id": int(gen["mask_token_id"]),
        "init_scale": float(config["weights"]["init_scale"]),
    }


def seed_key(seed: int):
    """One key from --seed, which may pass 2**31: fold its two halves."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class _Frozen(dict):
    """``dims`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


# ----------------------------------------------------------------- weights ----

def _top_keys(key, n_layers: int):
    return jax.random.split(key, 3 + n_layers)


@partial(jax.jit, static_argnames=("dims",))
def init_layer(key, dims: dict, layer) -> dict:
    ks = jax.random.split(_top_keys(key, dims["n_layers"])[3 + layer], 6)
    d, e, f, hd = (dims["d_model"], dims["n_experts"], dims["d_ff"],
                   dims["head_dim"])
    d_q, d_kv = dims["n_heads"] * hd, dims["n_kv_heads"] * hd

    def n(k, shape):
        return jax.random.normal(k, shape) * dims["init_scale"]

    p = {"g1": jnp.ones((d,)), "g2": jnp.ones((d,)),
         "gq": jnp.ones((hd,)), "gk": jnp.ones((hd,)),
         "wq": n(ks[0], (d, d_q)), "wk": n(ks[1], (d, d_kv)),
         "wv": n(ks[2], (d, d_kv)), "wo": n(ks[3], (d_q, d)),
         "router": n(ks[4], (d, e))}
    if dims["ffn"] == "swiglu":
        p["wg"] = n(ks[5], (e, d, f))
        p["wu"] = n(jax.random.fold_in(ks[5], 1), (e, d, f))
        p["wd"] = n(jax.random.fold_in(ks[5], 2), (e, f, d))
    else:
        p["w1"] = n(ks[5], (e, d, f))
        p["w2"] = n(jax.random.fold_in(ks[5], 1), (e, f, d))
    return p


@partial(jax.jit, static_argnames=("dims",))
def init_ends(key, dims: dict) -> dict:
    ks = _top_keys(key, dims["n_layers"])
    d, v = dims["d_model"], dims["vocab"]
    return {"embed": jax.random.normal(ks[0], (v, d)) * dims["init_scale"],
            "head": jax.random.normal(ks[1], (d, v)) * dims["init_scale"],
            "gf": jnp.ones((d,))}


def round_weights(tree, via: str):
    """The weights as a narrower type holds them, back in float32."""
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.dtype(via)).astype(jnp.float32), tree)


def model_weights(seed: int, dims: dict, via: str = "float32") -> dict:
    """Every weight at once: for the small sizes of the CPU tests."""
    key, dims = seed_key(seed), _Frozen(dims)
    return round_weights(
        {"ends": init_ends(key, dims),
         "layers": [init_layer(key, dims, i)
                    for i in range(dims["n_layers"])]}, via)


# ------------------------------------------------------------------- block ----

def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def norm(x, g, dims):
    if dims["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + dims["eps"]) * g


def rope(x, positions, theta: float):
    """x (H, T, Dh), positions (T,): rotate-half."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def attention(p: dict, x, mask, positions, dims: dict):
    """x (T, d); mask (T, T) bool, row i the positions i sees."""
    t = x.shape[0]
    h_q, h_kv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    h = norm(x, p["g1"], dims)
    q = mm(h, p["wq"]).reshape(t, h_q, hd).transpose(1, 0, 2)
    k = mm(h, p["wk"]).reshape(t, h_kv, hd).transpose(1, 0, 2)
    v = mm(h, p["wv"]).reshape(t, h_kv, hd).transpose(1, 0, 2)
    if dims["qk_norm"]:
        rms = dict(dims, norm="rmsnorm")
        q, k = norm(q, p["gq"], rms), norm(k, p["gk"], rms)
    if dims["rope_theta"] is not None:
        q = rope(q, positions, dims["rope_theta"])
        k = rope(k, positions, dims["rope_theta"])
    k = jnp.repeat(k, h_q // h_kv, axis=0)  # query head i reads head i // G
    v = jnp.repeat(v, h_q // h_kv, axis=0)
    scores = jnp.einsum("hqd,hkd->hqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(hd))
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", probs, v, precision=HIGHEST)
    return x + mm(o.transpose(1, 0, 2).reshape(t, h_q * hd), p["wo"])


def router_gates(router_w, x, dims: dict):
    """(T, E) combine weights: the softmax over all experts, kept for the
    k largest, renormalised over them where the config says so."""
    probs = jax.nn.softmax(mm(x, router_w), axis=-1)
    _, idx = jax.lax.top_k(probs, dims["top_k"])
    g = probs * jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]), axis=1)
    if dims["norm_topk_prob"] and dims["top_k"] > 1:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g


def moe(p: dict, x, dims: dict):
    """One expert after another, each on every row, weighted by its gate (0
    for the rows that did not choose it)."""
    g = router_gates(p["router"], x, dims)
    gated = dims["ffn"] == "swiglu"

    def one(acc, ew):
        if gated:
            wg, wu, wd, ge = ew
            y = mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)
        else:
            w1, w2, ge = ew
            y = mm(jax.nn.relu(mm(x, w1)), w2)
        return acc + ge[:, None] * y, None

    ws = (p["wg"], p["wu"], p["wd"]) if gated else (p["w1"], p["w2"])
    return jax.lax.scan(one, jnp.zeros_like(x), ws + (g.T,))[0]


@partial(jax.jit, static_argnames=("dims",))
def layer(p: dict, x, mask, positions, dims: dict):
    x = attention(p, x, mask, positions, dims)
    return x + moe(p, norm(x, p["g2"], dims), dims)


def head_logits(ends: dict, x, dims: dict):
    if dims["final_norm"]:
        x = norm(x, ends["gf"], dims)
    return mm(x, ends["head"])


def block_mask(positions, block_length: int):
    """M over a sequence at ``positions``: i sees j iff j // B <= i // B."""
    b = np.asarray(positions) // block_length
    return b[None, :] <= b[:, None]


def forward_logits(weights: dict, dims: dict, tokens, mask=None,
                   positions=None):
    """Logits (T, V) of one sequence, by default laid out from position 0
    under the block mask."""
    dims = _Frozen(dims)
    tokens = jnp.asarray(tokens, jnp.int32)
    if positions is None:
        positions = np.arange(tokens.shape[0])
    if mask is None:
        mask = block_mask(positions, dims["block_length"])
    x = weights["ends"]["embed"][tokens]
    for p in weights["layers"]:
        x = layer(p, x, jnp.asarray(mask), jnp.asarray(positions), dims)
    return head_logits(weights["ends"], x, dims)


# -------------------------------------------------------------- generation ----

def unmask(logits, masked, n: int):
    """What a denoising forward decides from the block's logits (B, V) and
    which of its positions are masked: (token a position, confidence a
    position in log-probability, the positions that leave the mask)."""
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    toks = logp.argmax(-1)
    conf = logp.max(-1)
    order = np.argsort(np.where(masked, -conf, np.inf), kind="stable")
    return toks, conf, sorted(int(i) for i in order[:n] if masked[i])


def generate(weights: dict, dims: dict, prompt, max_new_tokens: int,
             eos_id=None):
    """The generation loop, a forward at a time: (generated tokens, the
    forward records ``(block, kind, masked before, positions accepted,
    tokens after)``)."""
    width, steps = dims["block_length"], dims["denoising_steps"]
    prompt = [int(t) for t in prompt]
    start = len(prompt) // width * width
    clean, generated, records = prompt[:start], [], []
    tokens = np.zeros(width, np.int64)
    tokens[:len(prompt) - start] = prompt[start:]
    masked = np.arange(width) >= len(prompt) - start
    while True:
        block = len(clean) // width
        while masked.any():
            seq = clean + [dims["mask_token_id"] if m else int(t)
                           for t, m in zip(tokens, masked)]
            logits = forward_logits(weights, dims, seq)[-width:]
            toks, _, took = unmask(logits, masked, max(1, width // steps))
            before = masked.copy()
            tokens[took], masked[took] = toks[took], False
            records.append((block, "denoise", tuple(map(bool, before)),
                            tuple(took), tuple(int(t) for t in tokens)))
        first = max(0, len(prompt) - len(clean))
        for tok in tokens[first:]:
            if eos_id is not None and tok == eos_id:
                return generated, records
            generated.append(int(tok))
            if len(generated) >= max_new_tokens:
                return generated, records
        records.append((block, "commit", (False,) * width, (),
                        tuple(int(t) for t in tokens)))
        clean = clean + [int(t) for t in tokens]
        tokens, masked = np.zeros(width, np.int64), np.ones(width, bool)


def schedule_faults(prompt, forwards, n_generated: int, max_new_tokens: int,
                    dims: dict, eos_id=None) -> int:
    """How often a request's records break the schedule: a denoising forward
    that accepted other than ``B // D`` of its masked positions (all that
    were left, if fewer) or a position that was not masked; a block begun
    before the one before it was whole and committed, or from another mask
    than its own; a request that ended short of its length."""
    width = dims["block_length"]
    n = max(1, width // dims["denoising_steps"])
    start = len(prompt) // width * width
    block = start // width
    masked = tuple(i >= len(prompt) - start for i in range(width))
    faults = 0
    for b, kind, before, took, _ in forwards:
        whole = not any(masked)
        if kind == "denoise":
            faults += b != block or whole or tuple(before) != masked
            faults += len(took) != min(n, sum(before)) or \
                any(not before[i] for i in took)
            block = b
            masked = tuple(m and i not in took for i, m in enumerate(before))
        else:
            faults += b != block or not whole
            block, masked = b + 1, (True,) * width
    if eos_id is None and n_generated != max_new_tokens:
        faults += 1
    return int(faults)


# ------------------------------------------------------------------ replay ----

def noisy_states(prompt, forwards, dims: dict):
    """What a request's records say its forwards saw: the clean sequence
    (the prompt's whole blocks, then each block as its last record leaves
    it), and per denoising record ``(first row, input tokens with the mask
    id at the masked positions, masked before, positions accepted, tokens
    after)``."""
    width = dims["block_length"]
    start = len(prompt) // width * width
    blocks, noisy = {}, []
    for block, kind, before, took, after in forwards:
        blocks[block] = after
        if kind == "denoise":
            state = [dims["mask_token_id"] if m else int(t)
                     for t, m in zip(after, before)]
            noisy.append((block * width, state, before, took, after))
    clean = [int(t) for t in prompt[:start]]
    for block in sorted(blocks):
        clean += [int(t) for t in blocks[block]]
    return clean, noisy


def _stats(logits, judged):
    """Per position: the best logit, the judged token's logit, the log of
    the softmax's denominator, the best token."""
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, judged[:, None], axis=-1)[:, 0]
    return (best, at, jax.nn.logsumexp(logits, axis=-1),
            jnp.argmax(logits, axis=-1))


@partial(jax.jit, static_argnames=("dims",))
def _head_stats(ends, x, judged, dims):
    return _stats(head_logits(ends, x, dims), judged)


def stacked_layout(prompt, forwards, dims: dict, clean_width: int,
                   noisy_width: int):
    """One sequence a request: the clean copy (``clean_width`` positions,
    padded with zeros that nothing real sees) and beside it the noisy state
    of every denoising forward (``noisy_width`` positions). A noisy block
    sees the clean blocks before it and itself; the clean copy sees M.
    Returns (tokens, positions, mask, judged tokens, the noisy records)."""
    width = dims["block_length"]
    clean, noisy = noisy_states(prompt, forwards, dims)
    if len(clean) > clean_width or len(noisy) * width > noisy_width:
        raise ValueError(f"a request of {len(clean)} clean and "
                         f"{len(noisy) * width} noisy positions does not "
                         f"fit {clean_width} + {noisy_width}")
    total = clean_width + noisy_width
    tokens = np.zeros(total, np.int32)
    tokens[:len(clean)] = clean
    positions = np.concatenate([np.arange(clean_width),
                                np.zeros(noisy_width, np.int64)])
    mask = np.zeros((total, total), bool)
    mask[:clean_width, :clean_width] = block_mask(np.arange(clean_width),
                                                  width)
    judged = np.zeros(total, np.int32)
    for i in range(noisy_width // width):
        lo = clean_width + i * width
        mask[lo:lo + width, lo:lo + width] = True  # a spare block sees itself
        if i < len(noisy):
            row, state, _, _, after = noisy[i]
            tokens[lo:lo + width] = state
            positions[lo:lo + width] = row + np.arange(width)
            mask[lo:lo + width, :row] = True
            judged[lo:lo + width] = after
    return tokens, positions, mask, judged, noisy


def replay(seed: int, dims: dict, requests: list, weights_via: str,
           control_via=None, stacked: bool = True, clean_width=None,
           noisy_width=None) -> list:
    """Every denoising forward of ``requests`` (``(prompt, forwards)``
    each) again in the reference, from its recorded state. Per request a
    list, one entry a denoising record: ``(masked before, positions
    accepted, best logit, judged token's logit, confidence)`` with the last
    three an array over the block's B positions; the confidence is the
    reference's own, the log-probability of its best token. The judged
    token is the one the record says the position holds after the forward
    or, with ``control_via``, the one a second pass on weights rounded
    through that type puts first.

    ``stacked`` runs one forward a request over ``stacked_layout``, layer by
    layer with one layer's weights at a time, so that the real size fits
    beside nothing else on a chip; otherwise a forward a record, which is
    what the loop does and what the CPU test holds the stacked one equal
    to."""
    key, dims = seed_key(seed), _Frozen(dims)
    width = dims["block_length"]
    if not stacked:
        w = model_weights(seed, dims, weights_via)
        wc = model_weights(seed, dims, control_via) if control_via else None
        out = []
        for prompt, forwards in requests:
            clean, noisy = noisy_states(prompt, forwards, dims)
            rows = []
            for row, state, before, took, after in noisy:
                seq = clean[:row] + state
                logits = forward_logits(w, dims, seq)[-width:]
                judged = jnp.asarray(after, jnp.int32)
                if wc is not None:
                    judged = jnp.argmax(
                        forward_logits(wc, dims, seq)[-width:], axis=-1)
                best, at, logz, _ = _stats(logits, judged)
                rows.append((before, took, np.asarray(best), np.asarray(at),
                             np.asarray(best - logz)))
            out.append(rows)
        return out

    layouts = [stacked_layout(p, f, dims, clean_width, noisy_width)
               for p, f in requests]

    def forward(via):
        ends = round_weights(init_ends(key, dims), via)
        xs = [ends["embed"][jnp.asarray(lay[0])] for lay in layouts]
        for i in range(dims["n_layers"]):
            p = round_weights(init_layer(key, dims, i), via)
            xs = [layer(p, x, jnp.asarray(lay[2]), jnp.asarray(lay[1]), dims)
                  for x, lay in zip(xs, layouts)]
        return ends, [x[clean_width:] for x in xs]

    ends, xs = forward(weights_via)
    judged = [jnp.asarray(lay[3][clean_width:]) for lay in layouts]
    if control_via:
        ends_c, xs_c = forward(control_via)
        judged = [_head_stats(ends_c, x, j, dims)[3].astype(jnp.int32)
                  for x, j in zip(xs_c, judged)]
    out = []
    for x, j, lay in zip(xs, judged, layouts):
        best, at, logz, _ = (np.asarray(a)
                             for a in _head_stats(ends, x, j, dims))
        rows = []
        for i, (_, _, before, took, _) in enumerate(lay[4]):
            at_i = slice(i * width, (i + 1) * width)
            rows.append((before, took, best[at_i], at[at_i],
                         (best - logz)[at_i]))
        out.append(rows)
    return out
