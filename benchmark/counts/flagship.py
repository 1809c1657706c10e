"""Required operations and bytes of the flagship block, from shapes alone.

"Required" is what the mathematics needs, whatever the code of the day
computes: the top-k experts of a token and not all E, the causal half of the
attention scores, logits only where a token is sampled or scored, nothing
recomputed. So the counts read the same when routed dispatch replaces
``dense_moe`` or a length-aware kernel replaces ``_decode_block``: there is
no argument that names an implementation. A multiply-add is two operations.
``dims`` is ``reference.flagship_ref.dims_of(config)``.
"""

from __future__ import annotations


def layer_flops_token(dims: dict, context: float) -> float:
    """One layer's forward operations for one token that attends to
    ``context`` positions (itself included)."""
    d, e, f, k = (dims["d_model"], dims["n_experts"], dims["d_ff"],
                  dims["top_k"])
    projections = 4 * 2 * d * d            # q, k, v, o
    attention = 2 * 2 * context * d        # q.k and p.v over all heads
    router = 2 * d * e
    experts = k * (2 * d * f + 2 * f * d)  # the chosen k only
    return projections + attention + router + experts


def head_flops(dims: dict) -> float:
    return 2 * dims["d_model"] * dims["vocab"]


def token_flops(dims: dict, context: float, logits: bool) -> float:
    """Forward operations of one token through every layer."""
    return (dims["n_layers"] * layer_flops_token(dims, context)
            + (head_flops(dims) if logits else 0.0))


def train_flops_per_token(dims: dict, seq_len: int) -> float:
    """Forward and backward of one token of a packed causal sequence: the
    mean context is (T + 1) / 2, every position is scored, the backward
    pass costs twice the forward."""
    return 3.0 * token_flops(dims, (seq_len + 1) / 2.0, logits=True)


def prefill_flops(dims: dict, n: int) -> float:
    """A prompt of ``n`` real tokens (padding is not required work): causal
    contexts 1..n, logits for the last position only."""
    return (n * token_flops(dims, (n + 1) / 2.0, logits=False)
            + head_flops(dims))


def decode_flops(dims: dict, positions) -> float:
    """One decode step over the live slots; ``positions`` are the cache
    positions their new tokens are written at."""
    return sum(token_flops(dims, p + 1, logits=True) for p in positions)


def decode_step_bytes(dims: dict, positions, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read: the weights outside the experts,
    the experts its live tokens can hit (at most live x top-k of E), the keys
    and values of the live positions, one embedding row a live token."""
    d, e, f, k, v, n_layers = (dims["d_model"], dims["n_experts"],
                               dims["d_ff"], dims["top_k"], dims["vocab"],
                               dims["n_layers"])
    live = len(positions)
    if not live:
        return 0.0
    per_layer = (4 * d * d + d * e + 4 * d) * weight_bytes
    per_layer += min(e, live * k) * (2 * d * f + f + d) * weight_bytes
    kv = sum(2 * (p + 1) * d * kv_bytes for p in positions)
    ends = (live * d + d * v + v) * weight_bytes
    return n_layers * (per_layer + kv) + ends
