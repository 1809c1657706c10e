"""Required operations and bytes of Granite-4.0-H, from shapes alone.

"Required" is what the mathematics needs, whatever the code of the day
computes: causal attention over the positions a query sees, the Mamba-2
recurrence's own work a token (the chunked scan's extra matmuls are the
code's choice, not the model's), logits only where a token is sampled,
nothing recomputed, padding not counted. A multiply-add is two operations.
``dims`` is ``reference.granite_ref.dims_of(config)``; weights are counted
at ``weight_bytes``, the recurrent state in float32.
"""

from __future__ import annotations


def _widths(dims: dict) -> tuple:
    h, p, n, g = (dims["ssm_heads"], dims["ssm_head_dim"], dims["ssm_state"],
                  dims["ssm_groups"])
    d_inner = h * p
    return h, p, n, g, d_inner, d_inner + 2 * g * n


def _layers(dims: dict, kind: str) -> int:
    return sum(k == kind for k in dims["kinds"])


def scan_token_flops(dims: dict) -> float:
    """One token through one layer's recurrence: the state's decay and
    outer-product update (3 an element), its product with C (2 an
    element), the D term."""
    h, p, n, _, _, _ = _widths(dims)
    return 5.0 * h * p * n + 2.0 * h * p


def mamba_token_flops(dims: dict) -> float:
    """One token through a Mamba-2 layer's mixer: the two projections, the
    conv, the recurrence."""
    d, k = dims["d_model"], dims["ssm_conv"]
    h, _, _, _, d_inner, conv_dim = _widths(dims)
    return (2 * d * (d_inner + conv_dim + h) + 2 * k * conv_dim
            + scan_token_flops(dims) + 2 * d_inner * d)


def attention_token_flops(dims: dict, context: float) -> float:
    """One token through an attention layer's mixer that sees ``context``
    positions (itself included)."""
    d = dims["d_model"]
    d_q = dims["n_heads"] * dims["head_dim"]
    d_kv = dims["n_kv_heads"] * dims["head_dim"]
    return 2 * d * (d_q + 2 * d_kv) + 2 * d_q * d + 2 * 2 * context * d_q


def mlp_token_flops(dims: dict) -> float:
    return 2 * dims["d_model"] * 2 * dims["d_ff"] \
        + 2 * dims["d_ff"] * dims["d_model"]


def head_flops(dims: dict) -> float:
    return 2 * dims["d_model"] * dims["vocab"]


def token_flops(dims: dict, context: float, logits: bool) -> float:
    """Forward operations of one token through every layer."""
    return (_layers(dims, "mamba") * mamba_token_flops(dims)
            + _layers(dims, "attention") * attention_token_flops(dims,
                                                                 context)
            + dims["n_layers"] * mlp_token_flops(dims)
            + (head_flops(dims) if logits else 0.0))


def prefill_flops(dims: dict, n: int) -> float:
    """A prompt of ``n`` real tokens: causal contexts 1..n, logits for the
    last position only."""
    return n * token_flops(dims, (n + 1) / 2.0, logits=False) \
        + head_flops(dims)


def decode_flops(dims: dict, positions) -> float:
    """One decode step over the live slots; ``positions`` are the cache
    positions their new tokens are written at."""
    return sum(token_flops(dims, p + 1, logits=True) for p in positions)


def weight_count(dims: dict) -> float:
    """Parameters of the whole model (the head is the embedding)."""
    d, f, k = dims["d_model"], dims["d_ff"], dims["ssm_conv"]
    h, _, _, _, d_inner, conv_dim = _widths(dims)
    d_q = dims["n_heads"] * dims["head_dim"]
    d_kv = dims["n_kv_heads"] * dims["head_dim"]
    mixer = (d * (d_inner + conv_dim + h) + k * conv_dim + conv_dim
             + 3 * h + d_inner + d_inner * d)
    attention = d * (d_q + 2 * d_kv) + d_q * d
    per_layer = 3 * d * f + 2 * d
    return (_layers(dims, "mamba") * mixer
            + _layers(dims, "attention") * attention
            + dims["n_layers"] * per_layer + dims["vocab"] * d + d)


def state_bytes_slot(dims: dict, act_bytes: int = 2) -> float:
    """One slot's recurrent state (float32) and conv state (``act_bytes``
    an element), every state-space layer."""
    h, p, n, _, _, conv_dim = _widths(dims)
    return _layers(dims, "mamba") * (h * p * n * 4
                                     + (dims["ssm_conv"] - 1) * conv_dim
                                     * act_bytes)


def decode_step_bytes(dims: dict, positions, weight_bytes: int = 2,
                      act_bytes: int = 2) -> float:
    """Bytes one decode step has to move: every weight once, each live
    slot's state read and written, the K/V of its live positions read and
    its new row written, its embedding row."""
    live = len(positions)
    if not live:
        return 0.0
    kv_row = 2 * dims["n_kv_heads"] * dims["head_dim"] * act_bytes
    kv = _layers(dims, "attention") * sum((p + 2) * kv_row
                                          for p in positions)
    return (weight_count(dims) * weight_bytes
            + live * (2 * state_bytes_slot(dims, act_bytes)
                      + dims["d_model"] * weight_bytes) + kv)


# ---- the recurrence alone: what ``ssm_scan`` and ``ssm_step`` do ----

def scan_flops(dims: dict, n: int) -> float:
    """The recurrence over a prompt of ``n`` real tokens, every layer."""
    return _layers(dims, "mamba") * n * scan_token_flops(dims)


def scan_bytes(dims: dict, n: int) -> float:
    """Its inputs (x, dt, B, C) read and its output y written in float32 a
    real token, and the state it hands over written, every layer."""
    h, p, s, g, _, _ = _widths(dims)
    per_token = (2 * h * p + h + 2 * g * s) * 4
    return _layers(dims, "mamba") * (n * per_token + h * p * s * 4)


def state_step_flops(dims: dict, positions) -> float:
    """The one-token update of every live slot's state, every layer."""
    return _layers(dims, "mamba") * len(positions) * scan_token_flops(dims)


def state_step_bytes(dims: dict, positions) -> float:
    """Every live slot's state read and written in float32, every layer
    (the row's x, dt, B, C and y are a thousandth of it and left out)."""
    h, p, n, _, _, _ = _widths(dims)
    return _layers(dims, "mamba") * len(positions) * 2 * h * p * n * 4
