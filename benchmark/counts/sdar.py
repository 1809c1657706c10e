"""Required operations and bytes of the SDAR block served by block diffusion,
from shapes and a step's own record alone.

"Required" is what the configuration's mathematics and schedule need,
whatever the code of the day computes: the k chosen experts of a position and
not all E, a K/V head once for the query heads that share it, logits only in
a denoising forward. The schedule is the configuration's too: D denoising
forwards and one commit a block. A step's record lists the slot-forwards it
ran, each ``[first row, positions forwarded, tokens accepted, kind]``, and a
forward is counted as what its kind requires, so a program that runs fewer
forwards a block reports less required work and gains no utilisation by it.
No argument names an implementation. A multiply-add is two operations.
``dims`` is ``reference.sdar_ref.dims_of(config)``.
"""

from __future__ import annotations


def layer_flops_position(dims: dict, context: float) -> float:
    """One layer's forward operations for one position that attends to
    ``context`` rows (its own block included)."""
    d, e, f, k = (dims["d_model"], dims["n_experts"], dims["d_ff"],
                  dims["top_k"])
    h, h_kv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    projections = 2 * d * hd * (2 * h + 2 * h_kv)  # q and o; k and v
    qk_norm = 4 * hd * (h + h_kv)    # square, mean, scale, gain an element
    rotary = 3 * hd * (h + h_kv)     # two products and a sum an element
    attention = 2 * 2 * context * h * hd           # q.k and p.v, all heads
    router = 2 * d * e
    experts = k * 3 * 2 * d * f      # gate, up, down of the chosen k only
    return projections + qk_norm + rotary + attention + router + experts


def head_flops(dims: dict) -> float:
    return 2 * dims["d_model"] * dims["vocab"]


def forward_flops(dims: dict, first_row: int, positions: int,
                  kind: str) -> float:
    """One slot-forward: ``positions`` positions from ``first_row`` on, each
    seeing the rows before the block and the block; the head only where a
    token can be taken."""
    per = dims["n_layers"] * layer_flops_position(dims, first_row + positions)
    if kind == "denoise":
        per += head_flops(dims)
    return positions * per


def step_flops(dims: dict, slots) -> float:
    return sum(forward_flops(dims, row, n, kind) for row, n, _, kind in slots)


def prefill_flops(dims: dict, n: int) -> float:
    """A prompt of ``n`` tokens: its ``n // B`` whole blocks are stored,
    block j's positions seeing (j + 1) B rows; nothing is sampled."""
    width = dims["block_length"]
    return sum(width * dims["n_layers"]
               * layer_flops_position(dims, (j + 1) * width)
               for j in range(n // width))


def expected_distinct_experts(dims: dict, positions: int) -> float:
    """Experts that ``positions`` positions reach under uniform routing,
    each choosing k distinct of E: E (1 - (1 - k / E) ** positions)."""
    e, k = dims["n_experts"], dims["top_k"]
    return e * (1.0 - (1.0 - k / e) ** positions)


def step_bytes(dims: dict, slots, weight_bytes: int = 2,
               kv_bytes: int = 2) -> float:
    """Bytes one step has to move: each weight once (the experts: the
    expected distinct ones for the step's positions under uniform routing,
    which is what random weights give), the K/V rows each forward reads and
    the rows it writes, an embedding row a position, the head once if any
    forward takes tokens."""
    if not slots:
        return 0.0
    d, e, f, v, n_layers = (dims["d_model"], dims["n_experts"], dims["d_ff"],
                            dims["vocab"], dims["n_layers"])
    h, h_kv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    positions = sum(n for _, n, _, _ in slots)
    per_layer = (d * hd * (2 * h + 2 * h_kv) + d * e + 2 * d + 2 * hd) \
        * weight_bytes
    per_layer += expected_distinct_experts(dims, positions) * 3 * d * f \
        * weight_bytes
    row = 2 * h_kv * hd * kv_bytes                 # one position's K and V
    kv = sum((first + n) * row + n * row for first, n, _, _ in slots)
    ends = positions * d * weight_bytes
    if any(kind == "denoise" for *_, kind in slots):
        ends += (d * v + d) * weight_bytes
    return n_layers * (per_layer + kv) + ends
