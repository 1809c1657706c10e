"""Required operations and bytes of the block against a hand count at a tiny
shape: the top-k experts and not all E, and no argument that could tell
``dense_moe`` from routed dispatch."""

import inspect
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.counts import flagship as counts  # noqa: E402
from benchmark.harness.peaks import roofline_seconds  # noqa: E402

DIMS = {"vocab": 10, "d_model": 4, "n_heads": 2, "n_experts": 8, "d_ff": 3,
        "top_k": 2, "n_layers": 2}


def hand_layer(context):
    projections = 4 * (2 * 4 * 4)        # q, k, v, o: d x d each
    attention = 2 * (2 * context * 4)    # scores and values over d
    router = 2 * 4 * 8
    experts = 2 * (2 * 4 * 3 + 2 * 3 * 4)  # 2 chosen experts, two matrices
    return projections + attention + router + experts


def test_one_token_by_hand():
    assert counts.layer_flops_token(DIMS, 5) == hand_layer(5) == 368
    assert counts.token_flops(DIMS, 5, logits=True) == 2 * 368 + 2 * 4 * 10
    assert counts.token_flops(DIMS, 5, logits=False) == 2 * 368


def test_experts_count_top_k_and_not_all():
    more = dict(DIMS, n_experts=64)
    grown = counts.layer_flops_token(more, 5) - counts.layer_flops_token(
        DIMS, 5)
    assert grown == 2 * 4 * (64 - 8)  # the router's columns alone
    twice = dict(DIMS, top_k=4)
    assert counts.layer_flops_token(twice, 5) - hand_layer(5) == 2 * 48


@pytest.mark.parametrize("fn", [counts.layer_flops_token, counts.token_flops,
                                counts.train_flops_per_token,
                                counts.prefill_flops, counts.decode_flops,
                                counts.decode_step_bytes])
def test_no_count_knows_an_implementation(fn):
    """The same count for ``dense_moe`` as for routed dispatch: a count
    takes shapes and positions and nothing that names the code."""
    names = set(inspect.signature(fn).parameters)
    assert names <= {"dims", "context", "logits", "seq_len", "n",
                     "positions", "weight_bytes", "kv_bytes"}


def test_train_prefill_and_decode_by_hand():
    # T = 3: contexts 1, 2, 3, mean 2; every position scored; x3 for backward
    per_token = 2 * hand_layer(2) + 2 * 4 * 10
    assert counts.train_flops_per_token(DIMS, 3) == 3 * per_token
    # a prompt of 3: the same contexts, logits for the last position only
    assert counts.prefill_flops(DIMS, 3) == 3 * 2 * hand_layer(2) + 80
    # two live slots writing positions 4 and 0: contexts 5 and 1
    assert counts.decode_flops(DIMS, [4, 0]) == (
        2 * hand_layer(5) + 80 + 2 * hand_layer(1) + 80)


def test_decode_bytes_by_hand():
    positions = [4, 0]
    layer = (4 * 4 * 4 + 4 * 8 + 4 * 4) * 2           # attention, router, norms
    layer += min(8, 2 * 2) * (2 * 4 * 3 + 3 + 4) * 2  # 4 experts can be hit
    kv = (2 * 5 * 4 + 2 * 1 * 4) * 2
    ends = (2 * 4 + 4 * 10 + 10) * 2
    assert counts.decode_step_bytes(DIMS, positions) == 2 * (layer + kv) + ends
    # 40 live tokens x 2 could hit 80 experts: no more than the 8 there are
    many = counts.decode_step_bytes(DIMS, [0] * 40)
    one = counts.decode_step_bytes(DIMS, [0] * 4)
    assert many - one == 36 * (2 * 2 * 4 * 2 + 4 * 2)
    assert counts.decode_step_bytes(DIMS, []) == 0.0


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline_seconds(200.0, 10.0, peaks) == 2.0
    assert roofline_seconds(200.0, 50.0, peaks) == 5.0
