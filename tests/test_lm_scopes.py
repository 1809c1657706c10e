"""ISSUE 25: the ``lm_*`` named scopes reach the compiled text of every
flagship program, forward and backward. The benchmark maps a device trace
back to the model through exactly that text (``scopes_from_hlo``), so a
scope that does not survive compilation measures nothing. Tiny sizes, CPU.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.trace.reduce import scopes_from_hlo  # noqa: E402
from deeplearning4j_tpu.models import transformer_lm as lm  # noqa: E402

V, D, H, E, DFF, L = 61, 16, 2, 4, 32, 2
SLOTS, MAXLEN = 3, 32
SERVE = {"lm_embed", "lm_attn", "lm_cache_write", "lm_moe", "lm_sample"}
TRAIN = {"lm_embed", "lm_attn", "lm_moe", "lm_loss", "lm_update"}


@pytest.fixture(scope="module")
def params():
    return lm.init_lm_params(jax.random.PRNGKey(0), V, D, H, E, DFF,
                             n_layers=L)


def _serve_text(program: str, params) -> str:
    cache = lm.init_kv_cache(L, SLOTS, H, D // H, MAXLEN)
    key = jax.random.PRNGKey(1)
    zeros = np.zeros(SLOTS, np.int32)
    if program == "decode":
        lowered = lm.make_decode_step(H, 2, donate_cache=False).lower(
            params, cache, zeros, zeros, np.zeros(SLOTS, np.float32), key, 0)
    elif program == "verify":
        lowered = lm.make_verify_step(H, 2, donate_cache=False).lower(
            params, cache, np.zeros((SLOTS, 3), np.int32), zeros,
            np.zeros(SLOTS, np.float32), key, 0)
    elif program == "prefill":
        lowered = lm.make_prefill_step(H, 2, donate_cache=False).lower(
            params, cache, np.zeros((1, 8), np.int32), 3, 1, np.float32(0),
            key, 0)
    else:
        lowered = lm.make_chunk_prefill_step(H, 2, donate_cache=False).lower(
            params, cache, np.zeros((1, 8), np.int32), np.int32(0),
            np.int32(3), np.int32(1), np.float32(0), key, 0)
    return lowered.compile().as_text()


def _lm_names(op_names) -> set:
    return {m for op in op_names for m in re.findall(r"lm_[a-z_]+", op)}


@pytest.mark.parametrize("program", ["decode", "prefill", "chunk", "verify"])
def test_serve_programs_carry_their_scopes(program, params):
    ops = scopes_from_hlo(_serve_text(program, params)).values()
    assert _lm_names(ops) == SERVE
    # the cache write lies inside the attention half of the decode block
    if program != "prefill":
        assert any("lm_attn/lm_cache_write" in op for op in ops)


@pytest.mark.parametrize("build", ["sgd", "sgd_metrics", "opt"])
def test_train_step_carries_its_scopes_forward_and_backward(build, params):
    toks = jnp.zeros((2, 8), jnp.int32)
    if build == "opt":
        from deeplearning4j_tpu.optimize.updaters import OptimizerConfig

        opt = OptimizerConfig(name="adam")
        step = lm.make_single_device_train_step(H, lr=0.1, optimizer=opt)
        args = (params, lm.init_lm_opt_state(opt, params), toks, toks)
    else:
        step = lm.make_single_device_train_step(
            H, lr=0.1, with_metrics=build == "sgd_metrics")
        args = (params, toks, toks)
    ops = list(scopes_from_hlo(step.lower(*args).compile().as_text())
               .values())
    assert _lm_names(ops) == TRAIN
    backward = [op for op in ops if "transpose(jvp(" in op]
    assert _lm_names(backward) >= {"lm_embed", "lm_attn", "lm_moe",
                                   "lm_loss"}
    forward = [op for op in ops if "jvp(" not in op]
    assert "lm_update" in _lm_names(forward)


def test_no_other_scope_begins_with_lm():
    """The needle ``lm_`` means "under any of LM_SCOPES": no named scope
    elsewhere in the package may begin with it."""
    package = os.path.join(REPO, "deeplearning4j_tpu")
    found = set()
    for root, _, files in os.walk(package):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as fh:
                    found |= set(re.findall(
                        r'named_scope\(\s*f?["\'](lm_[^"\']*)', fh.read()))
    assert found == set(lm.LM_SCOPES)


def test_mesh_step_keeps_the_exchange_inside_lm_moe(params):
    """On the (data, expert) mesh ``moe_apply`` lies inside ``lm_moe`` with
    its ``moe_all2all_*`` scopes intact, so the benchmark's
    ``moe_all2all_exposed_pct`` still finds them."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "expert"))
    toks = jnp.zeros((4, 8), jnp.int32)
    sharded = lm.shard_lm_params(params, mesh)
    stoks, stgts = lm.shard_lm_batch(toks, toks, mesh)
    step = lm.make_composed_train_step(mesh, H, capacity=16,
                                       moe_impl="alltoall")
    ops = list(scopes_from_hlo(step.lower(sharded, stoks, stgts).compile()
                               .as_text()).values())
    assert _lm_names(ops) == TRAIN
    for needle in ("moe_all2all_dispatch", "moe_all2all_return"):
        inside = [op for op in ops if needle in op]
        assert inside and all("lm_moe" in op for op in inside)
