"""Everything the benchmark knows of the flagship block
(``models/transformer_lm.py`` served by ``serve/engine.py``'s DecodeEngine).

A configuration file names this file under ``model``. The drivers, the
readers and the tests ask it, and import no program, reference or count by
name. What a model's file offers (``PERF.md`` section 3 says what each has to
return):

- ``dims_of``, ``seed_key``;
- the system under test: ``build_serve``, with ``serve_counters``,
  ``serve_steps`` and ``serve_programs`` that read what this engine keeps,
  and ``build_train``;
- the comparison that decides ``correct``: ``serve_compare`` from the
  sampled request objects, ``train_reference`` for the first steps;
- the required work: ``prefill_flops``, ``step_flops``, ``step_bytes``,
  ``train_flops_per_token``;
- ``program_scopes`` and ``faults``.

Its reference and its counts are files of its own, found through the
registry as this one is.
"""

from __future__ import annotations

import types

import numpy as np

from benchmark.harness import registry

ref = registry.load_part("reference", "flagship_ref")
counts = registry.load_part("counts", "flagship")

seed_key = ref.seed_key
dims_of = ref.dims_of  # the harness itself reads ``vocab`` alone


# -------------------------------------------------------------------- serve ----

def build_serve(config: dict, seed: int, metrics):
    """The engine on the normal path: weights made on the device in one
    jitted call from the seed, in the type they are served in. ``metrics``
    is the ``MetricsRegistry`` the engine counts into."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer_lm import init_lm_params
    from deeplearning4j_tpu.serve.engine import DecodeEngine

    d, s = dims_of(config), config["serve"]
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[s["serve_dtype"]]

    def make(key):
        p = init_lm_params(key, d["vocab"], d["d_model"], d["n_heads"],
                           d["n_experts"], d["d_ff"], d["n_layers"])
        return jax.tree_util.tree_map(lambda w: w.astype(dtype), p)

    params = jax.jit(make)(seed_key(seed))
    return DecodeEngine(
        params, d["n_heads"], n_slots=s["n_slots"], max_len=s["max_len"],
        top_k=d["top_k"], serve_dtype=s["serve_dtype"],
        min_bucket=s["min_bucket"], registry=metrics, tuned=False,
        runprof=False, seed=int(seed) & 0x7FFFFFFF)


def serve_counters(engine, metrics) -> dict:
    """The engine's running totals, under the names the readers know."""
    return {
        "decode_steps": engine.decode_steps,
        "occupancy_sum": engine._occupancy_sum,
        "prefill_dispatches": metrics.counter(
            "serve_prefill_dispatches_total").value,
        "decode_ms_sum": metrics.histogram(
            "serve_decode_step_ms").snapshot()["sum"],
        "prefill_ms_sum": metrics.histogram(
            "serve_prefill_ms").snapshot()["sum"],
    }


def serve_steps(requests: list) -> list:
    """``[[stamp, slots], ...]``: every decode step that gave a token to
    one of ``requests``, with what it did in each of their slots as
    ``[position written, tokens accepted]``. This engine keeps no record of
    a step, so the steps are rebuilt from the token stamps: the tokens of
    one step share one stamp, and a step accepts one token a live slot."""
    steps = {}
    for r in requests:
        for j, stamp in enumerate(r.t_tokens[1:]):
            steps.setdefault(stamp, []).append([len(r.prompt) + j, 1])
    return [[stamp, slots] for stamp, slots in steps.items()]


def serve_programs(engine, buckets: list) -> list:
    """The compiled text of the decode program and of the prefill program
    at each of ``buckets``: found in the compile cache, the same programs
    the window ran."""
    e = engine
    texts = [e._decode.lower(e.params, e._cache, e._tokens, e._positions,
                             e._temps, e._key, e._step_idx)
             .compile().as_text()]
    for b in buckets:
        texts.append(e._prefill.lower(
            e.params, e._cache, np.zeros((1, b), np.int32), b - 1, 0,
            np.float32(0.0), e._key, e._step_idx).compile().as_text())
    return texts


def serve_compare(config: dict, traffic: dict, seed: int, sampled: list,
                  control_via=None) -> dict:
    """{name: [number, limit]} over the sampled requests as the engine
    left them: how far a served token's logit lies below the reference's
    best, teacher-forced through the float32 reference. The widest such gap
    (a wrong token reads several units) and the mean (the noise of the
    arithmetic, which a lower precision multiplies). With ``control_via``
    the tokens judged are those the reference on weights of that type puts
    first. No finished request to follow reads as a gap no limit admits."""
    limits = config["correct"]
    gaps = _logit_gaps(config, traffic, seed, sampled, control_via) \
        if sampled else [1e30]
    return {"widest_logit_gap": [float(np.max(gaps)),
                                 float(limits["widest_logit_gap"])],
            "mean_logit_gap": [float(np.mean(gaps)),
                               float(limits["mean_logit_gap"])]}


def _logit_gaps(config, traffic, seed, sampled, control_via) -> np.ndarray:
    """One shape whatever the seed drew (``check_requests`` rows as wide
    as the mix's longest request can be), so that the reference compiles
    once a cell."""
    rows = [list(r.prompt) + list(r.generated) for r in sampled]
    n_rows = max(int(traffic["check_requests"]), len(rows))
    longest = int(traffic["prompt_len"]["max"]) \
        + int(traffic["answer_len"]["max"])
    width = min(-(-longest // 256) * 256, int(config["serve"]["max_len"]))
    tokens = np.zeros((n_rows, width), np.int32)
    lengths = np.ones(n_rows, np.int64)  # a spare row judges nothing
    prompts = np.ones(n_rows, np.int64)
    for i, (x, r) in enumerate(zip(rows, sampled)):
        tokens[i, :len(x)] = x
        lengths[i], prompts[i] = len(x), len(r.prompt)
    gaps = ref.serve_logit_gaps(
        seed, dims_of(config), tokens, lengths, prompts,
        weights_via=config["precision"]["weights"],
        control_via=control_via, span=int(traffic["answer_len"]["max"]))
    return np.concatenate(gaps)


# -------------------------------------------------------------------- train ----

def build_train(config: dict, traffic: dict, seed: int, devices: list):
    """The compiled step with its state on the normal path: the
    single-device step on one chip, the composed step on the mesh that
    ``config["train"]["mesh"]`` names. Returns the program and its
    parameters from the seed. The program holds ``step(params, tokens,
    targets) -> (params, loss)``, ``place(tokens, targets)`` (a batch as the
    step wants it), ``mesh`` or None, and ``grad_norms(params after one
    step)`` and ``change_norms(params)``: per leaf, the norm of the first
    gradient as the optimizer got it (plain SGD: the change over lr) and of
    the change since the seed's weights."""
    import jax

    from deeplearning4j_tpu.models import transformer_lm as lm

    d, tr = dims_of(config), config["train"]
    key = seed_key(seed)

    def init(k):
        return lm.init_lm_params(k, d["vocab"], d["d_model"], d["n_heads"],
                                 d["n_experts"], d["d_ff"], d["n_layers"])

    common = dict(lr=tr["lr"], top_k=d["top_k"], aux_weight=tr["aux_weight"],
                  donate=True, tuned=False, runprof=False)
    mesh = None
    if "mesh" in tr:
        from jax.sharding import Mesh

        shape = tuple(tr["mesh"].values())
        mesh = Mesh(np.array(devices).reshape(shape), tuple(tr["mesh"]))
        shardings = lm.lm_param_shardings(jax.eval_shape(init, key), mesh)
        params = jax.jit(init, out_shardings=shardings)(key)
        # every route of a data row fits one expert's buffer: no drops
        capacity = (int(traffic["batch_sequences"]) * int(traffic["seq_len"])
                    // tr["mesh"]["data"])
        step = lm.make_composed_train_step(mesh, d["n_heads"], capacity,
                                           **common)

        def place(tokens, targets):
            return lm.shard_lm_batch(tokens, targets, mesh)
    else:
        params = jax.jit(init)(key)
        step = lm.make_single_device_train_step(d["n_heads"], **common)

        def place(tokens, targets):
            return jax.device_put(tokens), jax.device_put(targets)

    norms = jax.jit(lambda p, k: ref.change_norms(p, k, init))

    def change_norms(p) -> dict:
        return {k: float(v) for k, v in norms(p, key).items()}

    def grad_norms(p) -> dict:
        return {k: v / tr["lr"] for k, v in change_norms(p).items()}

    return types.SimpleNamespace(step=step, place=place, mesh=mesh,
                                 grad_norms=grad_norms,
                                 change_norms=change_norms), params


def train_reference(config: dict, seed: int, batches: list, mesh=None,
                    control_via=None) -> dict:
    """The plain reference's first steps on ``batches``: ``losses``, and per
    leaf ``grad_norms`` and ``change_norms`` as ``build_train`` gives them
    for the program. With ``control_via`` the whole step runs in that type:
    the control that stands in the program's place."""
    import jax.numpy as jnp

    tr = config["train"]
    place = _mesh_placement(mesh, dims_of(config)) if mesh is not None \
        else None
    kw = {"compute_dtype": jnp.dtype(control_via)} if control_via else {}
    return ref.train_reference(seed, dims_of(config), batches, tr["lr"],
                               tr["aux_weight"], place=place, **kw)


def _mesh_placement(mesh, dims: dict) -> dict:
    """Where the plain reference keeps its arrays on the mesh: the experts'
    wide axis and the batch's rows over every chip, the rest whole on
    each. A placement, not another computation."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(mesh.axis_names)
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda k: ref.init_params(k, ref._Frozen(dims)),
                            seed_key(0))
    tree = jax.tree_util.tree_map(lambda _: rep, shapes)
    ex = tree["blocks"]["experts"]
    ex["w1"] = NamedSharding(mesh, P(None, None, None, axes))
    ex["b1"] = NamedSharding(mesh, P(None, None, axes))
    ex["w2"] = NamedSharding(mesh, P(None, None, axes, None))
    return {"params": tree, "batch": NamedSharding(mesh, P(axes, None)),
            "whole": rep, "chips": mesh.size}


# ------------------------------------------------------------ required work ----

prefill_flops = counts.prefill_flops
train_flops_per_token = counts.train_flops_per_token


def step_flops(dims: dict, slots: list) -> float:
    """Required operations of one decode step from its own record
    (``serve_steps``): one token a live slot, at the position it wrote."""
    return counts.decode_flops(dims, [p for p, _ in slots])


def step_bytes(dims: dict, slots: list) -> float:
    return counts.decode_step_bytes(dims, [p for p, _ in slots])


def program_scopes():
    """The scopes this model's programs name in their compiled text, or
    None for a program that names none (a commit before them)."""
    try:
        from deeplearning4j_tpu.models.transformer_lm import LM_SCOPES
    except ImportError:
        return None
    return LM_SCOPES


# ------------------------------------------------------------------- faults ----

def faults(cell: dict) -> dict:
    """{fault: ``tamper(driver)``}: each fault this cell can have, planted
    under the timed path of this model's program once the driver has built
    it. A serve driver holds the engine as ``driver.engine``, a train
    driver the step as ``driver.step``."""
    if cell["traffic_data"]["kind"] == "serve":
        return {"alter_a_token": _alter_a_token}
    out = {"state_unchanged": _state_unchanged,
           "half_the_batch": _half_the_batch}
    if cell["chips"] > 1:
        out["no_exchange"] = _no_exchange
    return out


def _alter_a_token(driver) -> None:
    """A token altered where it is produced: every third decode step hands
    back the next id for every slot."""
    decode, calls = driver.engine._decode, [0]
    vocab = driver.dims["vocab"]

    def broken(*args):
        cache, toks = decode(*args)
        calls[0] += 1
        if calls[0] % 3 == 0:
            toks = (toks + 1) % vocab
        return cache, toks

    driver.engine._decode = broken


def _state_unchanged(driver) -> None:
    import jax

    step = driver.step

    def copy(tree):  # the step donates
        return jax.tree_util.tree_map(lambda x: x + 0, tree)

    driver.step = lambda p, tok, tgt: (p, step(copy(p), tok, tgt)[1])


def _half_the_batch(driver) -> None:
    step = driver.step

    def broken(p, tok, tgt):
        half = tok.shape[0] // 2
        return step(p, tok[:half], tgt[:half])

    driver.step = broken


def _no_exchange(driver) -> None:
    """The exchange between chips left out: each data row's chips train
    alone, on the first row's batch, as if nothing crossed the mesh."""
    step = driver.step

    def broken(p, tok, tgt):
        import jax.numpy as jnp

        half = tok.shape[0] // 2
        return step(p, jnp.concatenate([tok[:half]] * 2),
                    jnp.concatenate([tgt[:half]] * 2))

    driver.step = broken
