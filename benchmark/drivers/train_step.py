"""Drives the train step that ``models/transformer_lm.py`` builds: the
single-device step on one chip, the composed step on a mesh.

Set-up builds one object (the compiled step with its parameters), drives it
from the seed through its first three steps on the window's own feed, and
hands that same object to the window. The reference follows those three
steps after the window has closed.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmark.reference import flagship_ref as ref

FIRST_STEPS = 3


class Driver:
    kind = "train"

    def __init__(self, cell: dict, seed: int, devices: list):
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.seed = int(seed)
        self.devices = devices
        self.dims = ref.dims_of(self.config)
        self.mesh = None
        self.params = None

    # -- set-up ---------------------------------------------------------------
    def setup(self, tamper=None) -> dict:
        """``tamper(driver)`` lets a test break the timed path once it is
        built and before anything runs through it."""
        self.build()
        if tamper is not None:
            tamper(self)
        t0 = time.perf_counter()
        self.first_steps()
        return {"warmup_s": time.perf_counter() - t0}

    def build(self) -> None:
        import jax

        from deeplearning4j_tpu.models import transformer_lm as lm

        d, t, tr = self.dims, self.traffic, self.config["train"]
        self.batch, self.seq = int(t["batch_sequences"]), int(t["seq_len"])
        rng = np.random.default_rng([self.seed, 0xDA7A])
        rows = rng.integers(0, d["vocab"], size=(
            int(t["pool"]), self.batch, self.seq + 1), dtype=np.int32)
        self.pool = [(r[:, :-1], r[:, 1:]) for r in rows]  # rows all differ

        def init(key):
            return lm.init_lm_params(key, d["vocab"], d["d_model"],
                                     d["n_heads"], d["n_experts"], d["d_ff"],
                                     d["n_layers"])

        self.init = init
        key = ref.seed_key(self.seed)
        common = dict(lr=tr["lr"], top_k=d["top_k"],
                      aux_weight=tr["aux_weight"], donate=True, tuned=False,
                      runprof=False)
        if "mesh" in tr:
            from jax.sharding import Mesh

            shape = tuple(tr["mesh"].values())
            self.mesh = Mesh(np.array(self.devices).reshape(shape),
                             tuple(tr["mesh"]))
            shardings = lm.lm_param_shardings(jax.eval_shape(init, key),
                                              self.mesh)
            self.params = jax.jit(init, out_shardings=shardings)(key)
            # every route of a data row fits one expert's buffer: no drops
            capacity = self.batch * self.seq // tr["mesh"]["data"]
            self.step = lm.make_composed_train_step(
                self.mesh, d["n_heads"], capacity, **common)
        else:
            self.params = jax.jit(init)(key)
            self.step = lm.make_single_device_train_step(d["n_heads"],
                                                         **common)
        self.norms = jax.jit(lambda p, k: ref.change_norms(p, k, init))
        self.n_fed = 0

    def feed(self):
        """The next batch of the pool, placed as the step wants it."""
        import jax

        from deeplearning4j_tpu.models.transformer_lm import shard_lm_batch

        tokens, targets = self.pool[self.n_fed % len(self.pool)]
        self.n_fed += 1
        if self.mesh is not None:
            return shard_lm_batch(tokens, targets, self.mesh)
        return jax.device_put(tokens), jax.device_put(targets)

    def first_steps(self) -> None:
        lr = self.config["train"]["lr"]
        key = ref.seed_key(self.seed)
        self.first = {"losses": []}
        for i in range(FIRST_STEPS):
            self.params, loss = self.step(self.params, *self.feed())
            self.first["losses"].append(float(loss))
            if i == 0:
                self.first["grad_norms"] = {
                    k: float(v) / lr
                    for k, v in self.norms(self.params, key).items()}
        self.first["change_norms"] = {
            k: float(v) for k, v in self.norms(self.params, key).items()}

    # -- the window -----------------------------------------------------------
    def window(self, seconds: float, traced, rate_scale: float = 1.0) -> dict:
        import jax

        t0 = time.perf_counter()
        traced.begin(t0, seconds)
        steps, last, stamps = 0, None, []
        while True:
            self.params, loss = self.step(self.params, *self.feed())
            steps += 1
            if last is not None:
                # one step stays in flight while the host waits for the
                # one before it, so the device never waits for the host
                jax.block_until_ready(last)
                stamps.append(time.perf_counter())
                traced.tick()
                if stamps[-1] - t0 >= seconds:
                    break
            last = loss
        jax.block_until_ready(loss)
        t1 = time.perf_counter()
        stamps.append(t1)
        traced.finish()
        return {"t0": t0, "t1": t1, "steps": steps, "stamps": stamps,
                "last_loss": float(loss), "traced": (traced.t0, traced.t1)}

    def end_to_end(self, rec: dict) -> dict:
        tokens = rec["steps"] * self.batch * self.seq
        return {"train_tokens_per_s": tokens / (rec["t1"] - rec["t0"])}

    def attempted_failed(self, rec: dict) -> tuple:
        return rec["steps"], 0 if np.isfinite(rec["last_loss"]) else 1

    def summary(self, rec: dict) -> dict:
        scopes = None
        if rec["traced"][0] is not None and hasattr(self.step, "lower"):
            from benchmark.trace.reduce import scopes_from_hlo

            # found in the cache: the same program the window ran
            scopes = scopes_from_hlo(self.step.lower(
                self.params, *self.feed()).compile().as_text())
        return {"kind": "train", "scopes": scopes, "t0": rec["t0"], "t1": rec["t1"],
                "steps": rec["steps"], "stamps": rec["stamps"],
                "traced": rec["traced"], "dims": self.dims,
                "batch": self.batch, "seq": self.seq,
                "seconds": rec["t1"] - rec["t0"]}

    # -- correctness ------------------------------------------------------------
    def release(self) -> None:
        import jax

        self.params = self.step = self.norms = None
        gc.collect()
        jax.clear_caches()

    def sample(self, rec: dict) -> list:
        return self.pool[:FIRST_STEPS]

    def reference(self, batches: list, **kw) -> dict:
        place = None
        if self.mesh is not None:
            place = mesh_placement(self.mesh, self.dims)
        tr = self.config["train"]
        return ref.train_reference(self.seed, self.dims, batches, tr["lr"],
                                   tr["aux_weight"], place=place, **kw)

    def check(self, rec: dict, batches: list, control_via=None) -> tuple:
        """After ``release``: the program's first three steps against the
        reference's. With ``control_via`` the reference in that type stands
        in the program's place."""
        import jax.numpy as jnp

        want = self.reference(batches)
        got = self.first
        if control_via:
            got = self.reference(batches,
                                 compute_dtype=jnp.dtype(control_via))
        limits = self.config["correct"]
        numbers = compare_steps(got, want)
        compared = {k: [v, float(limits[k])] for k, v in numbers.items()}
        _, failed = self.attempted_failed(rec)
        compared["steps_not_finite"] = [float(failed), 0.0]
        return all(v <= lim for v, lim in compared.values()), compared


def mesh_placement(mesh, dims: dict) -> dict:
    """Where the plain reference keeps its arrays on the mesh: the experts'
    wide axis and the batch's rows over every chip, the rest whole on
    each. A placement, not another computation."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(mesh.axis_names)
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda k: ref.init_params(k, ref._Frozen(dims)),
                            ref.seed_key(0))
    tree = jax.tree_util.tree_map(lambda _: rep, shapes)
    ex = tree["blocks"]["experts"]
    ex["w1"] = NamedSharding(mesh, P(None, None, None, axes))
    ex["b1"] = NamedSharding(mesh, P(None, None, axes))
    ex["w2"] = NamedSharding(mesh, P(None, None, axes, None))
    return {"params": tree, "batch": NamedSharding(mesh, P(axes, None)),
            "whole": rep, "chips": mesh.size}


def worst_leaf_gap(got: dict, want: dict, skip=()) -> float:
    """The widest gap between the two norms of one leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but nought)."""
    median = statistics.median(want.values())
    return max(abs(got[k] - want[k]) / max(want[k], median)
               for k in want if k not in skip)


def compare_steps(got: dict, want: dict) -> dict:
    """The three numbers a train cell is held to."""
    median = statistics.median(want["grad_norms"].values())
    # a leaf whose gradient is nought to rounding moves by round-off alone
    still = {k for k, v in want["grad_norms"].items() if v < 1e-3 * median}
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in
                        zip(got["losses"], want["losses"])),
        "grad_norm_gap": worst_leaf_gap(got["grad_norms"],
                                        want["grad_norms"]),
        "change_norm_gap": worst_leaf_gap(got["change_norms"],
                                          want["change_norms"], still),
    }
