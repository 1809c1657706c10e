"""Drives the train step that the cell's model builds
(``models/<model>.py``: ``build_train``), on one chip or on a mesh.

Set-up builds one object (the compiled step with its parameters), drives it
from the seed through its first three steps on the window's own feed, and
hands that same object to the window. The model's plain reference follows
those three steps after the window has closed.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmark.harness import registry

FIRST_STEPS = 3


class Driver:
    kind = "train"

    def __init__(self, cell: dict, seed: int, devices: list):
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.seed = int(seed)
        self.devices = devices
        self.model = registry.load_model(cell)
        self.dims = self.model.dims_of(self.config)
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
        t = self.traffic
        self.batch, self.seq = int(t["batch_sequences"]), int(t["seq_len"])
        rng = np.random.default_rng([self.seed, 0xDA7A])
        rows = rng.integers(0, self.dims["vocab"], size=(
            int(t["pool"]), self.batch, self.seq + 1), dtype=np.int32)
        self.pool = [(r[:, :-1], r[:, 1:]) for r in rows]  # rows all differ
        self.program, self.params = self.model.build_train(
            self.config, t, self.seed, self.devices)
        self.step, self.mesh = self.program.step, self.program.mesh
        self.n_fed = 0

    def feed(self):
        """The next batch of the pool, placed as the step wants it."""
        tokens, targets = self.pool[self.n_fed % len(self.pool)]
        self.n_fed += 1
        return self.program.place(tokens, targets)

    def first_steps(self) -> None:
        self.first = {"losses": []}
        for i in range(FIRST_STEPS):
            self.params, loss = self.step(self.params, *self.feed())
            self.first["losses"].append(float(loss))
            if i == 0:
                self.first["grad_norms"] = self.program.grad_norms(
                    self.params)
        self.first["change_norms"] = self.program.change_norms(self.params)

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

        self.params = self.step = self.program = None
        gc.collect()
        jax.clear_caches()

    def sample(self, rec: dict) -> list:
        return self.pool[:FIRST_STEPS]

    def check(self, rec: dict, batches: list, control_via=None) -> tuple:
        """After ``release``: the program's first three steps against the
        reference's. With ``control_via`` the reference in that type stands
        in the program's place."""
        def reference(**kw):
            return self.model.train_reference(self.config, self.seed,
                                              batches, self.mesh, **kw)

        want = reference()
        got = reference(control_via=control_via) if control_via \
            else self.first
        limits = self.config["correct"]
        numbers = compare_steps(got, want)
        compared = {k: [v, float(limits[k])] for k, v in numbers.items()}
        _, failed = self.attempted_failed(rec)
        compared["steps_not_finite"] = [float(failed), 0.0]
        return all(v <= lim for v, lim in compared.values()), compared


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
