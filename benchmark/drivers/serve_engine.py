"""Drives a serving engine in-process with generated traffic: one thread
that submits what is due and calls ``engine.step()``. The engine, its
counters, its steps' records and the comparison that decides ``correct`` are
the cell's model's (``models/<model>.py``); of the engine this file uses
``submit(prompt, max_new_tokens=)``, ``step()``, ``has_work()``,
``run_until_idle()``, ``bucket_for(n)``, ``n_slots`` and ``max_len``, and of a
request its stamps (``t_submit``, ``t_admit``, ``t_first``, ``t_done``,
``t_tokens``), ``prompt``, ``generated`` and ``done``.

The traffic generator is general: a traffic file gives the loop (open at a
fixed rate, or closed with a fixed number of clients) and the two length
distributions. Lengths are the inverse CDF over an evenly spaced grid that
the seed only shuffles, and an open loop's arrivals are a fixed count of
uniform draws over the window (a Poisson process given its count), so every
seed offers the same work and only order and timing differ.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from benchmark.harness import registry
from benchmark.trace.reduce import scopes_by_program

DRAIN_SECONDS = 60.0
_NORMAL = statistics.NormalDist()


# ----------------------------------------------------------------- traffic ----

def _inverse_cdf(spec: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = float(spec["min"]), float(spec["max"])
    if spec["dist"] == "lognormal":
        q = np.array([_NORMAL.inv_cdf(float(v)) for v in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * q)
    elif spec["dist"] == "loguniform":
        x = lo * (hi / lo) ** u
    elif spec["dist"] == "uniform":
        x = lo + (hi - lo) * u
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def length_grid(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n: the histogram every
    seed offers."""
    return _inverse_cdf(spec, (np.arange(n) + 0.5) / n)


class Traffic:
    """Requests from the seed. ``take(j)`` is request j's (prompt tokens,
    answer length); grids are walked in shuffled order and shuffled again
    each time they run out."""

    def __init__(self, spec: dict, seed: int, vocab: int, grid: int):
        self.rng = np.random.default_rng([int(seed), 0x5EED])
        self.vocab = vocab
        self.grid = grid
        self.prompts = length_grid(spec["prompt_len"], grid)
        self.answers = length_grid(spec["answer_len"], grid)
        self._order = None

    def take(self, j: int):
        if j % self.grid == 0:
            self._order = (self.rng.permutation(self.grid),
                           self.rng.permutation(self.grid))
        i = j % self.grid
        n = int(self.prompts[self._order[0][i]])
        prompt = self.rng.integers(0, self.vocab, size=n).tolist()
        return prompt, int(self.answers[self._order[1][i]])


def open_arrivals(seed: int, n: int, seconds: float) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0xA771])
    return np.sort(rng.uniform(0.0, seconds, size=n))


# ------------------------------------------------------------------ driver ----

class Driver:
    kind = "serve"

    def __init__(self, cell: dict, seed: int, devices: list):
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.seed = int(seed)
        self.devices = devices
        self.model = registry.load_model(cell)
        self.dims = self.model.dims_of(self.config)
        self.engine = None

    # -- set-up ---------------------------------------------------------------
    def setup(self, tamper=None) -> dict:
        """``tamper(driver)`` lets a test break the timed path once it is
        built and before anything runs through it."""
        from deeplearning4j_tpu.telemetry.registry import MetricsRegistry

        self.registry = MetricsRegistry()
        self.engine = self.model.build_serve(self.config, self.seed,
                                             self.registry)
        if tamper is not None:
            tamper(self)
        t0 = time.perf_counter()
        self._warm_up()
        return {"warmup_s": time.perf_counter() - t0}

    def _grid(self) -> int:
        return int(self.traffic.get("grid", 256))

    def _buckets(self) -> list:
        """The prefill buckets this traffic can reach."""
        lens = length_grid(self.traffic["prompt_len"], self._grid())
        return sorted({self.engine.bucket_for(int(n)) for n in lens})

    def _warm_up(self) -> None:
        """One request through each prefill bucket this traffic can reach,
        and the decode step: nothing else is compiled."""
        e = self.engine
        rng = np.random.default_rng(0)
        for b in self._buckets():
            n = min(b, e.max_len - 1)
            e.submit(rng.integers(0, self.dims["vocab"], size=n).tolist(),
                     max_new_tokens=2)
        e.run_until_idle()

    # -- the window -----------------------------------------------------------
    def window(self, seconds: float, traced, rate_scale: float = 1.0) -> dict:
        t = self.traffic
        traffic = Traffic(t, self.seed, self.dims["vocab"], self._grid())
        e = self.engine
        before = self._counters()
        ticks = []
        if t["loop"] == "open":
            n = int(round(float(t["rate_per_s"]) * rate_scale * seconds))
            traffic.grid = n
            traffic.prompts = length_grid(t["prompt_len"], n)
            traffic.answers = length_grid(t["answer_len"], n)
            work = [traffic.take(j) for j in range(n)]
            arrivals = open_arrivals(self.seed, n, seconds)
            reqs, due = [], []
            t0 = time.perf_counter()
            t_end = t0 + seconds
            traced.begin(t0, seconds)
            i = 0
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    traced.close_mark()
                while i < n and t0 + arrivals[i] <= now:
                    reqs.append(e.submit(work[i][0],
                                         max_new_tokens=work[i][1]))
                    due.append(t0 + arrivals[i])
                    i += 1
                if e.has_work():
                    e.step()
                    ticks.append(time.perf_counter() - now)
                    traced.tick()
                elif i < n:
                    time.sleep(max(0.0, min(t0 + arrivals[i] - now, 0.001)))
                else:
                    break
                if now - t0 > seconds + DRAIN_SECONDS:
                    break
        else:
            clients = int(t["clients"])
            reqs, due, open_reqs = [], [], []
            t0 = time.perf_counter()
            t_end = t0 + seconds
            traced.begin(t0, seconds)
            j = 0
            for _ in range(clients):
                prompt, ans = traffic.take(j)
                j += 1
                r = e.submit(prompt, max_new_tokens=ans)
                reqs.append(r)
                due.append(r.t_submit)
                open_reqs.append(r)
            while time.perf_counter() < t_end:
                now = time.perf_counter()
                e.step()
                ticks.append(time.perf_counter() - now)
                traced.tick()
                for k, r in enumerate(open_reqs):
                    if r.done.is_set():
                        prompt, ans = traffic.take(j)
                        j += 1
                        r = e.submit(prompt, max_new_tokens=ans)
                        reqs.append(r)
                        due.append(r.t_submit)
                        open_reqs[k] = r
        t_stop = time.perf_counter()
        traced.finish()
        after = self._counters()
        return {"loop": t["loop"], "t0": t0, "t_end": t_end, "t_stop": t_stop,
                "seconds": seconds, "requests": reqs, "due": due,
                "ticks": ticks,
                "counters": {k: after[k] - before[k] for k in after},
                "traced": (traced.t0, traced.t1)}

    def _counters(self) -> dict:
        return self.model.serve_counters(self.engine, self.registry)

    # -- what the window showed -------------------------------------------------
    def end_to_end(self, rec: dict) -> dict:
        reqs = rec["requests"]
        out = {}
        if rec["loop"] == "open":
            gaps = np.concatenate([np.diff(r.t_tokens) for r in reqs
                                   if len(r.t_tokens) > 1] or [np.zeros(0)])
            out["token_gap_mean_ms"] = float(np.mean(gaps) * 1000.0)
        else:
            # all the work of the window: every token that came out in it,
            # also of requests still running when it closed
            n = sum(t <= rec["t_end"] for r in reqs for t in r.t_tokens)
            out["out_tokens_per_s"] = n / rec["seconds"]
        return out

    def attempted_failed(self, rec: dict) -> tuple:
        if rec["loop"] == "open":
            failed = sum(not r.done.is_set() for r in rec["requests"])
            return len(rec["requests"]), failed
        done = sum(r.done.is_set() for r in rec["requests"])
        return done, 0  # a closed loop's open requests are cut, not failed

    def summary(self, rec: dict) -> dict:
        """The window as plain numbers, for the per-layer readers: the
        program's objects do not outlive ``release``. ``steps`` is the
        model's record of each step, ``[stamp, slots]``, which its own
        counts of work read; ``scopes``, of a traced run alone, names the
        operations of the programs the window ran."""
        reqs, due = rec["requests"], rec["due"]
        e = self.engine
        rows = []
        for r, d in zip(reqs, due):
            rows.append({
                "due": d, "t_submit": r.t_submit, "t_admit": r.t_admit,
                "t_first": r.t_first, "t_done": r.t_done,
                "t_tokens": list(r.t_tokens), "prompt_len": len(r.prompt),
                "bucket": e.bucket_for(len(r.prompt)),
                "generated": len(r.generated), "done": r.done.is_set()})
        scopes = None
        if rec["traced"][0] is not None:
            scopes = scopes_by_program(
                self.model.serve_programs(e, self._buckets()))
        return {"kind": "serve", "scopes": scopes, "loop": rec["loop"],
                "t0": rec["t0"], "t_end": rec["t_end"],
                "seconds": rec["seconds"], "traced": rec["traced"],
                "requests": rows, "steps": self.model.serve_steps(reqs),
                "ticks": rec["ticks"], "counters": rec["counters"],
                "dims": self.dims, "n_slots": e.n_slots,
                "max_len": e.max_len}

    # -- correctness ------------------------------------------------------------
    def release(self) -> None:
        import jax

        self.engine = None
        gc.collect()
        jax.clear_caches()

    def sample(self, rec: dict) -> list:
        """The requests the reference follows: the longest finished one
        and others drawn from the seed."""
        done = [r for r in rec["requests"] if r.done.is_set()
                and len(r.generated) > 0
                and (rec["loop"] == "open" or r.t_done <= rec["t_end"])]
        if not done:
            return []
        k = int(self.traffic["check_requests"])
        longest = max(done, key=lambda r: len(r.prompt) + len(r.generated))
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng([self.seed, 0xC0DE])
        picks = rng.permutation(len(rest))[:max(0, k - 1)]
        return [longest] + [rest[i] for i in picks]

    def check(self, rec: dict, sampled: list, control_via=None) -> tuple:
        """After ``release``: no request of an open loop left unfinished,
        and the model's own comparison of the sampled requests with its
        plain reference (with ``control_via``, of the control in that
        type)."""
        _, failed = self.attempted_failed(rec)
        compared = {"requests_never_finished": [float(failed), 0.0],
                    **self.model.serve_compare(self.config, self.traffic,
                                               self.seed, sampled,
                                               control_via)}
        return all(v <= lim for v, lim in compared.values()), compared


def required_flops(summary: dict, model) -> float:
    """Required operations of everything the window processed, by the
    model's own counts: each prefill, and each step from its record."""
    dims = summary["dims"]
    lo, hi = summary["t0"], summary["t_end"]
    flops = 0.0
    for r in summary["requests"]:
        if r["t_first"] is not None and lo <= r["t_first"] <= hi:
            flops += model.prefill_flops(dims, r["prompt_len"])
    for stamp, slots in summary["steps"]:
        if lo <= stamp <= hi:
            flops += model.step_flops(dims, slots)
    return flops
