"""What every run shares: the compile counter, the device check, the traced
window, and the last line of standard output.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

from benchmark.harness.registry import ROOT
from benchmark.trace.reduce import WINDOW_MARK

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_SECONDS = 6.0  # the traced part of the window: traces are large


class CompileCounter:
    """Counts compile requests and persistent-cache hits through
    jax.monitoring (the events ``chip_smoke.py``'s CompileWatch reads)."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.requests, self.hits


def require_devices(chips: int, allow_cpu: bool = False) -> list:
    """The ``chips`` accelerator devices this cell runs on, or SystemExit:
    a run that finds no chip, or fewer than the cell asks for, prints no
    result. ``allow_cpu`` is for the rehearsal tests alone."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" and not allow_cpu:
        sys.exit(f"benchmark: JAX found no accelerator (platform "
                 f"{devices[0].platform!r}); a CPU run measures nothing")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chips and JAX found "
                 f"{len(devices)}")
    return devices[:chips]


def device_record(devices: list) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class TracedPart:
    """Profiles the last TRACE_SECONDS of a window. The window's loop calls
    ``tick()`` once an iteration: it starts the profiler when its time has
    come. ``close_mark()`` at the window's close ends the traced window on
    the profiler's clock; ``finish()`` stops the profiler once the loop has
    nothing left to do, because stopping blocks the host for seconds."""

    def __init__(self, on: bool, cell: str):
        self.on = on
        self.dir = os.path.join(TRACE_DIR, cell)
        self.t0 = self.t1 = None
        self._start_at = None
        self._mark = None

    def begin(self, t0: float, seconds: float) -> None:
        """The window opens at ``t0`` and lasts ``seconds``."""
        if self.on:
            self._start_at = t0 + max(0.0, seconds - TRACE_SECONDS)

    def tick(self) -> None:
        if self._start_at is None or time.perf_counter() < self._start_at:
            return
        import jax

        self._start_at = None
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        # the mark puts the window's two ends on the profiler's own clock
        self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
        self._mark.__enter__()
        self.t0 = time.perf_counter()

    def close_mark(self) -> None:
        if self._mark is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            self._mark.__exit__(None, None, None)

    def finish(self) -> None:
        if self._mark is None:
            return
        import jax

        self.close_mark()
        self._mark = None
        jax.profiler.stop_trace()

    def xplane(self):
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return files[-1] if files else None

    def discard(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def last_line(correct: bool, attempted: int, failed: int, metrics: dict,
              device: dict, compared: dict, breakdown=None) -> str:
    """The result line. ``metrics`` is {name: (value, unit)}; ``compared``
    is {short name: [number, limit]} and comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": float(v), "unit": u}
                       for k, (v, u) in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)


def print_compared(compared: dict, correct: bool) -> None:
    """Each number compared beside its limit, as the last lines of standard
    error."""
    for name, (value, limit) in compared.items():
        verdict = "ok" if value <= limit else "OVER"
        print(f"compared {name}: {value:.6g} limit {limit:.6g} {verdict}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
