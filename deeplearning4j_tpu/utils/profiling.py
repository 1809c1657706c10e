"""Tracing/profiling utilities (SURVEY.md §5).

The reference's tracing story is ad-hoc StopWatch timing in the YARN worker
and per-job millisecond logging in the Akka WorkerActor heartbeat
(ref: impl/multilayer/WorkerNode.java totalRunTimeWatch/batchWatch,
actor/core/actor/WorkerActor.java:198-202). The TPU-native equivalent adds
the XLA profiler on top of those counters (optimize/listeners.py,
statetracker job_ms_total): device traces viewable in XProf/TensorBoard,
scoped host annotations, and device-memory introspection.
"""

from __future__ import annotations

import contextlib
import os
from typing import Dict, List, Optional

import jax


@contextlib.contextmanager
def trace(log_dir: str, *, create_perfetto_link: bool = False):
    """Capture an XLA device+host trace for the enclosed block.

    Produces an XProf/TensorBoard-compatible trace directory — the
    device-side truth for where step time goes (MXU vs HBM vs infeed),
    which host-side StopWatch timing (the reference's tool) cannot see.
    """
    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir,
                            create_perfetto_link=create_perfetto_link):
        yield


def annotate(name: str, **attrs):
    """Scoped host annotation shown on the trace timeline
    (e.g. ``with annotate("pretrain-layer0"): ...``), on the profiler's
    own clock; ``attrs`` ride along as the event's arguments. The one
    place a ``TraceAnnotation`` is made: ``telemetry.trace.phase`` opens
    the serving tick's spans through it. An inactive ``TraceMe`` when no
    profiler session is live."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def named_scope(name: str):
    """In-graph twin of ``annotate``: names the ops traced inside the scope
    so DEVICE timelines (XProf) show the phase — use inside jitted code
    (ring K/V rotation, ulysses AllToAll, pp stage ticks, blockwise tiles),
    where the host-side TraceAnnotation would only mark trace time."""
    return jax.named_scope(name)


def device_memory_stats() -> List[Dict]:
    """Per-device live-memory stats (bytes in use / peak / limit where the
    backend reports them). Empty dict per device on backends without
    memory_stats (CPU)."""
    out = []
    for dev in jax.devices():
        stats = {}
        try:
            stats = dict(dev.memory_stats() or {})
        except Exception:
            pass
        out.append({"device": str(dev), **stats})
    return out


class ProfilerIterationListener:
    """IterationListener that traces a window of a live training run — drop
    it into net.listeners next to ScoreIterationListener (the listener-chain
    hook mirrors ref: optimize/api/IterationListener).

    Window semantics: listeners fire AFTER each iteration's compute, so the
    trace opens once the ``start``-th callback has fired and spans the NEXT
    ``steps`` iterations (callbacks start+1 … start+steps). The very first
    iteration's compile can therefore not be captured through this hook —
    wrap fit() in ``utils.profiling.trace`` for that. ``start=0`` opens the
    window at the first callback."""

    def __init__(self, log_dir: str, start: int = 1, steps: int = 3):
        self.log_dir = log_dir
        self.start = start
        self.steps = steps
        self._active = False
        self._done = False
        self._seen = 0
        self._traced = 0

    def __call__(self, model, iteration: int, score: float) -> None:
        self._seen += 1
        if self._active:
            self._traced += 1
            if self._traced >= self.steps:
                jax.profiler.stop_trace()
                self._active = False
                self._done = True
            return
        if not self._done and self._seen >= self.start:
            os.makedirs(self.log_dir, exist_ok=True)
            jax.profiler.start_trace(self.log_dir)
            self._active = True
            self._traced = 0

    def close(self) -> None:
        """Stop a still-open trace (training ended inside the window)."""
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True


def save_device_memory_profile(path: str) -> str:
    """Dump a pprof-format device memory profile (jax.profiler
    device_memory_profile) — allocation attribution for OOM hunts."""
    blob = jax.profiler.device_memory_profile()
    with open(path, "wb") as f:
        f.write(blob)
    return path
