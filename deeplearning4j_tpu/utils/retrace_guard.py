"""Retrace guard: fail when a step recompiles beyond its pinned budget.

The silent killer graftlint's static rules cannot see is *shape/weak-type
drift*: a python scalar where an array was traced, a batch that changes
size, a donated-buffer layout flip — and suddenly every training step
pays an XLA compile. On a fast chip that turns a 2 ms step into seconds
without any error. This module counts real XLA backend compilations via
``jax.monitoring`` (the ``/jax/core/compile/backend_compile_duration``
event wraps the whole compile-or-fetch, so it fires exactly once per program
a process asks XLA for, also when the persistent compilation cache serves it:
a retrace that hits that cache still counts) and raises when a guarded region
compiles more than its budget.

Usage (context manager)::

    step = make_single_device_train_step(heads)
    step(params, tk, tg)                      # warmup: compiles once
    with retrace_guard(0, label="lm_composed steady state"):
        for _ in range(5):
            params, loss = step(params, tk, tg)   # any retrace -> fail

tests/conftest.py exposes the same object as the ``retrace_budget``
pytest fixture; tests/test_retrace_guard.py pins compile budgets for the
composed LM / pipeline / DP-sync steps.

ISSUE 9: the guard also records the ABSTRACT SIGNATURE of each compile —
the ``Compiling <fn> with global shapes and types (ShapedArray(...), ...)``
line jax's pjit lowering logs carries exactly the shapes/dtypes/weak-types
that keyed the cache miss. A logging filter on that logger captures the
signatures into a bounded ring (and swallows the log record, so there is
no stderr spam), and a blown budget now reports *what* recompiled plus
the positional signature diff vs the previous compile of the same
function — "arg 2: f32[8] → weak f32[]" instead of just a count.
"""

from __future__ import annotations

import logging
import re
import threading

__all__ = ["RetraceBudgetExceeded", "retrace_guard", "compiles_so_far",
           "recent_compiles", "signature_diff"]


class RetraceBudgetExceeded(AssertionError):
    """A guarded region compiled more XLA programs than its pinned budget."""


_lock = threading.Lock()
_counter = {"n": 0}
_installed = {"done": False}

# one real XLA compile -> exactly one of these fires
_DURATION_EVENT_SUFFIX = "backend_compile_duration"


def _on_duration(name: str, secs: float, **kw) -> None:
    if name.endswith(_DURATION_EVENT_SUFFIX):
        with _lock:
            _counter["n"] += 1


# ------------------------------------------------- compile signatures ----

# pjit's per-compile log line (fires at DEBUG even with jax_log_compiles
# off, so capturing it costs no stderr noise)
_COMPILING_RE = re.compile(
    r"Compiling ([^\s]+) with global shapes and types \((.*)\)\. "
    r"Argument mapping"
)
_PXLA_LOGGER = "jax._src.interpreters.pxla"
_SIG_RING_MAX = 64
_sig_ring: list = []  # [{"seq", "name", "signature"}], bounded
_sig_seq = {"n": 0}


class _CompileSignatureFilter(logging.Filter):
    """Records each compile's (fn name, abstract signature) into the ring
    and swallows the matched record: captured, not printed."""

    def filter(self, record: logging.LogRecord) -> bool:
        m = _COMPILING_RE.search(record.getMessage())
        if not m:
            return True
        with _lock:
            _sig_seq["n"] += 1
            _sig_ring.append({"seq": _sig_seq["n"], "name": m.group(1),
                              "signature": m.group(2)})
            del _sig_ring[:-_SIG_RING_MAX]
        return False


def recent_compiles(since_seq: int = 0) -> list:
    """Compile records (seq, fn name, abstract signature) captured after
    ``since_seq`` — best-effort forensics riding the pjit log line; the
    compile COUNT always comes from jax.monitoring."""
    _install()
    with _lock:
        return [dict(r) for r in _sig_ring if r["seq"] > since_seq]


def _sig_avals(signature: str) -> list:
    return re.findall(r"ShapedArray\([^()]*\)", signature)


def signature_diff(prev: str, cur: str) -> str:
    """Human-readable positional diff of two abstract signatures."""
    a, b = _sig_avals(prev), _sig_avals(cur)
    if not a and not b:
        return "signatures unparsed"
    if len(a) != len(b):
        return f"arg count changed: {len(a)} -> {len(b)}"
    changes = [f"arg {i}: {x} -> {y}"
               for i, (x, y) in enumerate(zip(a, b)) if x != y]
    return "; ".join(changes) if changes else "signatures identical"


def _install() -> None:
    """Register the process-wide compile listener once."""
    import jax

    with _lock:
        if _installed["done"]:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        # signature recorder (ISSUE 9): pjit logs its per-compile abstract
        # signature at DEBUG; enable that level on just this logger and let
        # the filter capture and swallow the records
        pxla_logger = logging.getLogger(_PXLA_LOGGER)
        pxla_logger.setLevel(logging.DEBUG)
        pxla_logger.addFilter(_CompileSignatureFilter())
        _installed["done"] = True


def compiles_so_far() -> int:
    """Process-wide XLA compile count since the guard was first installed
    (monotonic; meaningful as a delta, which is what retrace_guard takes)."""
    _install()
    with _lock:
        return _counter["n"]


class retrace_guard:
    """Context manager asserting at most ``budget`` XLA compilations happen
    inside the block.

    ``budget=0`` pins a steady-state region (a warmed-up train step must
    never retrace); a positive budget pins a cold region's compile count
    (e.g. "first step compiles the train step and its data transfers, and
    nothing else"). The count is process-wide — don't run guarded regions
    concurrently in threads.
    """

    def __init__(self, budget: int, label: str = ""):
        self.budget = int(budget)
        self.label = label
        self.count = 0
        self.compiled: list = []  # signature records seen inside the region
        self._start = 0
        self._sig_start = 0

    def __enter__(self) -> "retrace_guard":
        _install()
        self._start = compiles_so_far()
        with _lock:
            self._sig_start = _sig_seq["n"]
        return self

    def _signature_report(self) -> str:
        """What recompiled in this region + the diff vs each program's
        previous compile (ISSUE 9) — empty when the pjit log line was not
        observed (non-pjit compile paths)."""
        if not self.compiled:
            return ""
        lines = ["", "compiled in this region:"]
        with _lock:
            ring = [dict(r) for r in _sig_ring]
        for rec in self.compiled:
            lines.append(f"  {rec['name']} [{rec['signature']}]")
            prev = [r for r in ring
                    if r["name"] == rec["name"] and r["seq"] < rec["seq"]]
            if prev:
                lines.append("    vs previous compile: " + signature_diff(
                    prev[-1]["signature"], rec["signature"]))
        return "\n".join(lines)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.count = compiles_so_far() - self._start
        self.compiled = recent_compiles(self._sig_start)
        if exc_type is None and self.count > self.budget:
            what = f" [{self.label}]" if self.label else ""
            raise RetraceBudgetExceeded(
                f"retrace budget exceeded{what}: {self.count} XLA "
                f"compilation(s) in a region pinned to {self.budget}. "
                "Likely shape/weak-type drift is recompiling the step per "
                "call (python scalar vs array argument, changing batch "
                "shape, donation layout flip). Pin the input shapes/dtypes "
                "— or raise the budget deliberately if the new compiles "
                "are intended." + self._signature_report())
        return False


def pytest_fixture():
    """Factory for the ``retrace_budget`` fixture (registered in
    tests/conftest.py): yields the retrace_guard class itself so tests
    write ``with retrace_budget(0, label=...):``."""
    return retrace_guard
