"""What the program itself names, read back for the per-layer metrics: the
serving tick's phases from its ring (``telemetry/trace.py``: ``tick`` >
``tick.admit`` > ``tick.prefill``, ``tick.decode``, ``tick.accept``, on
``perf_counter``) and the model's ``lm_*`` scopes from the compiled text.

Every function is arithmetic on ``run`` and the ring, and returns None,
never a guess, where there is nothing to read: a program that has no ring
or names no ``lm_*`` scope (a commit before them), a ring that has wrapped
past the window, a run that was not traced, a program missing from the
trace.

Idle time by phase needs no common clock. The tick is synchronous: every
dispatch is fenced before the next, so the device works on ``jit_step`` only
inside ``tick.decode`` and on ``jit_prefill`` only inside ``tick.admit``.
What a phase lasts beyond its program's device time, summed over the traced
part, is the time the device idled behind that phase; the traced part is
``summary["traced"]`` on the spans' clock and ``window_s`` on the device's.
A negative number means a span that does not enclose its program's run.
"""

from __future__ import annotations

from benchmark.trace.reduce import scope_seconds


def phases(run: dict, traced: bool):
    """The tick phases ``(name, tick, t0, t1, attrs)`` of the whole window,
    or of its traced part, clipped to it."""
    s = run["summary"]
    lo, hi = s["traced"] if traced else (s["t0"], s["t_end"])
    if lo is None or hi is None:
        return None
    try:
        from deeplearning4j_tpu.telemetry.trace import phases_between
    except ImportError:
        return None
    entries, wrapped = phases_between(lo, hi)
    return entries if entries and not wrapped else None


def seconds_under(entries: list, name: str) -> float:
    return sum(t1 - t0 for n, _, t0, t1, _ in entries if n == name)


def phase_seconds(run: dict, name: str):
    """Seconds of the traced part spent under phase ``name``."""
    entries = phases(run, traced=True)
    return None if entries is None else seconds_under(entries, name)


def idle_behind_pct(run: dict, phase: str, program: str):
    """Phase time minus its program's device time, over the traced
    window, in percent."""
    t = run["trace"]
    under = phase_seconds(run, phase)
    if under is None or program not in t["by_program"]:
        return None
    device_s = t["by_program"][program] * t["devices"]
    return 100.0 * (under - device_s) / t["window_s"]


def tick_mean_ms(run: dict, seconds_of):
    """``seconds_of(entries)`` (a sum over the whole window's phases) over
    the number of ticks there, in milliseconds."""
    entries = phases(run, traced=False)
    if entries is None:
        return None
    ticks = sum(n == "tick" for n, *_ in entries)
    return 1000.0 * seconds_of(entries) / ticks if ticks else None


def lm_share_pct(run: dict, needle: str):
    """Device time of operations whose scope holds ``needle`` over busy
    time, in percent. A program whose cached executable predates the
    scopes reads 0 (and ``unscoped_share_of_busy_pct`` 100): metadata is
    not part of the compile cache's key. The cell's model says whether its
    program names scopes at all (a commit before them names none)."""
    if not run["model"].program_scopes() or not run["summary"].get("scopes"):
        return None
    t = run["trace"]
    return 100.0 * scope_seconds(t, needle) / t["busy_s"]
