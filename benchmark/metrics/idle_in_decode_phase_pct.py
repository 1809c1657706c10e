"""Idle behind the decode dispatch and its fence: time under ``tick.decode``
in the traced part minus the decode program's device time there, over the
traced window."""

from benchmark.trace.named import idle_behind_pct


def read(run):
    return idle_behind_pct(run, "tick.decode", "jit_step")
