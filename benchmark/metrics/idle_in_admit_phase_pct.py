"""Idle behind admission: time under ``tick.admit`` in the traced part
(the admission loop with its fenced prefills) minus the prefill program's
device time there, over the traced window."""

from benchmark.trace.named import idle_behind_pct


def read(run):
    return idle_behind_pct(run, "tick.admit", "jit_prefill")
