"""Idle behind the block step's dispatch and its fence: time under
``tick.decode`` in the traced part minus ``jit_block_step``'s device time
there, over the traced window. With ``idle_in_admit_phase_pct`` and
``idle_outside_phases_pct`` it adds up to the idle share of a cell whose
tick runs the block step."""

from benchmark.trace.named import idle_behind_pct


def read(run):
    return idle_behind_pct(run, "tick.decode", "jit_block_step")
