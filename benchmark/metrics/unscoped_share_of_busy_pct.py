"""Busy time under no ``lm_*`` scope: the scan's stacking and copies,
operations the compiler made, gaps inside a loop. Near 100 it says "the
executable came from a cache filled before the scopes existed", not where
the time goes."""

from benchmark.trace.named import lm_share_pct


def read(run):
    scoped = lm_share_pct(run, "lm_")
    return None if scoped is None else 100.0 - scoped
