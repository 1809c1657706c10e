"""Device time under ``lm_update`` (the optimizer's parameter update) over
busy time."""

from benchmark.trace.named import lm_share_pct


def read(run):
    return lm_share_pct(run, "lm_update")
