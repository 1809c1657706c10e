"""Device time under ``lm_ssm`` (a state-space layer's norm, projections,
conv, recurrence, gated norm and residual, in the prompt pass and the cached
step) over busy time."""

from benchmark.trace.named import lm_share_pct


def read(run):
    return lm_share_pct(run, "lm_ssm")
