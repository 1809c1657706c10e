"""Device time under ``lm_attn`` (the whole attention block: layer norm, the
four projections and the core, forward and backward) over busy time.
``attn_core_share_of_busy_pct`` is the core alone."""

from benchmark.trace.named import lm_share_pct


def read(run):
    return lm_share_pct(run, "lm_attn")
