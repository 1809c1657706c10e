"""Device time under ``lm_moe`` (the second layer norm, routing, the experts
and the combine, forward and backward; on the mesh the ``moe_all2all_*``
exchange lies inside it) over busy time."""

from benchmark.trace.named import lm_share_pct


def read(run):
    return lm_share_pct(run, "lm_moe")
