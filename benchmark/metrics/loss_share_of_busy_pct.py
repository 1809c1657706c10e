"""Device time under ``lm_loss`` (the decoder matmul to the vocabulary,
``log_softmax``, the NLL and the load-balance term, forward and backward)
over busy time."""

from benchmark.trace.named import lm_share_pct


def read(run):
    return lm_share_pct(run, "lm_loss")
