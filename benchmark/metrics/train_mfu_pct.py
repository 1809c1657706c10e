"""Model FLOP/s utilization: required forward and backward operations a
token (top-k experts only, the causal half of the scores, nothing
recomputed) x tokens per second over chips x peak."""

from benchmark.counts.flagship import train_flops_per_token


def read(run):
    if run["peaks"] is None:
        return None
    s = run["summary"]
    per_token = train_flops_per_token(s["dims"], s["seq"])
    return 100.0 * per_token * run["values"]["train_tokens_per_s"] / (
        run["device"]["count"] * run["peaks"]["bf16_flops"])
