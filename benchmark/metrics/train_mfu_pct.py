"""Model FLOP/s utilization: required forward and backward operations a
token (by the counts of the cell's model: top-k experts only, the causal
half of the scores, nothing recomputed) x tokens per second over chips x
peak."""


def read(run):
    if run["peaks"] is None:
        return None
    s = run["summary"]
    per_token = run["model"].train_flops_per_token(s["dims"], s["seq"])
    return 100.0 * per_token * run["values"]["train_tokens_per_s"] / (
        run["device"]["count"] * run["peaks"]["bf16_flops"])
