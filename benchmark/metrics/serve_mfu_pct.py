"""The whole serve step's share of the chip's peak: required operations of
every prompt and output token processed in the window (by the counts of the
cell's model: top-k experts only, real prompt tokens only) over chips x peak
x window."""

from benchmark.drivers.serve_engine import required_flops


def read(run):
    if run["peaks"] is None:
        return None
    s = run["summary"]
    flops = required_flops(s, run["model"])
    if flops <= 0:
        return None
    return 100.0 * flops / (run["device"]["count"]
                            * run["peaks"]["bf16_flops"] * s["seconds"])
