"""The whole serve step's share of the chip's peak: required operations of
every prompt and output token processed in the window (top-k experts only,
real prompt tokens only) over chips x peak x window."""

from benchmark.drivers.serve_engine import count_work


def read(run):
    if run["peaks"] is None:
        return None
    s = run["summary"]
    flops, _ = count_work(s)
    if flops <= 0:
        return None
    return 100.0 * flops / (run["device"]["count"]
                            * run["peaks"]["bf16_flops"] * s["seconds"])
