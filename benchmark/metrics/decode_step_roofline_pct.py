"""The least time the chip could take for the decode steps of the traced
part (the larger of required operations over peak and required bytes over
bandwidth, each step) over the decode program's device time there."""

from benchmark.counts import flagship as counts
from benchmark.drivers.serve_engine import count_work


def read(run):
    t, s = run["trace"], run["summary"]
    if run["peaks"] is None or s["traced"][0] is None:
        return None
    device_s = t["by_program"].get("jit_step", 0.0) * t["devices"]
    if device_s <= 0:
        return None
    lo, hi = s["traced"]
    _, steps = count_work(s)
    least = sum(counts.roofline_seconds(
        counts.decode_flops(s["dims"], pos),
        counts.decode_step_bytes(s["dims"], pos), run["peaks"])
        for stamp, pos in steps.items() if lo <= stamp <= hi)
    return 100.0 * least / device_s if least > 0 else None
