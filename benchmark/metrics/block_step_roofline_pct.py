"""The least time the chip could take for the block steps of the traced part
(the larger of required operations over peak and required bytes over
bandwidth, each step from its own record by the counts of the cell's model)
over ``jit_block_step``'s device time there."""

from benchmark.harness.peaks import roofline_seconds


def read(run):
    t, s, model = run["trace"], run["summary"], run["model"]
    if run["peaks"] is None or s["traced"][0] is None:
        return None
    device_s = t["by_program"].get("jit_block_step", 0.0) * t["devices"]
    if device_s <= 0:
        return None
    lo, hi = s["traced"]
    least = sum(roofline_seconds(model.step_flops(s["dims"], slots),
                                 model.step_bytes(s["dims"], slots),
                                 run["peaks"])
                for stamp, slots in s["steps"] if lo <= stamp <= hi)
    return 100.0 * least / device_s if least > 0 else None
