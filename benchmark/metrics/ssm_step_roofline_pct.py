"""The least time the chip could take for the one-token state updates of
the decode steps of the traced part (each step's live slots from its own
record, by the counts of the cell's model: their states read and written)
over the device time of the operations under ``ssm_step`` there. None where
the model counts no state update or no operation carries the scope."""

from benchmark.harness.peaks import roofline_seconds
from benchmark.trace.reduce import scope_seconds


def read(run):
    t, s, model = run["trace"], run["summary"], run["model"]
    flops = getattr(model, "ssm_step_flops", None)
    if flops is None or run["peaks"] is None or s["traced"][0] is None:
        return None
    device_s = scope_seconds(t, "ssm_step") * t["devices"]
    if device_s <= 0:
        return None
    lo, hi = s["traced"]
    least = sum(roofline_seconds(flops(s["dims"], slots),
                                 model.ssm_step_bytes(s["dims"], slots),
                                 run["peaks"])
                for stamp, slots in s["steps"] if lo <= stamp <= hi)
    return 100.0 * least / device_s if least > 0 else None
