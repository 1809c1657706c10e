"""The least time the chip could take for the recurrence of every prompt
pass in the traced part (the larger of its required operations over peak and
its required bytes over bandwidth, each prompt's real tokens by the counts of
the cell's model) over the device time of the operations under ``ssm_scan``
(the chunked scan) there. None where the model counts no recurrence or no
operation carries the scope."""

from benchmark.harness.peaks import roofline_seconds
from benchmark.trace.reduce import scope_seconds


def read(run):
    t, s, model = run["trace"], run["summary"], run["model"]
    flops = getattr(model, "ssm_scan_flops", None)
    if flops is None or run["peaks"] is None or s["traced"][0] is None:
        return None
    device_s = scope_seconds(t, "ssm_scan") * t["devices"]
    if device_s <= 0:
        return None
    lo, hi = s["traced"]
    least = sum(roofline_seconds(flops(s["dims"], r["prompt_len"]),
                                 model.ssm_scan_bytes(s["dims"],
                                                      r["prompt_len"]),
                                 run["peaks"])
                for r in s["requests"]
                if r["t_first"] is not None and lo <= r["t_first"] <= hi)
    return 100.0 * least / device_s if least > 0 else None
