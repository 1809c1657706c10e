"""95th percentile of time to first token over every request due in the
window, from its scheduled arrival; one that never started counts as the
largest. Some 284 requests a window leave it a sampling error near 6%, too
wide for an end-to-end bound (PERF.md, Findings), so it is read here."""

import numpy as np


def read(run):
    s = run["summary"]
    rows = s["requests"]
    if not rows:
        return None
    last = max(r["t_done"] or s["t_end"] for r in rows)
    ttft = [((r["t_first"] if r["t_first"] is not None else last) - r["due"])
            * 1000.0 for r in rows]
    return float(np.percentile(ttft, 95))
