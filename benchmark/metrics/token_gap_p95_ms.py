"""95th percentile of the gap between consecutive output tokens of one
request, pooled over every request due in the window. It sits between two
modes ("a step" and "a step plus the tick's prefills") and jumps from one to
the other between runs of one seed (PERF.md, Findings), so it is read here
and the mean gap is the end-to-end metric."""

import numpy as np


def read(run):
    gaps = [b - a for r in run["summary"]["requests"]
            for a, b in zip(r["t_tokens"], r["t_tokens"][1:])]
    return float(np.percentile(gaps, 95) * 1000.0) if gaps else None
