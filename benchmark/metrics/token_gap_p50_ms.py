"""Median gap between consecutive output tokens of one request, pooled:
a decode step plus the prefills admitted in the same tick."""

import numpy as np


def read(run):
    gaps = [b - a for r in run["summary"]["requests"]
            for a, b in zip(r["t_tokens"], r["t_tokens"][1:])]
    return float(np.median(gaps) * 1000.0) if gaps else None
