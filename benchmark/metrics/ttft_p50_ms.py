"""Median time to first token from the scheduled arrival."""

import numpy as np


def read(run):
    t = [(r["t_first"] - r["due"]) * 1000.0
         for r in run["summary"]["requests"] if r["t_first"] is not None]
    return float(np.median(t)) if t else None
