"""Median wait for admission: ``t_admit`` minus the scheduled arrival."""

import numpy as np


def read(run):
    waits = [(r["t_admit"] - r["due"]) * 1000.0
             for r in run["summary"]["requests"] if r["t_admit"] is not None]
    return float(np.median(waits)) if waits else None
