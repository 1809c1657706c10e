"""How late the single-threaded loop submitted: submit time minus the
scheduled arrival, 99th percentile."""

import numpy as np


def read(run):
    rows = run["summary"]["requests"]
    if not rows:
        return None
    return float(np.percentile([(r["t_submit"] - r["due"]) * 1000.0
                                for r in rows], 99))
