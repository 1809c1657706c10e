"""Padded minus real prompt tokens over padded, of the prefills of the
window: what bucketing to a power of two costs."""


def read(run):
    rows = [r for r in run["summary"]["requests"] if r["t_first"] is not None]
    padded = sum(r["bucket"] for r in rows)
    if not padded:
        return None
    return 100.0 * (padded - sum(r["prompt_len"] for r in rows)) / padded
