"""Requests finished by the close of the window over requests due in it."""


def read(run):
    s = run["summary"]
    rows = s["requests"]
    if not rows:
        return None
    done = sum(r["t_done"] is not None and r["t_done"] <= s["t_end"]
               for r in rows)
    return 100.0 * done / len(rows)
