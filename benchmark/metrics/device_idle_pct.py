"""1 minus the union of device-operation intervals over the traced window,
on the fullest device."""


def read(run):
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s_fullest"] / t["window_s"])
