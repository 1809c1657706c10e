"""Device time of operations under ``moe_all2all_dispatch`` and
``moe_all2all_return`` during which nothing outside them ran on that
device, over the traced window."""

from benchmark.trace.reduce import scope_seconds


def read(run):
    t = run["trace"]
    sec = scope_seconds(t, "moe_all2all_", exposed=True)
    return 100.0 * sec / t["window_s"] if sec > 0 else None
