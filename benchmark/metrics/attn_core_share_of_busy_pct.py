"""Device time of operations under ``blockwise_q_block_*`` (the blockwise
attention core's named scopes) over busy time."""

from benchmark.trace.reduce import scope_seconds


def read(run):
    t = run["trace"]
    sec = scope_seconds(t, "blockwise_q_block_")
    return 100.0 * sec / t["busy_s"] if sec > 0 else None
