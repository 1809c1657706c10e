"""Device time under the routed expert layer's own parts (``moe_routed_sort``,
``moe_routed_experts``, ``moe_routed_combine``, inside ``lm_moe``) over busy
time: whether the routed form engaged in the cell, and what it costs. None
where no operation carries the scope: a program before it, a call whose rows
an expert the chooser keeps on the all-experts form, an untraced run."""

from benchmark.trace.reduce import scope_seconds


def read(run):
    t = run["trace"]
    sec = scope_seconds(t, "moe_routed")
    return 100.0 * sec / t["busy_s"] if sec > 0 else None
