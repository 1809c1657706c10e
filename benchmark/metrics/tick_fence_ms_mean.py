"""Mean over the window's ticks of the host time under ``tick.fence``: the
wait for the fenced calls' results and their copy to the host. The host's
clock alone: the program runs on the device during part of it, and during
part of the enqueue before it, so this less the step's device time is not
the time the device idled."""

from benchmark.trace.named import phases, seconds_under, tick_mean_ms


def read(run):
    entries = phases(run, traced=False)
    if entries is None or not any(e[0] == "tick.fence" for e in entries):
        return None  # a program before the phase
    return tick_mean_ms(run, lambda entries: seconds_under(entries,
                                                           "tick.fence"))
