"""Mean over the window's ticks of the host time under ``tick.dispatch``:
the jitted calls' enqueue, from entry to the return of their futures (the
decode or block step, and the prefills of a tick that admits). The host's
clock alone: the device may start the program before the call returns, so
this is not time the device idled."""

from benchmark.trace.named import phases, seconds_under, tick_mean_ms


def read(run):
    entries = phases(run, traced=False)
    if entries is None or not any(e[0] == "tick.dispatch" for e in entries):
        return None  # a program before the phase
    return tick_mean_ms(run, lambda entries: seconds_under(entries,
                                                           "tick.dispatch"))
