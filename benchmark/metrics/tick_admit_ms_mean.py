"""Mean over the window's ticks of the ``tick.admit`` span: what admission
and its prefills add to a tick (most ticks admit nothing)."""

from benchmark.trace.named import seconds_under, tick_mean_ms


def read(run):
    return tick_mean_ms(run, lambda entries: seconds_under(entries,
                                                           "tick.admit"))
