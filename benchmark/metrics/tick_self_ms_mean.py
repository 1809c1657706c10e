"""Mean over the window's ticks of the ``tick`` span minus its children:
the scheduler's own host time (the lock, the gauges, the slot lists),
measured inside the tick. ``host_ms_per_tick`` takes the tick from outside
and also counts the acceptance loop and admission's own host time."""

from benchmark.trace.named import tick_mean_ms


def _self_seconds(entries):
    # called only with entries in hand, so the program has the ring; a
    # program before it has no such name to import at the top
    from deeplearning4j_tpu.telemetry.trace import phase_self_seconds

    return sum(s for (n, *_), s in zip(entries, phase_self_seconds(entries))
               if n == "tick")


def read(run):
    return tick_mean_ms(run, _self_seconds)
