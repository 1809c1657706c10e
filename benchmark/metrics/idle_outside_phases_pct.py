"""Time in the traced part under neither ``tick.decode`` nor ``tick.admit``
(token acceptance, the tick's own work, the loop between ticks), over the
traced window. With the two ``idle_in_*`` metrics it adds up to the idle
share at the grain of whole programs."""

from benchmark.trace.named import phase_seconds


def read(run):
    decode = phase_seconds(run, "tick.decode")
    admit = phase_seconds(run, "tick.admit")
    if decode is None or admit is None:
        return None
    lo, hi = run["summary"]["traced"]
    return 100.0 * ((hi - lo) - decode - admit) / run["trace"]["window_s"]
