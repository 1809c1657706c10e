"""Host time a scheduler tick: the tick's wall time minus the engine's own
fenced dispatches (decode steps and prefills), host clock, mean."""


def read(run):
    s = run["summary"]
    if not s["ticks"]:
        return None
    c = s["counters"]
    fenced = c["decode_ms_sum"] + c["prefill_ms_sum"]
    return (sum(s["ticks"]) * 1000.0 - fenced) / len(s["ticks"])
