"""Time the garbage collector stopped the process (``host.gc`` phases) over
the whole window, not its traced part alone, which a rare full collection
can miss: milliseconds a second of window. 0 where the window saw no
collection; None where the ring holds no collection at all, before the
window either (set-up and warm-up collect many times): a program that
records none."""

from benchmark.trace.named import phases, seconds_under


def read(run):
    entries = phases(run, traced=False)
    if entries is None:
        return None
    from deeplearning4j_tpu.telemetry.trace import phases_between

    ring, _ = phases_between(float("-inf"), float("inf"))
    if not any(e[0] == "host.gc" for e in ring):
        return None
    s = run["summary"]
    return 1000.0 * seconds_under(entries, "host.gc") / (s["t_end"] - s["t0"])
