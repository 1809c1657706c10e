"""Programs found in the persistent cache over programs requested, up to
the start of the window (JAX's cache counters)."""


def read(run):
    s = run["setup"]
    if not s["compile_requests"]:
        return None
    return 100.0 * s["cache_hits"] / s["compile_requests"]
