"""Device time under the prefill program (``jit_prefill``) over busy
time, in the traced part of the window."""


def read(run):
    t = run["trace"]
    if "jit_prefill" not in t["by_program"]:
        return None
    return 100.0 * t["by_program"]["jit_prefill"] / t["busy_s"]
