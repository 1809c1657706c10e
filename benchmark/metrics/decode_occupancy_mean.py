"""Live slots a decode step: the engine's ``_occupancy_sum`` over its
``decode_steps``, across the window."""


def read(run):
    c = run["summary"]["counters"]
    if not c["decode_steps"]:
        return None
    return c["occupancy_sum"] / c["decode_steps"]
