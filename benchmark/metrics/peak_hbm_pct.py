"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read when
the window closes, over the table's capacity."""


def read(run):
    if run["peaks"] is None:
        return None
    return 100.0 * run["device"]["memory_peak_bytes"] \
        / run["peaks"]["hbm_bytes"]
