"""Compile requests inside the measured window; has to read 0."""


def read(run):
    return float(run["setup"]["compiles_in_window"])
