"""Host clock around the warm-up of the cell's own shapes (for a train
cell: its first three steps, compile included)."""


def read(run):
    return run["setup"]["warmup_s"]
