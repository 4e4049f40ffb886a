"""Tree nodes counted per second of the window (UTS cells)."""
from readers import work_per_s


def read(run):
    return work_per_s(run, "nodes")
