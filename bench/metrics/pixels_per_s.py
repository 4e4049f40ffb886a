"""Image pixels delivered per second of the window (MS cells)."""
from readers import work_per_s


def read(run):
    return work_per_s(run, "px")
