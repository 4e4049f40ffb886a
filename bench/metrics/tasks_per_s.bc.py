"""Tasks the master completed per second of the window (bc cells)."""
from readers import tasks_per_s


def read(run):
    return tasks_per_s(run, "bc")
