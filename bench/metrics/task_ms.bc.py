"""Median start-to-complete of a task body (bc cells)."""
from readers import task_ms


def read(run):
    return task_ms(run, "bc")
