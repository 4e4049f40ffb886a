"""Idle share of the device over the traced window (ms cells)."""
from readers import device_idle


def read(run):
    return device_idle(run, "ms")
