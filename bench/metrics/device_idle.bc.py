"""Idle share of the device over the traced window (bc cells)."""
from readers import device_idle


def read(run):
    return device_idle(run, "bc")
