"""Programs lowered inside the window (bc cells); should read 0."""
from readers import compiles_in_window


def read(run):
    return compiles_in_window(run, "bc")
