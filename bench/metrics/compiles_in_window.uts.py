"""Programs lowered inside the window (uts cells); should read 0."""
from readers import compiles_in_window


def read(run):
    return compiles_in_window(run, "uts")
