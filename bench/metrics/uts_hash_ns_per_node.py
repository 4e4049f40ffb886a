"""Device time of the uts_hash kernel per tree node counted."""
from readers import kernel_ns_per_unit


def read(run):
    return kernel_ns_per_unit(run, "uts_hash", "nodes")
