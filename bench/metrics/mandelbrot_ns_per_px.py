"""Device time of the mandelbrot kernel per image pixel delivered."""
from readers import kernel_ns_per_unit


def read(run):
    return kernel_ns_per_unit(run, "mandelbrot", "px")
