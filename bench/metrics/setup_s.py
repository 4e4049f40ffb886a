"""From JAX holding the chip to the first timed job: the program's
imports, the pool, compiles or cache loads, and the warm-up job."""


def read(run):
    return run.setup_s
