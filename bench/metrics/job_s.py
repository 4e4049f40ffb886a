"""Time to solution per job: the window over the jobs completed in it."""


def read(run):
    return run.window_s / len(run.jobs) if run.jobs else None
