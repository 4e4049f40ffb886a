"""Device busy time of the traced window per source completed.  A bc
cell runs no operation on the device but ``bc_batch``'s, so this is the
sweeps' device time per source."""


def read(run):
    if run.trace is None or run.unit != "sources" or not run.work \
            or not run.trace.busy_ns:
        return None
    return run.trace.busy_ns / run.work
