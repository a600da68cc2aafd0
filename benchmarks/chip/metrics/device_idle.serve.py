"""Device: 1 - (union of the intervals in which an operation ran on the
device) / (traced window), in %, from the trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
