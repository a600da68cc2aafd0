"""Process start to window start: JAX start-up, weights, programs from
the compile cache, warm-up (host clock)."""


def read(run):
    return run.setup_s
