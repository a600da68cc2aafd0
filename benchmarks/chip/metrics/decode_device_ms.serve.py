"""Model step: device time per run of the decode program (the executor's
jitted ``_step``), from the trace (ms)."""

PROGRAM = r"^jit__step$"


def read(run):
    if run.trace is None:
        return None
    hit = run.trace.program(PROGRAM)
    if not hit or not hit[0]:
        return None
    return 1e3 * hit[1] / hit[0]
