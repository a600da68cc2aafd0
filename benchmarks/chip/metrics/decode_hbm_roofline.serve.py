"""Model step: the decode step's least time over its device time (%).

The least time of a traced decode step is the bytes it must read over
HBM bandwidth: every weight it multiplies, once, in the served bf16, and
the live K/V the paged kernel reads (``flops.paged_attn_bytes``).  It is
averaged over the traced decode calls and divided by the mean device time
of a run of the decode program (``jit__step``, as in
``decode_device_ms.serve``).  Where the weights dominate it says how near
decode runs to streaming them."""
import flops

PROGRAM = r"^jit__step$"
WEIGHT_BYTES = 2          # the served bf16


def read(run):
    if run.trace is None or run.peak is None:
        return None
    hit = run.trace.program(PROGRAM)
    calls = [c for c in run.calls if c.kind == "decode" and c.traced]
    if not hit or not hit[0] or not hit[1] or not calls:
        return None
    weights = WEIGHT_BYTES * flops.matmul_params(run.cfg)
    least = sum(weights + flops.paged_attn_bytes(run.cfg, c.rows, c.tokens)
                for c in calls) / (len(calls) * run.peak.hbm_bytes)
    return 100.0 * least / (hit[1] / hit[0])
