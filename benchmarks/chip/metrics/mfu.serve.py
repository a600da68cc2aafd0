"""Model step: the FLOPs the window's decode steps require (live rows at
their own lengths, counted from shapes) over the sum of the decode calls'
host-clock times times the chip's bf16 peak (%)."""
import flops


def read(run):
    calls = [c for c in run.calls
             if c.kind == "decode" and c.start < run.seconds]
    if not calls or run.peak is None:
        return None
    work = sum(flops.decode_flops(run.cfg, c.rows, c.tokens) for c in calls)
    return 100.0 * work / (sum(c.cost for c in calls) * run.peak.bf16_flops)
