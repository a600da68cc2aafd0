"""Kernel: the paged-attention kernel's least time over its trace time
(%).  The least time of each traced decode step is the larger of its
bytes over HBM bandwidth and its FLOPs over the bf16 peak, both counted
from the live lengths; at decode the bytes bound it."""
import flops

KERNEL = r"paged_attention"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds = run.trace.op_seconds(KERNEL)
    calls = [c for c in run.calls if c.kind == "decode" and c.traced]
    if seconds <= 0 or not calls:
        return None
    least = sum(max(flops.paged_attn_bytes(run.cfg, c.rows, c.tokens)
                    / run.peak.hbm_bytes,
                    flops.paged_attn_flops(run.cfg, c.tokens)
                    / run.peak.bf16_flops) for c in calls)
    return 100.0 * least / seconds
