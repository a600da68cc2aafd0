"""Executor layer: mean host-clock cost the executor returns for its
prefill() calls in the window (ms)."""


def read(run):
    costs = [c.cost for c in run.calls
             if c.kind == "prefill" and c.start < run.seconds]
    return 1e3 * sum(costs) / len(costs) if costs else None
