"""Scheduling layer: 95th percentile of admission time minus due time over
the window's requests, on the engine's wall-aligned clock (s).  A request
never served counts with the run's end as its admission: a lower bound
that keeps the number finite (the run also counts it as failed)."""
import stats


def read(run):
    if run.kind != "serve":
        return None
    return stats.percentile(
        [(r.t_admit if r.t_first > 0 else max(run.elapsed, r.t_submit))
         - r.t_submit for r in run.requests], 95)
