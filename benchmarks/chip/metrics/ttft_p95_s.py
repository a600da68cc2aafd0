"""95th percentile over every request due in the window of first-token
time minus due time (wall clock).  A request that never got its first
token counts with the run's end as its first-token time: a lower bound
on its wait that keeps the number finite (the run also counts it as
failed)."""
import stats


def read(run):
    if run.kind != "serve":
        return None
    return stats.percentile(
        [(r.t_first if r.t_first > 0 else max(run.elapsed, r.t_submit))
         - r.t_submit for r in run.requests], 95)
