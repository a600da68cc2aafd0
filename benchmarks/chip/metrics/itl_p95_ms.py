"""95th percentile of every gap between consecutive output tokens that
ends inside the window, pooled over the requests due in it (wall clock),
in ms."""
import stats


def read(run):
    if run.kind != "serve":
        return None
    gaps = [b - a for r in run.requests
            for a, b in zip(r.token_times, r.token_times[1:])
            if b <= run.seconds]
    return 1e3 * stats.percentile(gaps, 95) if gaps else None
