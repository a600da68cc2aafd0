"""Output tokens delivered inside the window, over the window's length."""


def read(run):
    if run.kind != "serve":
        return None
    n = sum(1 for r in run.requests for t in r.token_times
            if t <= run.seconds)
    return n / run.seconds
