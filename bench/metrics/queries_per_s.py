"""Queries answered within the window, over the window's seconds."""


def read(run):
    n = sum(1 for r in run.queries(done_by=run.t_close)
            if r.get("status") == 200)
    return n / run.seconds
