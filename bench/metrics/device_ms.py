"""Device: the chip's busy time in the traced window per query answered
there, in milliseconds — the device programs behind one request."""


def read(run):
    t = run.trace
    if not t or not t["chips"] or t["busy_s"] <= 0:
        return None
    n = sum(1 for r in run.queries(done_by=run.t_close)
            if r.get("status") == 200)
    return 1e3 * t["busy_s"] / n if n else None
