"""Median client-side latency of the window's queries (ingest batches
excluded).  An open loop times a query from when it was due, a closed
loop from when it was sent; a failed or lost query counts as infinite."""
import math


def latencies(run):
    out = []
    for r in run.queries() + [r for r in run.lost
                              if r["desc"]["op"] != "ingest"]:
        if r.get("status") != 200:
            out.append(math.inf)
        else:
            start = r["due"] if r.get("due") is not None else r["send"]
            out.append(r["done"] - start)
    return out


def pct(xs, q):
    xs = sorted(xs)
    if not xs:
        return None
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def read(run):
    return pct(latencies(run), 50)
