"""HTTP transport and JSON: median over the window's answered queries of
the client's round trip minus the server's own ``timing.total_s``
(enqueue to result), in milliseconds."""
import statistics


def read(run):
    xs = [(r["done"] - r["send"] - r["timing"]["total_s"]) * 1e3
          for r in run.queries() if r.get("status") == 200 and r["timing"]]
    return statistics.median(xs) if xs else None
