"""Admission: median ``timing.queue_s`` (enqueue to a worker taking the
request) of the window's answered queries, in milliseconds."""
import statistics


def read(run):
    xs = [r["timing"]["queue_s"] * 1e3 for r in run.queries()
          if r.get("status") == 200 and r["timing"]]
    return statistics.median(xs) if xs else None
