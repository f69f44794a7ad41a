"""Merge-on-read: median ``timing.merge_s`` (the ``d4m.merge`` span:
combining what a read found in the ingest table's base and delta) of the
window's answered queries, in milliseconds; None where the server reports
no such field."""
import statistics


def read(run):
    xs = [r["timing"]["merge_s"] * 1e3 for r in run.queries()
          if r.get("status") == 200 and r["timing"]
          and "merge_s" in r["timing"]]
    return statistics.median(xs) if xs else None
