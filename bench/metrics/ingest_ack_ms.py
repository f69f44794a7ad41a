"""Ingest write path: median ``timing.exec_s`` of the window's
acknowledged ingest batches, in milliseconds."""
import statistics


def read(run):
    xs = [r["timing"]["exec_s"] * 1e3 for r in run.records
          if r["desc"]["op"] == "ingest" and r.get("status") == 200
          and r["timing"]]
    return statistics.median(xs) if xs else None
