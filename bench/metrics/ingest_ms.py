"""Ingest write path: median ``timing.ingest_s`` (the own time of the
``d4m.ingest`` span: ranking, canonicalizing, uploading and folding one
batch, lock waits excluded) of the window's acknowledged batches, in
milliseconds; None where the server reports no such field."""
import statistics


def read(run):
    xs = [r["timing"]["ingest_s"] * 1e3 for r in run.records
          if r["desc"]["op"] == "ingest" and r.get("status") == 200
          and r["timing"] and "ingest_s" in r["timing"]]
    return statistics.median(xs) if xs else None
