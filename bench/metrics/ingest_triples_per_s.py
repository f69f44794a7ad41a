"""Triples of ingest batches acknowledged within the window, over the
window's seconds."""


def read(run):
    n = sum(r["triples"] for r in run.records
            if r["desc"]["op"] == "ingest" and r.get("status") == 200
            and r["done"] <= run.t_close)
    return n / run.seconds
