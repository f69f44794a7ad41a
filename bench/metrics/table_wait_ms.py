"""Ingest table lock: nearest-rank 95th percentile of ``timing.table_wait_s``
(the ``d4m.table_wait`` span: waiting for the ingest table's lock) over
the window's answered queries, in milliseconds — the stall a write or a
compaction puts on a read; None where the server reports no such field."""
import math


def read(run):
    xs = sorted(r["timing"]["table_wait_s"] * 1e3 for r in run.queries()
                if r.get("status") == 200 and r["timing"]
                and "table_wait_s" in r["timing"])
    if not xs:
        return None
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)]
