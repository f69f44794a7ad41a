"""Compaction: write amplification over the window — the entries that
whole-table merges wrote (``/stats`` ``ingest`` ``merge_entries_out``) over
the triples inserted (``insert_triples``) in the cell's ingest table;
None where the server has no such counters or nothing was inserted."""


def table_stats(run):
    """The window's counters of the cell's ingest table, or None."""
    ing = run.spec["cell"].get("ingest")
    t = (run.stats.get("ingest") or {}).get(ing["table"]) if ing else None
    if not t or "merge_entries_out" not in t:
        return None
    return t


def read(run):
    t = table_stats(run)
    if t is None or not t.get("insert_triples"):
        return None
    return t["merge_entries_out"] / t["insert_triples"]
