"""Device selection compaction: the share of the window's selection
compactions that moved their result into a result-sized buffer, in %
(``/stats`` ``compact``: ``sized`` ÷ (``sized`` + ``full``)); None where
the server has no such counter or compacted nothing."""


def read(run):
    c = run.stats.get("compact")
    if not c or not c["sized"] + c["full"]:
        return None
    return 100.0 * c["sized"] / (c["sized"] + c["full"])
