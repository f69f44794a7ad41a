"""Wire decode: median ``timing.decode_s`` (the ``d4m.decode`` span) of the
window's answered queries, in milliseconds; None where the server
reports no such field."""
import statistics


def read(run):
    xs = [r["timing"]["decode_s"] * 1e3 for r in run.queries()
          if r.get("status") == 200 and r["timing"]
          and "decode_s" in r["timing"]]
    return statistics.median(xs) if xs else None
