"""Result formatting: median ``timing.format_s`` (the ``d4m.format`` span's
own time, copies excluded) of the window's answered queries, in
milliseconds; None where the server reports no such field."""
import statistics


def read(run):
    xs = [r["timing"]["format_s"] * 1e3 for r in run.queries()
          if r.get("status") == 200 and r["timing"]
          and "format_s" in r["timing"]]
    return statistics.median(xs) if xs else None
