"""Result transfer: median ``timing.to_host_s`` (the ``d4m.to_host`` span:
the copies to the host) of the window's answered queries, in
milliseconds; None where the server reports no such field."""
import statistics


def read(run):
    xs = [r["timing"]["to_host_s"] * 1e3 for r in run.queries()
          if r.get("status") == 200 and r["timing"]
          and "to_host_s" in r["timing"]]
    return statistics.median(xs) if xs else None
