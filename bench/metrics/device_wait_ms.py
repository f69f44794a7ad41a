"""Device: median ``timing.wait_s`` (the ``d4m.device_wait`` span: waiting
for the device's results) of the window's answered queries, in
milliseconds; None where the server reports no such field."""
import statistics


def read(run):
    xs = [r["timing"]["wait_s"] * 1e3 for r in run.queries()
          if r.get("status") == 200 and r["timing"]
          and "wait_s" in r["timing"]]
    return statistics.median(xs) if xs else None
