"""Engine execution (decode binding, planning, device work, result
formatting): median ``timing.exec_s`` of the window's answered queries,
in milliseconds."""
import statistics


def read(run):
    xs = [r["timing"]["exec_s"] * 1e3 for r in run.queries()
          if r.get("status") == 200 and r["timing"]]
    return statistics.median(xs) if xs else None
