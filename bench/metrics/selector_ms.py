"""Host selection planning: median ``timing.selector_s`` (the
``d4m.selector`` span: selector compile, box planning, bounds and mask
uploads) of the window's answered queries, in milliseconds; None where
the server reports no such field."""
import statistics


def read(run):
    xs = [r["timing"]["selector_s"] * 1e3 for r in run.queries()
          if r.get("status") == 200 and r["timing"]
          and "selector_s" in r["timing"]]
    return statistics.median(xs) if xs else None
