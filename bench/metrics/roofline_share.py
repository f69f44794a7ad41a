"""Kernels/device: the least time the chip needs for the useful work of
the two-hop queries answered in the traced window, over the device's
busy time there, in percent.

Useful work is counted from the generated graph, whatever strategy the
planner picks (``bench/work.py``).  The least time of a query is the
larger of its operations over the peak FLOP/s and its bytes over the peak
HBM bandwidth; f32 semiring work is counted against the bf16 peak, the
only one published."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_work", Path(__file__).resolve().parents[1] / "work.py")
_work = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_work)


def read(run):
    t = run.trace
    if not t or t["busy_s"] <= 0:
        return None
    least = 0.0
    for r in run.queries(done_by=run.t_close):
        q = r["desc"]
        if q["op"] != "twohop" or r.get("status") != 200:
            continue
        ops, nbytes = _work.twohop_work(run.ref_table(q["table"]), q)
        least += max(ops / run.peaks["flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    if least == 0.0:
        return None
    return 100.0 * least / t["busy_s"]
