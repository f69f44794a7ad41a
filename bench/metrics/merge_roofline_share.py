"""Ingest merge kernel: the least HBM time of the window's whole-table
merges over the traced window's device busy time, in percent.

A whole-table merge reads every entry of the base and the delta and
writes every merged entry, 12 bytes each (int32 row and column ranks and
a float32 value): its least time is 12 B × (entries in + entries out)
over the peak HBM bandwidth of ``bench/peaks.json``.  The entries come
from the ``/stats`` ``ingest`` counters; the busy time covers every device
operation of the window, so this is a lower bound on the merges' share of
their own roofline and cannot pass 100 %.  None without the counters, a
trace, or a merge."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_write_amp", Path(__file__).with_name("write_amp.py"))
_wa = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_wa)

ENTRY_BYTES = 12


def read(run):
    t = _wa.table_stats(run)
    tr = run.trace
    if t is None or not tr or tr["busy_s"] <= 0:
        return None
    moved = t["merge_entries_in"] + t["merge_entries_out"]
    if not moved:
        return None
    least = ENTRY_BYTES * moved / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / tr["busy_s"]
