"""Profiler trace → device busy time, top device operations, idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  Device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per device
operation.  Busy time is the union of those intervals (overlapping ops
count once), averaged over the chips.  An idle gap is a stretch of the
window with no device operation on chip 0; it is named by the host-side
event (any line of a ``/host:`` plane) that overlaps it most, or
``"no host event"``.  Event times are nanoseconds from the start of the
trace, so the window is ``[0, window_s]``.
"""
from __future__ import annotations

import glob
import os
import re

__all__ = ["reduce_trace", "reduce_profile", "find_xplane", "merge"]

_DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


NAME_CHARS = 160      # an XLA op's event name is its whole HLO line


def _events(line):
    for ev in line.events:
        yield ev.name[:NAME_CHARS], float(ev.start_ns), float(ev.duration_ns)


def reduce_profile(planes, window_s: float, top: int = 10) -> dict | None:
    """``planes`` as ``ProfileData.planes`` gives them (anything with
    ``.name`` and ``.lines`` of events with ``name``/``start_ns``/
    ``duration_ns``).  Returns None when no device plane holds an op."""
    lo, hi = 0.0, window_s * 1e9
    devices, host = [], []
    for p in planes:
        if _DEVICE.match(p.name):
            ops = [ev for ln in p.lines if ln.name == OPS_LINE
                   for ev in _events(ln)]
            devices.append((p.name, ops))
        elif p.name.startswith("/host:"):
            host.extend(ev for ln in p.lines for ev in _events(ln)
                        if ev[2] > 0)
    devices = [(n, ops) for n, ops in devices if ops]
    if not devices:
        return None
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    busy, op_time = [], {}
    for _, ops in devices:
        ivs = merge(_clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy.append(sum(e - s for s, e in ivs) / 1e9)
        for name, s, d in ops:
            e = min(s + d, hi)
            if e > max(s, lo):
                op_time[name] = op_time.get(name, 0.0) + (e - max(s, lo))
    n_dev = len(devices)
    device_ops = sorted(((k, v / 1e9 / n_dev) for k, v in op_time.items()),
                        key=lambda kv: -kv[1])[:top]
    # idle gaps on chip 0, named by the host event overlapping them most
    ivs = merge(_clip([(s, s + d) for _, s, d in devices[0][1]], lo, hi))
    gaps, t = [], lo
    for s, e in ivs + [[hi, hi]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:top]
    host.sort(key=lambda ev: ev[1])
    named = []
    for g0, g1 in gaps:
        best, best_ov = "no host event", 0.0
        for name, s, d in host:
            if s >= g1:
                break
            ov = min(g1, s + d) - max(g0, s)
            if ov > best_ov:
                best, best_ov = name, ov
        named.append([best, (g1 - g0) / 1e9])
    return {"busy_s": sum(busy) / n_dev, "window_s": window_s,
            "device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": named, "chips": n_dev}


def reduce_trace(trace_dir: str, window_s: float, top: int = 10):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    return reduce_profile(pd.planes, window_s, top)
