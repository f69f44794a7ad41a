"""Program spans in a profiler trace: the stages of each request, and the
device's idle and busy time split by the ``d4m.*`` span open at the time.

The program (``repro.trace``) opens its ``d4m.*`` spans as
``TraceAnnotation``s; they land on the lines of a ``/host:`` plane, one
line per thread, on the clock of the device's events.  From the same
``.xplane.pb`` that ``trace.py`` reads, :func:`reduce_spans` returns:

* ``threads``: per line that holds spans, per span name: the count, total
  and median seconds, and the same of the ``own`` part that no nested
  span covers; ``spans`` the same over all lines;
* ``idle_by_span`` (None without a device plane): the window's
  device-idle time on chip 0, split by the innermost ``d4m.*`` span open
  at that instant on any thread (the one started last); idle time with
  none open is ``"no request in flight"``.  The parts sum to ``idle_s``.
  ``idle_in_flight_s`` is the idle time in which some ``d4m.request``
  span is open;
* ``device_by_span``: each device ``XLA Modules`` execution attributed to
  the innermost span, on the launching thread, that encloses the
  launch's ``DoEnqueueProgram`` event (the two share a ``run_id``), in
  seconds per span name and module name.  A runtime may record launches
  on lines of its own; such a line is matched to the worker (a line
  holding ``d4m.execute``) with a span open at the most of its launches,
  at least twice as many as any other worker.  A launch whose line has no
  match is attributed by time alone, where exactly one worker has a span
  open.  ``attributed_share`` is the share
  of module time attributed, in percent.

Times are nanoseconds from the start of the trace; the window is
``[start_s, start_s + window_s]`` in seconds.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs one window of a cell on the chip under the profiler, as ``run.py``
does, and prints one JSON line: the window's end-to-end metrics and
checks beside the reduction.  ``--keep DIR`` keeps the trace.
"""
from __future__ import annotations

import bisect
import heapq
import importlib.util
import statistics
import sys
import time
from pathlib import Path

T_START = time.monotonic()

BENCH = Path(__file__).resolve().parent
_spec = importlib.util.spec_from_file_location("bench_trace_for_spans",
                                               BENCH / "trace.py")
_trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_trace)

__all__ = ["reduce_spans", "spans_of_trace", "PREFIX", "IDLE_NONE"]

PREFIX = "d4m."
IDLE_NONE = "no request in flight"
LAUNCH = "DoEnqueueProgram"
MODULES_LINE = "XLA Modules"


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


class _Line:
    """One thread's spans, nested: each ``[start, end, name, own,
    depth]``, with ``own`` = end − start − its direct children."""

    def __init__(self, name, spans):
        self.name = name
        spans.sort(key=lambda s: (s[0], -s[1]))
        stack = []
        for s in spans:
            while stack and stack[-1][1] <= s[0]:
                stack.pop()
            if stack:
                stack[-1][3] -= s[1] - s[0]
            s += [s[1] - s[0], len(stack)]
            stack.append(s)
        self.spans = spans
        self.starts = [s[0] for s in spans]
        self.worker = any(s[2] == PREFIX + "execute" for s in spans)

    def innermost(self, t):
        """The innermost span open at ``t`` (the latest started), or
        None."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            s = self.spans[i]
            if s[1] > t:
                return s
            if s[4] == 0:              # a closed outermost span: none open
                return None
            i -= 1
        return None


def _segments(spans, lo, hi):
    """Label ``[lo, hi]`` by the latest-started span open at each instant
    (spans as ``(start, end, name)`` from any line): sorted disjoint
    ``(t0, t1, name)`` covering the window."""
    points = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                                if lo < t < hi})
    by_start = sorted(spans)
    heap, i, out = [], 0, []
    for a, b in zip(points, points[1:]):
        while i < len(by_start) and by_start[i][0] <= a:
            s, e, name = by_start[i]
            heapq.heappush(heap, (-s, e, name))
            i += 1
        label = IDLE_NONE
        while heap:                    # drop spans ended by ``a``
            if heap[0][1] > a:
                label = heap[0][2]
                break
            heapq.heappop(heap)
        out.append((a, b, label))
    return out


def _summary(per):
    """Per span name, from ``[(duration, own)]`` in ns: the count, total
    and median seconds, and the same of the own time."""
    return {n: {"n": len(v), "total_s": sum(d for d, _ in v) / 1e9,
                "own_s": sum(o for _, o in v) / 1e9,
                "median_s": statistics.median(d for d, _ in v) / 1e9,
                "median_own_s": statistics.median(o for _, o in v) / 1e9}
            for n, v in sorted(per.items())}


def _match(launches, workers):
    """The worker with a span open at the most of ``launches``, if at
    least twice as many as any other worker's."""
    votes = sorted(((sum(w.innermost(t) is not None for t, _ in launches),
                     i) for i, w in enumerate(workers)), reverse=True)
    if votes and votes[0][0] and (len(votes) == 1
                                  or votes[0][0] >= 2 * votes[1][0]):
        return workers[votes[0][1]]
    return None


def _overlap(ivs_a, ivs_b):
    """Pairs of overlaps between two sorted disjoint interval lists:
    yields ``(t0, t1, i_a, i_b)``."""
    i = j = 0
    while i < len(ivs_a) and j < len(ivs_b):
        s = max(ivs_a[i][0], ivs_b[j][0])
        e = min(ivs_a[i][1], ivs_b[j][1])
        if e > s:
            yield s, e, i, j
        if ivs_a[i][1] <= ivs_b[j][1]:
            i += 1
        else:
            j += 1


def reduce_spans(planes, window_s: float, start_s: float = 0.0) -> dict:
    """``planes`` as ``ProfileData.planes`` gives them (or doubles with
    ``name``, ``lines``, and events with ``name``/``start_ns``/
    ``duration_ns``/``stats``)."""
    lo, hi = start_s * 1e9, (start_s + window_s) * 1e9
    lines, launch_lines, modules, chips = [], [], [], []
    for p in planes:
        if _trace._DEVICE.match(p.name):
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ln in p.lines if ln.name == _trace.OPS_LINE
                   for ev in ln.events]
            if ops:
                chips.append((int(p.name.rsplit(":", 1)[1]), ops))
            modules += [(float(ev.start_ns), float(ev.duration_ns),
                         ev.name.split("(")[0], _stat(ev, "run_id"))
                        for ln in p.lines if ln.name == MODULES_LINE
                        for ev in ln.events]
        elif p.name.startswith("/host:"):
            for ln in p.lines:
                spans, launches = [], []
                for ev in ln.events:
                    if ev.name.startswith(PREFIX):
                        spans.append([float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns),
                                      ev.name])
                    elif ev.name == LAUNCH:
                        launches.append((float(ev.start_ns),
                                         _stat(ev, "run_id")))
                line = _Line(ln.name, spans) if spans else None
                if line:
                    lines.append(line)
                if launches:
                    launch_lines.append((line, launches))

    threads, every = [], {}
    for line in lines:
        per = {}
        for s, e, n, own, _ in line.spans:
            if lo <= s < hi:
                per.setdefault(n, []).append((e - s, own))
                every.setdefault(n, []).append((e - s, own))
        threads.append({"line": line.name, "spans": _summary(per)})

    # idle time on chip 0, split by the innermost span open
    idle_s = in_flight = idle_by = None
    if chips:
        idle_by = {}
        all_spans = [tuple(s[:3]) for line in lines for s in line.spans]
        idle, t = [], lo
        for s, e in _trace.merge(_trace._clip(min(chips)[1], lo, hi)) + [
                [hi, hi]]:
            if s > t:
                idle.append((t, s))
            t = max(t, e)
        idle_s = sum(e - s for s, e in idle) / 1e9
        segs = _segments(all_spans, lo, hi)
        for s, e, _, j in _overlap(idle, segs):
            idle_by[segs[j][2]] = (idle_by.get(segs[j][2], 0.0)
                                   + (e - s) / 1e9)
        req = _trace.merge(_trace._clip(
            [(s, e) for s, e, n in all_spans if n == PREFIX + "request"],
            lo, hi))
        in_flight = sum(e - s for s, e, _, _ in _overlap(idle, req)) / 1e9

    # device modules → the span that launched them
    workers = [x for x in lines if x.worker]
    launch_of = {}                     # run_id → (time, span line)
    for line, launches in launch_lines:
        if line is None:               # a runtime's own line
            line = _match(launches, workers)
        launch_of.update((rid, (t, line)) for t, rid in launches
                         if rid is not None)
    by_span, links = {}, {"thread": 0, "time": 0, "none": 0}
    dev_total = dev_attr = 0.0
    for s, d, mod, rid in modules:
        d = min(s + d, hi) - max(s, lo)
        if d <= 0:
            continue
        dev_total += d
        span = None
        if rid in launch_of:
            t, line = launch_of[rid]
            if line is not None:
                span = line.innermost(t)
                links["thread" if span else "none"] += 1
            else:
                open_ = [x for x in (w.innermost(t) for w in workers)
                         if x is not None]
                span = open_[0] if len(open_) == 1 else None
                links["time" if span else "none"] += 1
        else:
            links["none"] += 1
        if span is not None:
            dev_attr += d
            per = by_span.setdefault(span[2], {})
            per[mod] = per.get(mod, 0.0) + d / 1e9
    return {"window_s": window_s, "spans": _summary(every),
            "threads": threads, "idle_s": idle_s,
            "idle_by_span": idle_by, "idle_in_flight_s": in_flight,
            "idle_in_flight_share": (None if in_flight is None
                                     else 100.0 * in_flight / window_s),
            "device_s": dev_total / 1e9, "device_by_span": by_span,
            "attributed_share": (100.0 * dev_attr / dev_total
                                 if dev_total else None),
            "launch_links": links}


def spans_of_trace(trace_dir: str, window_s: float, start_s: float = 0.0):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(_trace.find_xplane(trace_dir))
    return reduce_spans(pd.planes, window_s, start_s)


# -- one traced window, the trace kept ----------------------------------------

def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import tempfile
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="directory to keep the profiler trace in")
    args = ap.parse_args(argv)
    checkout = BENCH.parent
    sys.path.insert(0, str(checkout / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    import harness
    cell = harness.Cell(checkout, args.workload, args.seed)
    tdir = args.keep or tempfile.mkdtemp(prefix="bench-spans-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        t_tr0 = time.monotonic()
        try:
            w = cell.window(args.seconds, False, t_start=T_START)
        finally:
            jax.profiler.stop_trace()
        out = spans_of_trace(tdir, w["t_close"] - w["t0"], w["t0"] - t_tr0)
    finally:
        cell.close()
        if not args.keep:
            shutil.rmtree(tdir, ignore_errors=True)
    res = harness.evaluate(cell, w, False)
    timing = [r["timing"] for r in w["records"]
              if r.get("status") == 200 and r.get("timing")]
    out["median_timing_s"] = {k: statistics.median(t[k] for t in timing)
                              for k in (timing[0] if timing else {})}
    out.update(correct=res["correct"], metrics=res["metrics"],
               checks=res["checks"], stats=w["stats"])
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
