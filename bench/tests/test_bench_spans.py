"""Program spans in a trace: idle and device time split by the span open,
launches linked to their spans by ``run_id``."""
import importlib.util
import json
from pathlib import Path

import pytest

from test_bench_trace import _Ev, _Line, _Plane

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
_spec = importlib.util.spec_from_file_location("bench_spans_t",
                                               BENCH / "spans.py")
sp = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sp)
_spec = importlib.util.spec_from_file_location("bench_trace_t2",
                                               BENCH / "trace.py")
tr = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tr)

MS = 1e6
# recorded on one TPU v5 lite by record_spans_trace.py: three served
# one-row reads of a 1,024-triple table; every event ends by 0.106 s
SPANS_WINDOW_S = 0.11


def _ev(name, start_ms, end_ms, **stats):
    ev = _Ev(name, start_ms * MS, (end_ms - start_ms) * MS)
    ev.stats = list(stats.items())
    return ev


def _device(modules):
    """A chip whose operations are exactly ``modules``
    (``(name, start, end, run_id)`` in ms)."""
    return _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_ev(n, s, e, run_id=r)
                              for n, s, e, r in modules]),
        _Line("XLA Ops", [_ev("op", s, e) for _, s, e, _ in modules])])


def _flat(by_span):
    return {(s, m): v for s, mods in by_span.items()
            for m, v in mods.items()}


def _spans(line, spans):
    return _Line(line, [_ev(n, s, e) for n, s, e in spans])


def _launches(line, launches):
    return _Line(line, [_ev("DoEnqueueProgram", t, t + 0.01, run_id=r)
                        for t, r in launches])


def test_idle_split_by_innermost_span_and_launches_linked_by_run_id():
    dev = _device([("jit_gather(12)", 2, 4, 1), ("jit_sort(7)", 6, 9, 2)])
    host = _Plane("/host:CPU", [
        _spans("python", [("d4m.request", 1, 10), ("d4m.decode", 1, 1.5)]),
        _spans("python", [("d4m.execute", 1.6, 9.5),
                          ("d4m.select", 1.7, 5.5),
                          ("d4m.compact", 1.8, 2.2),
                          ("d4m.format", 5.5, 9.4),
                          ("d4m.device_wait", 5.6, 9.2)]),
        _launches("main/7", [(2.0, 1), (5.0, 2)])])
    out = sp.reduce_spans([dev, host], 0.012)
    # idle [0,2] [4,6] [9,12]; a request is open over [1,10]
    assert out["idle_s"] == pytest.approx(0.007)
    assert out["idle_in_flight_s"] == pytest.approx(0.004)
    assert out["idle_in_flight_share"] == pytest.approx(100 * 4 / 12)
    # the span started last wins, across threads
    assert out["idle_by_span"] == pytest.approx({
        sp.IDLE_NONE: 0.003, "d4m.decode": 0.0005, "d4m.request": 0.0006,
        "d4m.execute": 0.0002, "d4m.select": 0.0016,
        "d4m.compact": 0.0002, "d4m.format": 0.0003,
        "d4m.device_wait": 0.0006})
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])
    # the runtime's launch line is the worker's: run ids name the span
    assert _flat(out["device_by_span"]) == pytest.approx({
        ("d4m.compact", "jit_gather"): 0.002,
        ("d4m.select", "jit_sort"): 0.003})
    assert out["attributed_share"] == pytest.approx(100.0)
    assert out["launch_links"] == {"thread": 2, "time": 0, "none": 0}
    worker = out["threads"][1]["spans"]
    assert worker["d4m.execute"]["own_s"] == pytest.approx(0.0002)
    assert worker["d4m.format"]["own_s"] == pytest.approx(0.0003)


def test_unmatched_launch_line_falls_back_to_the_one_open_worker():
    dev = _device([("a", 1.2, 1.5, 1), ("b", 3.6, 4.0, 2),
                   ("c", 6.1, 7.1, 3)])
    host = _Plane("/host:CPU", [
        _spans("w0", [("d4m.execute", 0, 4)]),
        _spans("w1", [("d4m.execute", 3, 8), ("d4m.compact", 5, 7)]),
        # as often inside one worker's spans as the other's: no match
        _launches("runtime", [(1.0, 1), (3.5, 2), (6.0, 3)])])
    out = sp.reduce_spans([dev, host], 0.010)
    assert out["launch_links"] == {"thread": 0, "time": 2, "none": 1}
    assert _flat(out["device_by_span"]) == pytest.approx({
        ("d4m.execute", "a"): 0.0003, ("d4m.compact", "c"): 0.001})
    assert out["attributed_share"] == pytest.approx(100 * 1.3 / 1.7)


def test_reduce_profile_on_the_recorded_fixtures_is_unchanged():
    golden = json.loads((DATA / "reduce_profile_golden.json").read_text())
    assert tr.reduce_trace(str(DATA / "tpu_small_trace"),
                           golden["tpu_small_trace"]["window_s"]) == \
        golden["tpu_small_trace"]
    assert tr.reduce_trace(str(DATA / "cpu_small_trace"), 0.05) is None
    assert golden["cpu_small_trace"] is None


def test_chip_trace_links_the_compaction_to_its_span():
    out = sp.spans_of_trace(str(DATA / "tpu_spans_trace"), SPANS_WINDOW_S)
    assert out["launch_links"]["thread"] >= 1
    assert out["device_by_span"]["d4m.compact"]["jit_gather"] > 0
    assert out["attributed_share"] > 95
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        out["idle_s"])
    names = {n for t in out["threads"] for n in t["spans"]}
    assert {"d4m.request", "d4m.execute", "d4m.select", "d4m.compact",
            "d4m.device_wait", "d4m.to_host", "d4m.format"} <= names
