"""Trace reduction on recorded traces, and the useful-work counter."""
import importlib.util
import itertools
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
_spec = importlib.util.spec_from_file_location("bench_trace_t",
                                               BENCH / "trace.py")
tr = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tr)
_spec = importlib.util.spec_from_file_location("bench_work_t",
                                               BENCH / "work.py")
work = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(work)

# recorded on one TPU v5 lite: three rounds of a 20 ms host span
# ("host_gap") followed by a jitted sort and a jitted matmul
TPU_WINDOW_S = 0.31133909300000084


def test_tpu_trace_busy_ops_and_gaps():
    from jax.profiler import ProfileData
    out = tr.reduce_trace(str(DATA / "tpu_small_trace"), TPU_WINDOW_S)
    assert out["chips"] == 1 and out["window_s"] == TPU_WINDOW_S
    # busy time, recomputed on a microsecond grid from the raw events
    pd = ProfileData.from_file(tr.find_xplane(str(DATA /
                                                  "tpu_small_trace")))
    grid = np.zeros(int(TPU_WINDOW_S * 1e6) + 1, bool)
    for p in pd.planes:
        if p.name == "/device:TPU:0":
            for ln in p.lines:
                if ln.name == "XLA Ops":
                    for ev in ln.events:
                        a = int(ev.start_ns // 1000)
                        b = int(-(-(ev.start_ns + ev.duration_ns) // 1000))
                        grid[a:b] = True
    assert out["busy_s"] > 0
    assert abs(out["busy_s"] - grid.sum() / 1e6) < 2e-5
    ops = dict(out["device_ops"])
    assert any(k.startswith("%sort") for k in ops)
    assert sum(ops.values()) >= out["busy_s"] - 1e-9
    assert len(out["idle_gaps"]) <= 10
    gaps = [g for g in out["idle_gaps"] if g[0] == "host_gap"]
    assert len([g for g in gaps if 0.015 < g[1] < 0.1]) >= 3


def test_cpu_trace_has_no_device_plane():
    assert tr.reduce_trace(str(DATA / "cpu_small_trace"), 0.05) is None


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_overlaps_count_once_and_gaps_are_named():
    ms = 1e6
    dev = _Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev("a", 0, 2 * ms), _Ev("b", 1 * ms, 2 * ms),     # overlap: 0-3
        _Ev("a", 6 * ms, 1 * ms), _Ev("c", 9.5 * ms, 5 * ms)])])
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("plan_matmul", 3 * ms, 2.5 * ms), _Ev("json", 7 * ms, 0.2 * ms),
        _Ev("format", 7.2 * ms, 2 * ms)])])
    out = tr.reduce_profile([dev, host], 0.010)
    assert out["busy_s"] == pytest.approx(0.0045)       # 3 + 1 + 0.5 ms
    assert dict(out["device_ops"]) == pytest.approx(
        {"a": 0.003, "b": 0.002, "c": 0.0005})
    assert out["idle_gaps"] == [["plan_matmul", pytest.approx(0.003)],
                                ["format", pytest.approx(0.0025)]]


def _brute_twohop(t, q):
    """Count every (i, k, j) with A_sel[i, k] and G[k, j] stored."""
    rows = set(np.flatnonzero(t.key_mask(t.rkeys, q["rows"])).tolist())
    ent = set(zip(t.r.tolist(), t.c.tolist()))
    a = [(i, t.ckeys[k]) for i, k in ent if i in rows]
    g_by_row = {}
    for i, j in ent:
        g_by_row.setdefault(t.rkeys[i], []).append(j)
    products = sum(len(g_by_row.get(k, [])) for _, k in a)
    touched = {k for _, k in a if k in g_by_row}
    nbytes = 12 * (len(a) + sum(len(g_by_row[k]) for k in touched))
    nbytes += 4 * (len(t.rkeys) if q["axis"] == 1 else len(t.ckeys))
    return 2 * products, nbytes


@pytest.mark.parametrize("roots,axis", itertools.product(
    [["0"], ["1", "5", "17"], [str(i) for i in range(0, 64, 3)]], [0, 1]))
def test_twohop_work_against_brute_force(roots, axis):
    import sys
    sys.path.insert(0, str(BENCH))
    from reference import Table
    spec = importlib.util.spec_from_file_location(
        "kron_t", BENCH / "data" / "kronecker.py")
    kron = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kron)
    d = kron.generate({"SCALE": 6, "edgefactor": 16, "A": 0.57, "B": 0.19,
                       "C": 0.19, "aggregate": "min"}, 3)["tables"]["G"]
    t = Table(d["rows"], d["cols"], d["vals"], "min")
    q = {"rows": {"kind": "keys", "keys": roots}, "axis": axis}
    assert work.twohop_work(t, q) == _brute_twohop(t, q)
