"""The ``paper18-ingest.readwrite`` cell: its checked-in files load, each
of its per-layer readers reads its value from a recorded run (and None
without one), its control fails the check, and a CPU-sized run of it is
correct."""
import time
from pathlib import Path

import pytest

import control
import harness

REPO = Path(__file__).resolve().parents[2]
CELL = "paper18-ingest.readwrite"
# the other configurations cut to CPU sizes as ``conftest.SMALL`` cuts them
SMALL = {"paper18": {"n": 8}, "graph500-s14": {"SCALE": 9}}
READERS = ["merge_ms.readwrite", "table_wait_ms.readwrite",
           "ingest_ms.readwrite", "write_amp.readwrite",
           "merge_roofline_share.readwrite"]


def test_cell_loads_from_the_checked_in_files():
    spec = harness.load_cell(REPO, CELL)
    assert spec["config"]["n"] == 18 and spec["config"]["aggregate"] == "sum"
    assert spec["cell"]["ingest"] == {"table": "A",
                                      "compact_threshold": 262144}
    assert spec["cell"]["rate_per_s"] == 10
    assert [st["senders"] for st in spec["mix"]["streams"]] == [16, 1]
    writer = spec["mix"]["streams"][1]["classes"][0]
    assert (writer["op"], writer["batch"]) == ("ingest", 2048)
    assert {m["name"] for m in spec["e2e"]} == {"query_p50_s",
                                                "query_p95_s", "setup_s"}
    assert [m["name"] for m in spec["per_layer"]] == READERS


def _rec(op, timing, status=200):
    return {"desc": {"op": op}, "status": status, "timing": timing}


def _run(stats=True, trace=True):
    """A recorded window: three reads, two acknowledged batches, the
    table's counters and a trace."""
    reads = [_rec("select", {"merge_s": m, "table_wait_s": w})
             for m, w in [(0.001, 0.0), (0.003, 0.002), (0.002, 0.010)]]
    writes = [_rec("ingest", {"ingest_s": x}) for x in (0.004, 0.006)]
    st = {"ingest": {"A": {"insert_triples": 4096,
                           "merge_entries_in": 2_000_000,
                           "merge_entries_out": 1_000_000}}} if stats else {}
    return harness.Run(
        records=reads + writes, lost=[], stats=st,
        trace={"busy_s": 2.0, "window_s": 51.0} if trace else None,
        peaks={"hbm_bytes_per_s": 819e9},
        spec={"cell": {"ingest": {"table": "A"}}})


@pytest.mark.parametrize("name,want", [
    ("merge_ms.readwrite", 2.0),
    ("table_wait_ms.readwrite", 10.0),
    ("ingest_ms.readwrite", 5.0),
    ("write_amp.readwrite", 1_000_000 / 4096),
    ("merge_roofline_share.readwrite",
     100.0 * 12 * 3_000_000 / 819e9 / 2.0),
])
def test_reader_reads_its_value_and_none_without(name, want):
    read = harness.reader(REPO / "bench", name)
    assert read(_run()) == pytest.approx(want)
    bare = _run(stats=False, trace=False)
    for r in bare.records:
        r["timing"] = {"exec_s": 0.001}     # a server without the fields
    assert read(bare) is None


@pytest.fixture(scope="module")
def checkout14(tmp_path_factory, checkout_maker):
    # n = 14: keys of five digits, so a four-byte key is ambiguous
    return checkout_maker(tmp_path_factory.mktemp("ingest14"),
                          {**SMALL, "paper18-ingest": {"n": 14}})


@pytest.mark.parametrize("seed", [2 ** 31 + 1, 2 ** 32 + 9])
def test_control_fails_the_limit(checkout14, seed):
    limits = harness.load_cell(checkout14, CELL)["cell"]["compare"]["limits"]
    r = control.readings(checkout14, CELL, seed, 10.0, 10)
    assert r["answers_compared"] >= 10
    assert r["wrong_entries"] > limits["wrong_entries"], r


def test_cell_runs_correct_at_cpu_size(tmp_path, no_cache, checkout_maker):
    co = checkout_maker(tmp_path / "co",
                        {**SMALL, "paper18-ingest": {"n": 8}})
    cell = harness.Cell(co, CELL, 2 ** 31 + 77, require_tpu=False,
                        peak_kind="TPU v5 lite")
    try:
        w = cell.window(2.0, False, t_start=time.monotonic())
    finally:
        cell.close()
    out = harness.evaluate(cell, dict(w, trace={
        "busy_s": 1.0, "window_s": 2.0, "device_ops": [], "idle_gaps": [],
        "chips": 1}), True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["checks"]["answers_compared"][
        "value"] >= 1
    assert w["warm_batches"] == 16
    assert out["window_compiles"] == 0          # the warm-up compiled all
    for name in READERS[:4]:
        assert name in out["metrics"], (name, out["metrics"])
