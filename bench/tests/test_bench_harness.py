"""The harness: found by name, refuses without a chip, fails when the
served path is broken underneath."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import harness

REPO = Path(__file__).resolve().parents[2]


def _run(checkout, workload, seed=2 ** 31 + 3, seconds=2.0):
    return harness.run_cell(checkout, workload, seed, seconds, False,
                            t_start=time.monotonic(), require_tpu=False,
                            peak_kind="TPU v5 lite")


def test_new_cell_mix_and_metric_are_found_by_name(tmp_path, no_cache,
                                                   checkout_maker):
    """A cell, a traffic mix and a per-layer metric added as files only:
    the harness finds and runs them with no edit to its code."""
    co = checkout_maker(tmp_path / "co")
    bench = json.loads((co / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "paper18.scan", "config": "paper18",
                               "traffic": "scan", "chips": 1,
                               "why": "range scans only"})
    bench["per_layer"].append({"name": "scan_answers.scan", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "HTTP transport",
                               "moves": "query_p50_s",
                               "workloads": ["paper18.scan"]})
    bench["end_to_end"][0]["workloads"].append("paper18.scan")
    (co / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = {"shape_seed": 1, "streams": [{"loop": "open", "senders": 4,
                                         "classes": [{
        "name": "range", "op": "select", "table": "A", "axis": "rows",
        "sel": {"kind": "range", "lo": [10, 25]}}]}]}
    (co / "bench/traffic/scan.json").write_text(json.dumps(mix))
    cell = json.loads((co / "bench/workloads/paper18.select.json")
                      .read_text())
    (co / "bench/workloads/paper18.scan.json").write_text(json.dumps(cell))
    (co / "bench/metrics/scan_answers.py").write_text(
        "def read(run):\n"
        "    return sum(r.get('status') == 200 for r in run.queries())\n")
    spec = harness.load_cell(co, "paper18.scan")
    assert [m["name"] for m in spec["per_layer"]] == ["scan_answers.scan"]
    assert {m["name"] for m in spec["e2e"]} == {"query_p50_s", "setup_s"}
    out = _run(co, "paper18.scan", seconds=1.0)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"query_p50_s", "setup_s"}
    cellobj = harness.Cell(co, "paper18.scan", 5, require_tpu=False,
                           peak_kind="TPU v5 lite")
    try:
        w = cellobj.window(1.0, False, t_start=time.monotonic())
    finally:
        cellobj.close()
    traced = harness.evaluate(cellobj, dict(w, trace={
        "busy_s": 0.0, "window_s": 1.0, "device_ops": [], "idle_gaps": [],
        "chips": 0}), True)
    assert traced["metrics"]["scan_answers.scan"]["value"] > 0


def test_unknown_device_kind_is_an_error(checkout):
    with pytest.raises(harness.SpecError, match="TPU v9"):
        harness.peak_for(checkout / "bench", "TPU v9")
    peaks = harness.peak_for(checkout / "bench", "TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9


def test_no_tpu_exits_nonzero_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "paper18.select", "--seed", str(2 ** 33),
                        "--seconds", "1", "--trace", "0"], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "platform 'cpu'" in p.stderr


def test_benchmark_files_alone_exit_nonzero(checkout):
    """In a directory holding only BENCHMARK.json and bench/, there is no
    program to run: the command fails and prints no result."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "paper18.select", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=checkout, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# -- faults planted in the served path: the run is not correct ----------------

def _alter(result):
    """An answer altered where it is produced: its first value moves."""
    if result.get("kind") == "scalar":
        result["val"] += 1.0
    elif result.get("vals"):
        result["vals"][0] += 1.0
    return result


def _halve(result):
    """Half of the answer left out."""
    for k in ("rows", "cols", "vals"):
        if k in result:
            result[k] = result[k][:len(result[k]) // 2]
    if result.get("kind") == "vector":
        result["vals"] = result["vals"][:len(result["vals"]) // 2]
    return result


@pytest.mark.parametrize("fault,check", [(_alter, "max_rel_gap"),
                                         (_halve, "wrong_entries")])
@pytest.mark.parametrize("workload", ["paper18.select",
                                      "graph500-s14.twohop"])
def test_fault_in_the_answer_is_caught(checkout, no_cache, monkeypatch,
                                       fault, check, workload):
    from repro.serve import engine
    orig = engine.format_result
    monkeypatch.setattr(engine, "format_result",
                        lambda res, limit=None: fault(orig(res, limit)))
    out = _run(checkout, workload)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]


def test_insert_that_leaves_the_table_unchanged_is_caught(
        checkout, no_cache, monkeypatch):
    """A write acknowledged but not applied (the state returned
    unchanged): reads miss it."""
    from repro.ingest import IngestTable

    def insert(self, rows, cols, vals):
        return {"accepted": len(rows), "delta_depth": self.delta_depth}

    monkeypatch.setattr(IngestTable, "insert", insert)
    out = _run(checkout, "paper18.ingest")
    assert not out["correct"]
    c = out["checks"]
    assert (c["wrong_entries"]["value"] > 0
            or c["max_rel_gap"]["value"] > c["max_rel_gap"]["limit"]), c
