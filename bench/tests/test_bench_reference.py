"""The plain reference against the host ``Assoc`` and the served path.

At CPU sizes: every request class of every mix is answered by the
reference, by the program's host layer (``Assoc``, float64) and, through
the HTTP server over device tables with every kernel in Pallas interpret
mode, by the served path; all three agree.  Ingest reads agree with an
``IngestTable`` fed the same batches."""
import json

import numpy as np
import pytest

import harness
import traffic
from reference import Table, answer, compare_answer, ingest_candidates

WORKLOADS = ["paper18.select", "graph500-s14.twohop", "paper18.ingest",
             "paper18.kinds"]


def _spec_data(checkout, workload, seed):
    spec = harness.load_cell(checkout, workload)
    gen = harness.load_module(
        spec["root"] / "data" / f"{spec['config']['generator']}.py", "g")
    return spec, gen.generate(spec["config"], seed)


def _requests(spec, data, seed):
    """A few of every class (warm-up draws plus the window's)."""
    warm = traffic.warmup_requests(spec["mix"], data["ctx"], seed,
                                   {"per_class": 3, "counts": [1, 3, 8]})
    return [q for q in warm if q["op"] != "ingest"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reference_equals_host_assoc(checkout, workload):
    from repro.core import Assoc
    from repro.serve import from_wire, to_wire
    from repro.serve.engine import format_result
    seed = 2 ** 31 + 99
    spec, data = _spec_data(checkout, workload, seed)
    ref, host = {}, {}
    for name in harness.tables_used(spec["mix"]):
        t = data["tables"][name]
        ref[name] = Table(t["rows"], t["cols"], t["vals"], t["aggregate"])
        host[name] = Assoc(t["rows"], t["cols"], t["vals"],
                           aggregate=t["aggregate"])
    reqs = _requests(spec, data, seed)
    assert {q["cls"] for q in reqs} == {
        c["name"] for st in spec["mix"]["streams"] for c in st["classes"]
        if c["op"] != "ingest"}
    for q in reqs:
        expr = from_wire(to_wire(harness.to_expr(q)), resolve=host.get)
        got = json.loads(json.dumps(format_result(expr.collect(),
                                                  limit=None)))
        want = answer(ref, q)
        if want[0] == "vector":
            # a host-layer degree vector spans the selected rows only,
            # where the device serves every row key: compare by key
            t = ref[q["table"]]
            keys = t.ckeys if q["axis"] == 0 else t.rkeys
            if len(got["vals"]) != len(keys):
                keys = keys[Table.key_mask(keys, q["rows"])]
            d = _by_key(got["vals"], keys, want[2])
            got = {"kind": "triples", "rows": [k for k, _ in d],
                   "cols": ["" for _ in d], "vals": list(d.values()),
                   "truncated": False, "nnz": len(d)}
            want = ("triples", _by_key(want[1], t.ckeys if q["axis"] == 0
                                       else t.rkeys, want[2]))
        wrong, gap = compare_answer(got, want)
        assert wrong == 0 and gap <= 1e-12, (q, wrong, gap)


def _by_key(vals, keys, zero):
    """A dense vector as the triples of its stored (non-zero) entries."""
    vals = np.asarray(vals, np.float64)
    assert len(vals) == len(keys)
    keep = vals != zero
    return dict(zip(((k, "") for k in keys[keep].tolist()),
                    vals[keep].tolist()))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_served_answers_equal_reference(checkout, workload, no_cache,
                                        interpret):
    """A whole run through the server (kernels in interpret mode): every
    kept answer is compared and the run is correct."""
    import time
    out = harness.run_cell(checkout, workload, 2 ** 31 + 5, 2.0, False,
                           t_start=time.monotonic(), require_tpu=False,
                           peak_kind="TPU v5 lite")
    assert out["correct"], out["checks"]
    assert out["checks"]["answers_compared"]["value"] >= 3
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   harness.load_cell(checkout,
                                                     workload)["e2e"]}


def test_ingest_reference_equals_ingest_table(checkout):
    from repro.core import AssocTensor, StartsWith
    from repro.ingest import IngestTable
    spec, data = _spec_data(checkout, "paper18.ingest", 17)
    t = data["tables"]["A"]
    base = Table(t["rows"], t["cols"], t["vals"], "sum")
    table = IngestTable(AssocTensor.from_triples(
        t["rows"], t["cols"], t["vals"], aggregate="sum"), aggregate="sum")
    w = {"batch": 64, "key_hi": 2 ** 8 + 64, "vals": [1, 100]}
    batches = [traffic.ingest_batch(17, i, w) for i in range(5)]
    for b in batches[:3]:
        table.insert(*b)
    snap = table.snapshot().to_assoc()
    q = {"op": "select", "table": "A", "rows": {"kind": "prefix", "p": "1"},
         "cols": None}
    r, c, v = snap[StartsWith("1"), :].triples()
    served = {"kind": "triples", "nnz": len(r), "rows": r.tolist(),
              "cols": c.tolist(), "vals": v.tolist(), "truncated": False}
    cands = dict(ingest_candidates(base, batches, q, 0, 5))
    assert compare_answer(served, cands[3]) == (0, 0.0)
    assert compare_answer(served, cands[2])[0] > 0
    total = {"kind": "scalar", "val": float(np.asarray(
        snap.triples()[2], np.float64).sum())}
    tot = dict(ingest_candidates(base, batches, {"op": "total"}, 0, 5))
    assert compare_answer(total, tot[3])[1] <= 1e-12
    assert compare_answer(total, tot[4])[1] > 1e-6
