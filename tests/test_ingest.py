"""Dynamic ingest tests: LSM delta buffers, merge-on-read parity across
all three layers and the full semiring registry, compaction (including
plan-cache invalidation), the /ingest HTTP path, admission ordering, and
the concurrent ingest+query hammer."""
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import Assoc, AssocTensor, DistAssoc, KeySpace, PLAN_STATS
from repro.core import keyspace as keyspace_mod
from repro.core.semiring import REGISTRY
from repro.ingest import Compactor, IngestTable
from repro.serve import (D4MClient, Engine, ServerError, TableRef,
                         TableRegistry, WireError, ingest_from_wire,
                         ingest_to_wire, start_server, to_wire)

REPO = Path(__file__).resolve().parent.parent


def _mesh1():
    import jax
    return jax.make_mesh((1,), ("data",))


# deliberately nasty triple mix: base↔delta key collisions, duplicates
# WITHIN one delta batch, brand-new row AND col keys sorting before/after
# the existing ranges
_BASE = (["b", "d", "f", "h"], ["x", "y", "x", "z"], [2.0, 3.0, 4.0, 5.0])
_DELTA = (["b", "b", "a", "zz", "d"], ["x", "x", "w", "z", "y"],
          [10.0, 20.0, 1.5, 7.0, 0.5])


def _build(layer, rows, cols, vals, aggregate):
    if layer == "host":
        return Assoc(rows, cols, vals, aggregate=aggregate)
    if layer == "device":
        return AssocTensor.from_triples(rows, cols, vals,
                                        aggregate=aggregate)
    return DistAssoc.from_triples(rows, cols, vals, _mesh1(),
                                  aggregate=aggregate)


def _as_dict(arr):
    a = arr.to_assoc() if not isinstance(arr, Assoc) else arr
    r, c, v = a.triples()
    return {(rk, ck): vv for rk, ck, vv in zip(list(r), list(c), list(v))}


@pytest.mark.parametrize("layer", ["host", "device", "dist"])
@pytest.mark.parametrize("sr_name", sorted(REGISTRY))
def test_merge_on_read_parity_full_semiring_registry(layer, sr_name):
    """base ⊕ delta merge-on-read ≡ one-shot constructor over the
    concatenated triples, for every ⊕ monoid the semiring registry uses
    (collision aggregation order included: delta has in-batch dups AND
    base collisions)."""
    agg = REGISTRY[sr_name].add_kind
    base = _build(layer, *_BASE, agg)
    t = IngestTable(base, aggregate=agg)
    # two batches → multiple delta segments in one merge
    r, c, v = _DELTA
    t.insert(r[:2], c[:2], v[:2])
    t.insert(r[2:], c[2:], v[2:])
    got = _as_dict(t.snapshot())

    oracle = _build(layer, _BASE[0] + r, _BASE[1] + c, _BASE[2] + v, agg)
    want = _as_dict(oracle)
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-4), (k, agg)


def test_host_order_sensitive_aggregate():
    """Host tables accept any Assoc aggregator — 'concat' proves the
    base-first ⊕ ordering survives the overlay merge."""
    base = Assoc(["a", "a"], ["x", "x"], ["u", "v"], aggregate="concat")
    t = IngestTable(base, aggregate="concat")
    t.insert(["a", "b"], ["x", "y"], ["w", "q"])
    got = _as_dict(t.snapshot())
    assert got[("a", "x")] == "uvw"      # base value on the left
    assert got[("b", "y")] == "q"


def test_device_rejects_order_sensitive_aggregate():
    base = AssocTensor.from_triples(*_BASE, aggregate="sum")
    with pytest.raises(ValueError, match="max.*min.*sum"):
        IngestTable(base, aggregate="concat")


def test_snapshot_memoized_until_next_mutation():
    base = AssocTensor.from_triples(*_BASE, aggregate="sum")
    t = IngestTable(base, aggregate="sum")
    assert t.snapshot() is base          # empty delta: stable identity
    t.insert(["a"], ["w"], [1.0])
    s1 = t.snapshot()
    assert t.snapshot() is s1            # memo hit between mutations
    t.insert(["q"], ["w"], [2.0])
    s2 = t.snapshot()
    assert s2 is not s1                  # mutation invalidates the memo
    assert t.info()["merge_hit_rate"] > 0


def test_merge_kernel_matches_concat_oracle():
    """The overlay-scatter merge program ≡ the concat+dedup fallback on
    identical padded operands (the fallback is the semantic oracle)."""
    import jax.numpy as jnp
    from repro.ingest.merge import _merge_concat_prog, _merge_read_prog

    rng = np.random.default_rng(3)
    SENT = np.int32(2**31 - 1)

    def canon(cap, n, ncols):
        r = np.sort(rng.choice(cap * 4, n, replace=False)).astype(np.int32)
        c = rng.integers(0, ncols, n).astype(np.int32)
        v = rng.uniform(0.5, 2.0, n).astype(np.float32)
        pad = cap - n
        return (jnp.asarray(np.concatenate([r, np.full(pad, SENT,
                                                       np.int32)])),
                jnp.asarray(np.concatenate([c, np.full(pad, SENT,
                                                       np.int32)])),
                jnp.asarray(np.concatenate([v, np.zeros(pad, np.float32)])))

    ncols = 16
    br, bc, bv = canon(64, 40, ncols)
    dr, dc, dv = canon(32, 20, ncols)
    for agg in ("sum", "min", "max"):
        r1, c1, v1, n1 = _merge_read_prog(agg)(br, bc, bv, dr, dc, dv,
                                               jnp.int32(ncols))
        r2, c2, v2, n2 = _merge_concat_prog(agg)(br, bc, bv, dr, dc, dv)
        assert int(n1) == int(n2)
        k = int(n1)
        np.testing.assert_array_equal(np.asarray(r1)[:k],
                                      np.asarray(r2)[:k])
        np.testing.assert_array_equal(np.asarray(c1)[:k],
                                      np.asarray(c2)[:k])
        np.testing.assert_allclose(np.asarray(v1)[:k], np.asarray(v2)[:k],
                                   rtol=1e-5)


def test_compaction_preserves_content_and_bumps_version():
    base = DistAssoc.from_triples(*_BASE, _mesh1(), aggregate="sum")
    t = IngestTable(base, aggregate="sum")
    t.insert(*_DELTA)
    before = _as_dict(t.snapshot())
    out = t.compact()
    assert out["compacted"] == len(_DELTA[0]) and out["version"] == 1
    assert t.delta_depth == 0
    assert _as_dict(t.snapshot()) == before
    assert t.compact() == {"compacted": 0, "version": 1}   # idempotent
    # post-compact ingest still lands correctly (routing table refreshed)
    t.insert(["zz"], ["z"], [1.0])
    after = _as_dict(t.snapshot())
    assert after[("zz", "z")] == pytest.approx(before[("zz", "z")] + 1.0)


def test_compaction_invalidates_plan_cache():
    """Regression: plans keyed on a retired base's Source id must be
    dropped at compaction, and the next query must re-plan against the
    new base (stale plans would silently serve pre-ingest data)."""
    from repro.serve.wire import from_wire

    base = AssocTensor.from_triples(*_BASE, aggregate="sum")
    reg = TableRegistry()
    reg.register("t", IngestTable(base, aggregate="sum"))
    payload = to_wire(TableRef("t").sum(axis=None))

    def run():
        return float(from_wire(payload, resolve=reg.resolve)
                     .collect())

    v0 = run()
    assert run() == v0                   # second run is a plan hit
    inv0 = PLAN_STATS["plan_invalidations"]
    tab = reg.ingest_table("t")
    tab.insert(["a"], ["w"], [100.0])
    assert run() == pytest.approx(v0 + 100.0)
    tab.compact()
    assert PLAN_STATS["plan_invalidations"] > inv0
    assert run() == pytest.approx(v0 + 100.0)   # replanned, same answer


def test_registry_ingest_spec_and_resolution():
    reg = TableRegistry.from_specs([
        {"name": "mut", "generator": "random", "n": 16, "nnz": 32,
         "seed": 0, "layer": "device", "ingest": True,
         "compact_threshold": 99},
        {"name": "ro", "generator": "random", "n": 16, "nnz": 32,
         "seed": 1, "layer": "device"},
    ])
    assert reg.ingest_names() == ["mut"]
    assert reg.is_ingest("mut") and not reg.is_ingest("ro")
    assert reg.layer_of("mut") == "device"
    tab = reg.ingest_table("mut")
    assert tab.compact_threshold == 99 and tab.name == "mut"
    with pytest.raises(WireError) as ei:
        reg.ingest_table("ro")
    assert ei.value.code == "not_ingestable"
    # resolve() returns the snapshot (the base while the delta is empty)
    assert reg.resolve("mut") is tab.base
    info = reg.info("mut")
    assert info["ingest"] is True and info["delta_depth"] == 0


def test_wire_ingest_roundtrip_and_validation():
    p = ingest_to_wire("edges", ["r1", "r2"], ["c1", "c2"], [1.0, 2.0])
    name, r, c, v = ingest_from_wire(p)
    assert name == "edges" and list(r) == ["r1", "r2"]
    assert v.dtype.kind == "f" and v[1] == 2.0

    def code_of(payload):
        with pytest.raises(WireError) as ei:
            ingest_from_wire(payload)
        return ei.value.code

    assert code_of([1, 2]) == "bad_payload"
    assert code_of({"version": 99, "ingest": {}}) == "bad_version"
    assert code_of({"version": 1, "ingest": []}) == "bad_payload"
    base = {"table": "t", "rows": ["a"], "cols": ["b"], "vals": [1.0]}
    assert code_of({"version": 1,
                    "ingest": {**base, "table": ""}}) == "bad_batch"
    assert code_of({"version": 1,
                    "ingest": {**base, "rows": []}}) == "bad_batch"
    assert code_of({"version": 1,
                    "ingest": {**base, "vals": [1.0, 2.0]}}) == "bad_batch"
    assert code_of({"version": 1,
                    "ingest": {**base, "rows": ["a", 3]}}) == "bad_batch"


def test_admission_keys_ingest_vs_query_disjoint():
    """Satellite: a mutation must never share a batch key with reads on
    the table it mutates — and two mutations of the same table must."""
    reg = TableRegistry()
    reg.register("mut", IngestTable(
        AssocTensor.from_triples(*_BASE, aggregate="sum")))
    with Engine(reg, workers=1, compact_interval_s=0) as eng:
        qkey = eng._admission_key(to_wire(TableRef("mut")[:, :]))
        assert qkey[0] == "query"
        i1 = eng.submit_ingest(ingest_to_wire("mut", ["a"], ["b"], [1.0]))
        i2 = eng.submit_ingest(ingest_to_wire("mut", ["c"], ["d"], [2.0]))
        assert i1.batch_key == ("ingest", "mut") == i2.batch_key
        assert i1.batch_key != qkey
        i1.wait(30), i2.wait(30)


@pytest.fixture(scope="module")
def ingest_server():
    reg = TableRegistry()
    reg.register("mut", IngestTable(
        AssocTensor.from_triples(*_BASE, aggregate="sum"),
        aggregate="sum", compact_threshold=10_000))
    reg.register("ro", Assoc(*_BASE, aggregate="sum"))
    srv = start_server(reg, workers=2)
    yield srv
    srv.close()


def test_http_ingest_endpoint(ingest_server):
    c = D4MClient(ingest_server.url, timeout=120)
    total0 = c.query(to_wire(TableRef("mut").sum(axis=None)))
    r = c.ingest("mut", ["new1", "b"], ["w", "x"], [6.0, 1.0])
    assert r["result"]["kind"] == "ingest"
    assert r["result"]["accepted"] == 2
    total1 = c.query(to_wire(TableRef("mut").sum(axis=None)))
    assert total1["result"]["val"] == pytest.approx(
        total0["result"]["val"] + 7.0)
    st = c.stats()
    assert "mut" in st["ingest"]
    assert st["ingest"]["mut"]["insert_triples"] >= 2
    assert st["server"]["ingests"] >= 1


def test_http_ingest_errors(ingest_server):
    c = D4MClient(ingest_server.url, timeout=120)
    with pytest.raises(ServerError) as ei:
        c.ingest("ro", ["a"], ["b"], [1.0])
    assert ei.value.status == 400 and ei.value.code == "not_ingestable"
    with pytest.raises(ServerError) as ei:
        c.ingest("ghost", ["a"], ["b"], [1.0])
    assert ei.value.status == 400 and ei.value.code == "unknown_table"
    with pytest.raises(ServerError) as ei:
        c.ingest("mut", ["a"], ["b"], [])
    assert ei.value.status == 400 and ei.value.code == "bad_batch"
    with pytest.raises(ServerError) as ei:
        c.ingest("mut", ["a"], ["b"], ["str_val"])
    assert ei.value.code == "execution_error"   # device table is numeric


def test_http_concurrent_ingest_query_hammer():
    """8 threads — 4 streaming disjoint key ranges into one table, 4
    issuing sum queries THROUGHOUT — then the final state must equal the
    deterministic expected total (⊕=sum commutes, keys are disjoint per
    thread, so interleaving cannot change the answer)."""
    reg = TableRegistry()
    reg.register("mut", IngestTable(
        AssocTensor.from_triples(["seed"], ["c"], [1.0], aggregate="sum"),
        aggregate="sum", compact_threshold=64))
    srv = start_server(reg, workers=4)
    try:
        url = srv.url
        n_writers, n_readers, n_batches, bsz = 4, 4, 6, 8
        errs, partials = [], []
        barrier = threading.Barrier(n_writers + n_readers)

        def writer(wid):
            c = D4MClient(url, timeout=120)
            try:
                barrier.wait(timeout=30)
                for b in range(n_batches):
                    rows = [f"w{wid}r{b}k{i}" for i in range(bsz)]
                    cols = [f"c{i % 3}" for i in range(bsz)]
                    out = c.ingest("mut", rows, cols, [1.0] * bsz)
                    assert out["result"]["accepted"] == bsz
            except Exception as exc:
                errs.append(exc)

        def reader():
            c = D4MClient(url, timeout=120)
            payload = to_wire(TableRef("mut").sum(axis=None))
            try:
                barrier.wait(timeout=30)
                for _ in range(8):
                    partials.append(c.query(payload)["result"]["val"])
            except Exception as exc:
                errs.append(exc)

        threads = [threading.Thread(target=writer, args=(w,))
                   for w in range(n_writers)]
        threads += [threading.Thread(target=reader)
                    for _ in range(n_readers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errs, errs

        want = 1.0 + n_writers * n_batches * bsz
        c = D4MClient(url, timeout=120)
        final = c.query(to_wire(TableRef("mut").sum(axis=None)))
        assert final["result"]["val"] == pytest.approx(want)
        # mid-ingest reads saw monotonically plausible partial sums
        assert all(1.0 <= p <= want + 1e-6 for p in partials)
        # the background compactor ran (threshold 64 < 192 inserted)
        deadline = time.time() + 10
        while time.time() < deadline:
            info = c.stats()["ingest"]["mut"]
            if info["compactions"] >= 1 and info["delta_depth"] == 0:
                break
            time.sleep(0.1)
        assert info["compactions"] >= 1
        assert c.query(to_wire(TableRef("mut").sum(axis=None)))[
            "result"]["val"] == pytest.approx(want)
    finally:
        srv.close()


def test_background_compactor_idle_trigger():
    reg = TableRegistry()
    reg.register("mut", IngestTable(
        AssocTensor.from_triples(*_BASE, aggregate="sum"),
        compact_threshold=10_000))
    comp = Compactor(reg, interval_s=0.02, idle_s=0.05).start()
    try:
        reg.ingest_table("mut").insert(["a"], ["b"], [1.0])
        deadline = time.time() + 10
        while time.time() < deadline:
            if reg.ingest_table("mut").version == 1:
                break
            time.sleep(0.02)
        assert reg.ingest_table("mut").version == 1
        assert reg.ingest_table("mut").delta_depth == 0
    finally:
        comp.stop()


def test_background_compactor_counts_failures(monkeypatch, caplog):
    """A failing compaction is counted in info() and logged, and the loop
    keeps serving the other tables; it is never dropped silently."""
    reg = TableRegistry()
    bad = reg.register("bad", IngestTable(
        AssocTensor.from_triples(*_BASE, aggregate="sum")))
    good = reg.register("good", IngestTable(
        AssocTensor.from_triples(*_BASE, aggregate="sum")))

    def boom(idle_s):
        raise RuntimeError("merge program failed")

    monkeypatch.setattr(bad, "maybe_compact", boom)
    comp = Compactor(reg, interval_s=0.02, idle_s=0.05).start()
    try:
        good.insert(["a"], ["b"], [1.0])
        deadline = time.time() + 10
        while time.time() < deadline:
            if good.version == 1 and bad.info()["compact_errors"] >= 2:
                break
            time.sleep(0.02)
        assert bad.info()["compact_errors"] >= 2
        assert "merge program failed" in caplog.text
        assert good.version == 1 and good.info()["compact_errors"] == 0
    finally:
        comp.stop()


# ---------------------------------------------------------------------------
# satellite riders: union-cache eviction counter, compare.py bootstrap
# ---------------------------------------------------------------------------

def test_union_cache_eviction_counter():
    keyspace_mod.clear_union_cache()
    base = KeySpace(["aa", "bb"])
    for i in range(keyspace_mod._UNION_CACHE_CAP + 8):
        base.union(KeySpace([f"k{i:04d}"]))
    stats = keyspace_mod.UNION_STATS
    assert stats["evictions"] >= 8
    assert len(keyspace_mod._UNION_CACHE) <= keyspace_mod._UNION_CACHE_CAP
    keyspace_mod.clear_union_cache()
    assert keyspace_mod.UNION_STATS["evictions"] == 0


def test_compare_missing_baseline_warns_unless_strict(tmp_path, capsys):
    sys.path.insert(0, str(REPO))
    try:
        from benchmarks.compare import main as compare_main
    finally:
        sys.path.pop(0)
    new = tmp_path / "new.json"
    new.write_text('[{"bench": "x", "impl": "a", "n": 1, '
                   '"seconds": 1.0, "nnz": 100}]')
    missing = str(tmp_path / "nonexistent.json")
    assert compare_main(["--baseline", missing, "--new", str(new)]) == 0
    assert "WARNING" in capsys.readouterr().out
    assert compare_main(["--baseline", missing, "--new", str(new),
                         "--strict"]) == 1
