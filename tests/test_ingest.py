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
    assert t.snapshot() is t.base        # empty delta: stable identity
    assert t.snapshot() is t.snapshot()
    t.insert(["a"], ["w"], [1.0])
    s1 = t.snapshot()
    assert t.snapshot() is s1            # memo hit between mutations
    t.insert(["q"], ["w"], [2.0])
    s2 = t.snapshot()
    assert s2 is not s1                  # mutation invalidates the memo
    assert t.info()["merge_hit_rate"] > 0


def _host_dict(rows, cols, vals, op):
    """⊕ of triples by (row, col) in order, zero results dropped: the
    plain reference of a canonical merge."""
    out = {}
    for r, c, v in zip(rows, cols, vals):
        k = (int(r), int(c))
        out[k] = op(out[k], float(v)) if k in out else float(v)
    return {k: v for k, v in out.items() if v != 0.0}


def _canon(rng, cap, n, nkeys, vals):
    """A canonical sentinel-padded rank triple of ``n`` entries over an
    ``nkeys × nkeys`` keyspace."""
    SENT = np.int32(2**31 - 1)
    pairs = set()
    while len(pairs) < n:
        pairs.add((int(rng.integers(nkeys)), int(rng.integers(nkeys))))
    r, c = (np.array(x, np.int32) for x in zip(*sorted(pairs)))
    v = rng.choice(vals, n).astype(np.float32)
    pad = cap - n
    return (np.concatenate([r, np.full(pad, SENT, np.int32)]),
            np.concatenate([c, np.full(pad, SENT, np.int32)]),
            np.concatenate([v, np.zeros(pad, np.float32)]))


@pytest.mark.parametrize("agg", ["sum", "min", "max"])
@pytest.mark.parametrize("nkeys,vals", [
    (16, [0.5, 1.0, 2.0]),                  # dense collisions
    (2 ** 16, [1.0, 3.0, 7.0]),             # nrows · ncols = 2^32
    (12, [-2.0, -1.0, 1.0, 2.0]),           # ⊕ cancels to zero under sum
], ids=["small", "wide", "cancel"])
def test_merge_kernel_matches_concat_oracle(agg, nkeys, vals):
    """The whole-table merge program (pair search, log-step moves, no
    sort) ≡ the plain ⊕ of base-then-delta triples, on identical padded
    operands, whatever the keyspace size."""
    import jax.numpy as jnp
    from repro.core.coo import SENT
    from repro.ingest.merge import REMAP_SLOTS, _merge_read_prog
    from repro.kernels.sorted_merge.ops import pair_search

    rng = np.random.default_rng(3)
    br, bc, bv = _canon(rng, 64, min(40, nkeys * nkeys // 2), nkeys, vals)
    dr, dc, dv = _canon(rng, 32, min(20, nkeys * nkeys // 4), nkeys, vals)
    dp, dh = pair_search(jnp.asarray(br), jnp.asarray(bc), jnp.asarray(dr),
                         jnp.asarray(dc))
    none = jnp.full(REMAP_SLOTS, SENT)
    r, c, v, n = _merge_read_prog(agg, "shift")(
        br, bc, bv, dr, dc, dv, dp, dh, none, none, out_cap=128)
    k = int(n)
    got = dict(zip(zip(np.asarray(r)[:k].tolist(),
                       np.asarray(c)[:k].tolist()),
                   np.asarray(v)[:k].tolist()))
    assert (np.asarray(r)[k:] == int(SENT)).all()
    assert list(got) == sorted(got)               # canonical order
    op = {"sum": lambda a, b: a + b, "min": min, "max": max}[agg]
    ok = br != int(SENT)
    dok = dr != int(SENT)
    want = _host_dict(np.r_[br[ok], dr[dok]], np.r_[bc[ok], dc[dok]],
                      np.r_[bv[ok], dv[dok]], op)
    assert got == pytest.approx(want)


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


def test_idle_trigger_waits_out_a_steady_writers_gaps(monkeypatch):
    """A steady open-loop writer is never taken for idle, not even just
    after a compaction (its gaps are kept across folds); once it stops,
    three of its longest gaps make it idle."""
    from repro.ingest import table as table_mod

    clock = [1000.0]
    monkeypatch.setattr(table_mod.time, "monotonic", lambda: clock[0])
    t = IngestTable(Assoc(*_BASE), compact_threshold=64)
    rng = np.random.default_rng(3)
    for i in range(300):
        clock[0] += float(rng.exponential(0.1))
        t.insert([f"r{i}"], ["c"], [1.0])
        t.maybe_compact(idle_s=0.25)    # the depth trigger, every 64
        assert t.version == (i + 1) // 64
        if i < 32:                      # the writer's first gaps
            continue
        clock[0] += 0.3                 # past idle_s, short of its gaps
        assert not t.maybe_compact(idle_s=0.25)
        clock[0] -= 0.3
    clock[0] += 3 * max(t._gaps) + 1e-6
    assert t.maybe_compact(idle_s=0.25) and t.delta_depth == 0


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
# the device table batch by batch against the host reference
# ---------------------------------------------------------------------------

def _keys(rng, n, hi, prefix):
    return np.char.add(prefix, rng.integers(0, hi, n).astype(str))


def _entries(a):
    """An Assoc's entries, explicit zeros left out (the device stores
    none)."""
    r, c, v = a.triples()
    return {(x, y): z for x, y, z in zip(r.tolist(), c.tolist(),
                                         v.tolist()) if z != 0}


# case: (key universe of the base, of the batches, batch values, triples
# per batch, batches, compact before the reads of every k-th batch)
_STREAMS = {
    "narrow": (40, 40, [1.0, 2.0, 5.0], 24, 6, 0),
    "wide": (2 ** 16, 2 ** 16, [1.0, 4.0], 1500, 3, 0),   # 2^32 key pairs
    "new_keys": (30, 60, [1.0, 3.0], 20, 6, 0),
    "cancel": (6, 6, [-3.0, -1.0, 1.0, 3.0], 16, 8, 0),
    "compact": (40, 50, [1.0, 2.0], 24, 8, 3),
}


@pytest.mark.parametrize("case", sorted(_STREAMS))
def test_device_ingest_matches_host_reference(case):
    """A device IngestTable after each batch ≡ the host Assoc built from
    the same triples (``Assoc.combine`` with the same ⊕): selections of
    single rows (present, absent and new keys), a prefix and the whole
    table, and the merged snapshot."""
    from repro.core import Keys, StartsWith

    base_hi, hi, vals, n, batches, every = _STREAMS[case]
    rng = np.random.default_rng(len(case))
    br, bc = _keys(rng, 2 * n, base_hi, "r"), _keys(rng, 2 * n, base_hi, "c")
    bv = rng.choice([1.0, 2.0], 2 * n)
    t = IngestTable(AssocTensor.from_triples(br, bc, bv, aggregate="sum"),
                    aggregate="sum", compact_threshold=4 * n)
    ref = Assoc(br, bc, bv, aggregate="sum")
    for b in range(batches):
        r, c = _keys(rng, n, hi, "r"), _keys(rng, n, hi, "c")
        v = rng.choice(vals, n)
        t.insert(r, c, v)
        ref = ref.combine(Assoc(r, c, v, aggregate="sum"), "sum")
        if every and b % every == every - 1:
            t.compact()
        want = _entries(ref)
        for k in [r[0], r[-1], br[0], "r-absent"]:
            got = _entries(t.select(Keys([k]), slice(None)))
            assert got == {e: x for e, x in want.items() if e[0] == k}, \
                (b, k)
        got = _entries(t.select(StartsWith("r1"), slice(None)))
        assert got == {e: x for e, x in want.items()
                       if e[0].startswith("r1")}
        assert _entries(t.select(slice(None), slice(None))) == want
        assert _entries(t.snapshot().to_assoc()) == want
    # a delta that would overflow its capacity is folded first
    assert t.info()["compactions"] >= (batches // every if every else 0)


def test_read_during_compaction_in_flight(monkeypatch):
    """A read while a compaction builds the new base: it neither waits
    for it nor misses a batch, and the swap keeps every entry."""
    from repro.core import Keys

    t = IngestTable(AssocTensor.from_triples(*_BASE, aggregate="sum"),
                    aggregate="sum")
    t.insert(["b", "q"], ["x", "w"], [10.0, 4.0])
    started, release = threading.Event(), threading.Event()
    merged = t._merged

    def slow(view, count=True):
        started.set()
        assert release.wait(30)
        return merged(view, count)

    monkeypatch.setattr(t, "_merged", slow)
    worker = threading.Thread(target=t.compact)
    worker.start()
    try:
        assert started.wait(30)
        t0 = time.monotonic()
        got = _entries(t.select(Keys(["b", "q"]), slice(None)))
        assert time.monotonic() - t0 < 10     # did not wait for the merge
        assert got == {("b", "x"): 12.0, ("q", "w"): 4.0}
        assert t.version == 0
    finally:
        release.set()
        worker.join(30)
    assert t.version == 1 and t.delta_depth == 0
    assert _entries(t.select(Keys(["b", "q"]), slice(None))) == got


def test_reads_writes_and_compactions_under_thread_churn():
    """One writer, four readers and a background compactor on one device
    table, the interpreter switching threads every 10 µs: each read sees
    the state after some whole number of batches (batch b stores (k, cb)
    and adds 1 to (k, c0)), never a torn view, and never an older state
    than the same reader saw before; the last state is the reference's."""
    from repro.core import Keys

    reg = TableRegistry()
    t = reg.register("mut", IngestTable(
        AssocTensor.from_triples(["k"], ["c0"], [1.0], aggregate="sum"),
        aggregate="sum", compact_threshold=8))
    n_batches, errs, seen = 30, [], []
    done = threading.Event()

    def writer():
        try:
            for b in range(1, n_batches + 1):
                t.insert(["k", "k"], ["c0", f"c{b:02d}"], [1.0, 1.0])
        except Exception as exc:
            errs.append(exc)
        finally:
            done.set()

    def reader():
        last = 0
        try:
            while not done.is_set():
                got = _entries(t.select(Keys(["k"]), slice(None)))
                b = len(got) - 1
                assert got == {("k", "c0"): 1.0 + b,
                               **{("k", f"c{i:02d}"): 1.0
                                  for i in range(1, b + 1)}}, got
                assert b >= last
                last = b
            seen.append(last)
        except Exception as exc:
            errs.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    comp = Compactor(reg, interval_s=0.001, idle_s=0.001).start()
    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader) for _ in range(4)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=240)
    finally:
        comp.stop()
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert not errs, errs
    assert t.info()["compactions"] >= 1 and len(seen) == 4
    assert _entries(t.select(Keys(["k"]), slice(None)))[("k", "c0")] == \
        1.0 + n_batches


_COMPILES = {"n": 0}


def _count_backend_compiles():
    from jax import monitoring

    if _COMPILES.get("on"):
        return

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES["n"] += 1

    monitoring.register_event_duration_secs_listener(listen)
    _COMPILES["on"] = True


def test_stream_compiles_a_bounded_set_of_programs():
    """After a warm-up of inserts, reads and one compaction, 50 more
    batches (new keys among them), their reads and 3 compactions compile
    nothing: the delta, batch and base capacities stay put and keyspace
    growth moves ranks without new shapes."""
    from repro.core import Keys

    _count_backend_compiles()
    rng = np.random.default_rng(11)
    br, bc = _keys(rng, 2000, 400, "r"), _keys(rng, 2000, 400, "c")
    t = IngestTable(AssocTensor.from_triples(br, bc, np.ones(2000),
                                             aggregate="sum"),
                    aggregate="sum", compact_threshold=4096)

    def step():
        r, c = _keys(rng, 64, 420, "r"), _keys(rng, 64, 420, "c")
        t.insert(r, c, rng.integers(1, 9, 64).astype(float))
        t.select(Keys([str(r[0])]), slice(None)).nnz()

    for _ in range(4):
        step()
    t.compact()
    step()
    before = _COMPILES["n"]
    for i in range(50):
        step()
        if i % 16 == 15:
            t.compact()
    assert t.info()["compactions"] == 4
    assert _COMPILES["n"] == before


@pytest.mark.parametrize("kind", ["str", "float"])
def test_batch_ranks_and_base_codes_match_a_plain_search(kind):
    """An insert ranks each axis of a batch with one search of the
    delta's keyspace: the grown keyspace, the new keys, the ranks and the
    codes against the base equal ``np.insert`` and a search of each
    keyspace, batch after batch, as keys the base lacks pile up."""
    from repro.ingest.table import _ranked

    def plain(space, base, keys):
        keys = keys.astype(str) if space.is_string else keys
        new = np.unique(keys[~np.isin(keys, space.keys)])
        grown = KeySpace(np.insert(space.keys, np.searchsorted(
            space.keys, new), new)) if len(new) else space
        ranks = np.searchsorted(grown.keys, keys)
        pos = np.searchsorted(base.keys, keys)
        hit = np.isin(keys, base.keys)
        return grown, new, ranks, 2 * pos + hit

    rng = np.random.default_rng(5)
    conv = (lambda a: a.astype(str)) if kind == "str" else \
        (lambda a: a.astype(np.float64))
    for _ in range(20):
        universe = int(rng.integers(8, 300))
        base = KeySpace(conv(rng.integers(0, universe, universe)))
        space, lack = base, []
        for _ in range(4):
            keys = conv(rng.integers(0, universe + 16,
                                     int(rng.integers(1, 64))))
            got = _ranked(space, tuple(lack), keys)
            want = plain(space, base, keys)
            assert got[0] == want[0]
            assert np.array_equal(got[0].keys, want[0].keys)
            for g, w in zip(got[1:], want[1:]):
                assert np.array_equal(g, w)
            lack += [got[1]] if len(got[1]) else []
            space = got[0]


def test_keyspace_digest_hashes_the_keys_bytes():
    """The digest reads the key array's own buffer: the same value as
    hashing a copy of its bytes, for a strided array too."""
    import hashlib

    keys = np.array(["a", "bb", "ccc", "dddd"])
    for arr in (keys, keys[::2], np.arange(6.0)[::3]):
        space = KeySpace.from_sorted_unique(arr)
        want = hashlib.sha1(f"{arr.dtype.str}:{len(arr)}:".encode()
                            + arr.tobytes()).hexdigest()
        assert space.digest == want


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
