"""Serve subsystem tests: registry, engine (admission batching, plan-cache
hits across requests), HTTP server/client end-to-end, and the
multithreaded hammer over the now-locked core caches."""
import json
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core import (CACHE_STATS, PLAN_STATS, Assoc, Keys, StartsWith,
                        compile_selector, reset_all_stats)
from repro.serve import (D4MClient, D4MServer, Engine, ServerError, TableRef,
                         TableRegistry, WireError, start_server, to_wire)
from repro.serve.registry import generate_triples, load_triples_file


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def registry():
    return TableRegistry.from_specs([
        {"name": "edges", "generator": "random", "n": 64, "nnz": 512,
         "seed": 0, "layer": "device"},
        {"name": "feat", "generator": "random", "n": 64, "nnz": 512,
         "seed": 1, "layer": "device"},
        {"name": "hostt", "generator": "random", "n": 32, "nnz": 128,
         "seed": 2, "layer": "host"},
    ])


@pytest.fixture()
def engine(registry):
    with Engine(registry, workers=2, max_batch=4) as eng:
        yield eng


def _pipeline_payload(prefix="r0"):
    A, B = TableRef("edges"), TableRef("feat")
    return to_wire((A[StartsWith(prefix), :] @ B).sum(axis=1))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_load_triples_file(tmp_path):
    p = tmp_path / "t.tsv"
    p.write_text("# comment\nr0\tc0\t1.5\nr1\tc1\t2.5\n\nr0\tc1\t3.0\n")
    rows, cols, vals = load_triples_file(str(p))
    assert list(rows) == ["r0", "r1", "r0"]
    assert vals.dtype.kind == "f" and vals[2] == 3.0
    # comma fallback + string values
    q = tmp_path / "t.csv"
    q.write_text("a,b,blue\nc,d,red\n")
    _, _, v2 = load_triples_file(str(q))
    assert v2.dtype.kind == "U" and list(v2) == ["blue", "red"]
    # malformed line is a clear error
    bad = tmp_path / "bad.tsv"
    bad.write_text("only_one_field\n")
    with pytest.raises(ValueError, match="bad.tsv:1"):
        load_triples_file(str(bad))


def test_generate_triples_deterministic():
    a = generate_triples({"generator": "random", "n": 32, "nnz": 64,
                          "seed": 7})
    b = generate_triples({"generator": "random", "n": 32, "nnz": 64,
                          "seed": 7})
    assert list(a[0]) == list(b[0]) and np.allclose(a[2], b[2])


def test_registry_info_and_lookup(registry):
    assert len(registry) == 3 and "edges" in registry
    info = {i["name"]: i for i in registry.list_info()}
    assert info["edges"]["layer"] == "device"
    assert info["hostt"]["layer"] == "host"
    assert info["edges"]["nnz"] > 0
    with pytest.raises(WireError) as ei:
        registry.get("ghost")
    assert ei.value.code == "unknown_table"
    with pytest.raises(TypeError):
        registry.register("bad", object())


def test_registry_file_spec_roundtrip(tmp_path):
    p = tmp_path / "edges.tsv"
    p.write_text("r0\tc0\t1.0\nr1\tc1\t2.0\n")
    reg = TableRegistry.from_specs([{"name": "e", "path": str(p)}])
    assert isinstance(reg.get("e"), Assoc)
    assert reg.layer_of("e") == "host"


# ---------------------------------------------------------------------------
# engine: execution, batching, plan-cache behaviour, errors
# ---------------------------------------------------------------------------

def test_engine_executes_and_repeats_hit_plan_cache(engine):
    payload = _pipeline_payload()
    out1 = engine.query(payload)
    assert out1["result"]["kind"] == "vector"
    h0, m0 = PLAN_STATS["plan_hits"], PLAN_STATS["plan_misses"]
    out2 = engine.query(payload)
    assert PLAN_STATS["plan_hits"] == h0 + 1
    assert PLAN_STATS["plan_misses"] == m0
    assert out1["result"]["vals"] == out2["result"]["vals"]
    assert out2["timing"]["exec_s"] >= 0


def test_engine_triples_and_scalar_results(engine):
    A = TableRef("edges")
    out = engine.query(to_wire(A[StartsWith("r0"), :]))
    assert out["result"]["kind"] == "triples"
    assert out["result"]["nnz"] == len(out["result"]["rows"])
    out = engine.query(to_wire(A.sum(axis=None)))
    assert out["result"]["kind"] == "scalar"
    assert out["result"]["val"] > 0


def test_engine_result_truncation(engine):
    A = TableRef("edges")
    out = engine.query(to_wire(A[:, :]), options={"limit": 3})
    assert out["result"]["truncated"] is True
    assert len(out["result"]["rows"]) == 3
    assert out["result"]["nnz"] > 3       # true count still reported


def test_engine_malformed_rejected_synchronously(engine):
    with pytest.raises(WireError) as ei:
        engine.submit({"version": 1, "nodes": [{"op": "table",
                                                "name": "ghost"}],
                       "root": 0})
    assert ei.value.code == "unknown_table"


def test_engine_admission_key_groups_by_tables_and_layer(engine):
    k1 = engine._admission_key(_pipeline_payload("r0"))
    k2 = engine._admission_key(_pipeline_payload("r1"))
    assert k1 == k2                      # same tables, batchable
    k3 = engine._admission_key(to_wire(TableRef("hostt")[:, :]))
    assert k3 != k1                      # different table set / layer
    assert k3[0] == "query"              # disjoint from ("ingest", name)
    assert k3[2] == ("host",)


def test_engine_batches_compatible_requests(registry):
    # single worker + a large batch window: concurrent same-key submits
    # coalesce into one admitted batch
    with Engine(registry, workers=1, max_batch=8) as eng:
        # stall the worker with one slow-ish query, then pile up 4 more
        reqs = [eng.submit(_pipeline_payload()) for _ in range(5)]
        for r in reqs:
            r.wait(timeout=120)
        st = eng.stats()
        assert st["server"]["requests"] == 5
        # at least one admitted batch carried >1 request
        assert max(r.batch_size for r in reqs) > 1
        assert st["server"]["batch_mean"] > 1.0


def test_engine_stats_shape_and_reset(engine):
    engine.query(_pipeline_payload())
    st = engine.stats()
    assert {"server", "plan", "cache", "union", "dispatch", "kernels",
            "queue_depth", "workers"} <= set(st)
    assert st["server"]["requests"] >= 1
    assert "p50_s" in st["server"] and "p99_s" in st["server"]
    engine.reset_stats()
    st2 = engine.stats()
    assert st2["server"].get("requests", 0.0) == 0.0
    assert st2["plan"]["plan_hits"] == 0


def test_compile_cache_placement(monkeypatch):
    import jax
    from repro.serve.server import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(Path(__file__).resolve().parent.parent / ".jax_cache")
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# HTTP server + client end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def server(registry):
    srv = start_server(registry, workers=2)
    yield srv
    srv.close()


@pytest.fixture()
def client(server):
    return D4MClient(server.url, timeout=120)


def test_http_health_and_tables(client):
    h = client.health()
    assert h["status"] == "ok" and h["tables"] == 3
    names = {t["name"] for t in client.tables()}
    assert names == {"edges", "feat", "hostt"}


def test_http_query_roundtrip(client):
    A, B = TableRef("edges"), TableRef("feat")
    out = client.query((A[StartsWith("r0"), :] @ B).sum(axis=1))
    assert out["result"]["kind"] == "vector"
    assert out["batch"] >= 1


def test_http_stats_exposes_core_counters(client):
    client.reset_stats()
    expr = (TableRef("edges")[StartsWith("r0"), :]
            @ TableRef("feat")).sum(axis=1)
    client.query(expr)
    client.query(expr)
    st = client.stats()
    assert st["plan"]["plan_hits"] >= 1
    assert st["server"]["requests"] == 2.0


def test_http_malformed_is_400_not_500(client):
    with pytest.raises(ServerError) as ei:
        client.query({"version": 1, "nodes": [{"op": "table",
                                               "name": "ghost"}],
                      "root": 0})
    assert ei.value.status == 400 and ei.value.code == "unknown_table"
    with pytest.raises(ServerError) as ei:
        client.query({"version": 77, "nodes": [], "root": 0})
    assert ei.value.status == 400 and ei.value.code == "bad_version"
    with pytest.raises(ServerError) as ei:
        client._request("/query", {"not_expr": 1})
    assert ei.value.status == 400 and ei.value.code == "bad_payload"


def test_http_execution_error_is_422(client):
    # structurally valid wire payload whose execution fails: matmul with
    # mismatched inner keyspace types (string cols vs float rows is fine —
    # use a reduce of a matmul between incompatible tables instead)
    with pytest.raises(ServerError) as ei:
        client.query(TableRef("edges") @ TableRef("hostt"))
    assert ei.value.status in (422, 504)
    assert ei.value.code == "execution_error"


STAGES = ("decode_s", "selector_s", "wait_s", "to_host_s", "format_s")
SPANS = ("d4m.request", "d4m.decode", "d4m.encode", "d4m.execute",
         "d4m.select", "d4m.selector", "d4m.keep", "d4m.compact",
         "d4m.format", "d4m.device_wait", "d4m.to_host")


def _one_row(registry):
    key = str(registry.get("edges").row_space.keys[0])
    return TableRef("edges")[Keys([key]), :]


def test_http_one_row_read_times_its_stages(client, registry):
    client.reset_stats()
    out = client.query(_one_row(registry))
    t = out["timing"]
    assert out["result"]["nnz"] >= 1
    assert all(t[k] >= 0 for k in STAGES)
    assert t["selector_s"] > 0 and t["wait_s"] > 0 and t["to_host_s"] > 0
    assert sum(t[k] for k in STAGES) <= t["total_s"]
    assert t["exec_s"] <= t["total_s"]
    tr = client.stats()["transfer"]
    cap = registry.get("edges").capacity
    assert tr == {"to_host_bytes": 12 * cap, "to_host_calls": 1,
                  "entries_returned": out["result"]["nnz"]}


def test_http_stats_counts_compaction_paths(client, registry):
    client.reset_stats()
    assert client.stats()["compact"] == {"sized": 0, "full": 0}
    client.query(_one_row(registry))
    # a 512-entry table is below 8 × 256 slots: whole-capacity compaction
    assert client.stats()["compact"] == {"sized": 0, "full": 1}


def test_profiler_trace_of_a_read_holds_every_span(client, registry,
                                                   tmp_path):
    import jax
    from jax.profiler import ProfileData

    client.query(_one_row(registry))                 # compiled before
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        client.query(_one_row(registry))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {ev.name for p in ProfileData.from_file(str(path)).planes
             for ln in p.lines for ev in ln.events}
    assert set(SPANS) <= names


def test_http_404(client):
    with pytest.raises(ServerError) as ei:
        client._request("/nope")
    assert ei.value.status == 404


# ---------------------------------------------------------------------------
# acceptance: ≥4 concurrent clients, hot mix ⇒ plan_hits > plan_misses
# ---------------------------------------------------------------------------

def test_concurrent_hot_mix_plan_hits_exceed_misses(server):
    client = D4MClient(server.url, timeout=120)
    client.reset_stats()
    payload = _pipeline_payload()        # one hot multi-node pipeline
    client.query(payload)                # warm the plan once

    errs = []

    def worker():
        c = D4MClient(server.url, timeout=120)
        try:
            for _ in range(5):
                out = c.query(payload)
                assert out["result"]["kind"] == "vector"
        except Exception as exc:         # pragma: no cover
            errs.append(exc)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errs
    st = client.stats()
    assert st["server"]["requests"] == 21.0
    assert st["plan"]["plan_hits"] > st["plan"]["plan_misses"]


# ---------------------------------------------------------------------------
# hammer: the locked caches survive concurrent mutation pressure
# ---------------------------------------------------------------------------

def test_multithreaded_cache_hammer(registry):
    """Many threads pounding collect() + compile_selector concurrently:
    exercises _PLAN_CACHE, _COMPILE_CACHE, the union cache and the stats
    dicts under their new locks.  Without the locks this intermittently
    corrupts the OrderedDicts (KeyError/RuntimeError) or loses counts."""
    reset_all_stats()
    edges = registry.get("edges")
    feat = registry.get("feat")
    keys = edges.row_space.keys
    n_threads, n_iter = 8, 30
    errs = []
    barrier = threading.Barrier(n_threads)

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            barrier.wait(timeout=30)
            for i in range(n_iter):
                # rotate through a small set of selectors: repeats hit the
                # caches, fresh ones insert/evict
                lo = int(rng.integers(0, len(keys) - 8))
                sel = Keys(list(keys[lo:lo + 4]))
                compile_selector(sel, edges.row_space)
                if i % 3 == 0:
                    expr = (TableRef("edges")[StartsWith("r0"), :]
                            @ TableRef("feat")).sum(axis=1)
                    from repro.serve.wire import from_wire, to_wire
                    bound = from_wire(
                        to_wire(expr),
                        resolve=registry.resolve)
                    bound.collect()
        except Exception as exc:
            errs.append(exc)

    threads = [threading.Thread(target=worker, args=(s,))
               for s in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errs, errs
    # locked counters lose no increments: every compile is a hit or miss
    assert (CACHE_STATS["hits"] + CACHE_STATS["misses"]
            >= n_threads * n_iter)
    # the hot pipeline planned once (or a few cold races), then hit
    assert PLAN_STATS["plan_hits"] > 0


def test_http_ingest_then_read_on_one_connection():
    """One keep-alive connection: an insert, then a one-row read of the
    ingest table that sees it (read-your-writes), the read's ingest
    stages in its ``timing``, and the write path's counters on
    ``/stats`` under ``"ingest"``."""
    from http.client import HTTPConnection
    from urllib.parse import urlparse

    from repro.core import AssocTensor
    from repro.ingest import IngestTable
    from repro.serve import ingest_to_wire

    reg = TableRegistry()
    reg.register("mut", IngestTable(
        AssocTensor.from_triples(["a", "b"], ["x", "y"], [1.0, 2.0],
                                 aggregate="sum"),
        aggregate="sum", compact_threshold=1000))
    srv = start_server(reg, workers=2)
    u = urlparse(srv.url)
    conn = HTTPConnection(u.hostname, u.port, timeout=120)

    def post(path, body):
        conn.request("POST", path, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        assert resp.status == 200, out
        return out

    try:
        w = post("/ingest", ingest_to_wire("mut", ["a", "new"], ["x", "z"],
                                           [5.0, 7.0]))
        assert w["result"]["accepted"] == 2
        assert w["timing"]["ingest_s"] > 0
        r = post("/query", {"expr": to_wire(
            TableRef("mut")[Keys(["a", "new"]), :])})
        got = dict(zip(zip(r["result"]["rows"], r["result"]["cols"]),
                       r["result"]["vals"]))
        assert got == {("a", "x"): 6.0, ("new", "z"): 7.0}
        assert r["timing"]["merge_s"] > 0
        assert r["timing"]["table_wait_s"] >= 0
        conn.request("GET", "/stats")
        st = json.loads(conn.getresponse().read())["ingest"]["mut"]
        assert st["insert_triples"] == 2 and st["reads"] == 1
        assert {"merge_entries_in", "merge_entries_out"} <= set(st)
        reg.ingest_table("mut").compact()   # unless the idle trigger did
        conn.request("GET", "/stats")
        st = json.loads(conn.getresponse().read())["ingest"]["mut"]
        assert (st["merge_entries_in"], st["merge_entries_out"]) == (4, 3)
    finally:
        conn.close()
        srv.close()
