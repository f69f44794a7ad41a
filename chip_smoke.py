#!/usr/bin/env python
"""Chip smoke: the served D4M path, once, on a TPU, checked end to end.

One process boots the query server (``repro.serve``) over resident device
tables built from the paper's own dataset (``repro.configs.d4m_bench``),
drives it over loopback HTTP with ``D4MClient`` from client threads of the
same process (a second process could not reach the chip), and compares
every answer with the host ``Assoc`` reference built from the same seeded
triples:

  (a) load device tables ``A``/``B`` at the paper's top size, n = 18;
  (b) serve Range / StartsWith / Keys selections and fused degree vectors;
  (c) serve lazy ``(A[sel] @ B).sum(axis)`` pipelines and full products
      under plus_times and min_plus, at the largest paper n whose planned
      product fits the chip;
  (d) ``POST /ingest`` batches into ingest tables, read them through
      merge-on-read, then ``compact()``.

It then requires zero error responses in ``/stats`` and every kernel on
the path traced as ``pallas`` and compiled to a Mosaic call.  The last
stdout line is ``{"ok": true, "device": {...}}``; any failure exits 1
before printing it.  Without a TPU it exits 1 at once — it never falls
back to another platform.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # only the sharded DistAssoc phase
"""
import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_DATA = 18          # the paper's top size: 8·2^18 triples per table
HEADROOM = 4         # dense products sort C with an index payload to
#                      extract COO: ~3 more copies of the planned footprint
TILE = 128


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- answers as comparable dicts --------------------------------------------

def host_triples(h):
    r, c, v = h.triples()
    return dict(zip(zip(r.tolist(), c.tolist()), v.tolist()))


def served_triples(res):
    require(res["kind"] == "triples" and not res["truncated"],
            f"expected untruncated triples, got {res['kind']}")
    return dict(zip(zip(res["rows"], res["cols"]), res["vals"]))


def host_vector(h, axis):
    r, c, v = h.triples()
    return dict(zip((r if axis == 1 else c).tolist(), v.tolist()))


def served_vector(res, keys, zero):
    require(res["kind"] == "vector" and res["n"] == len(keys),
            f"expected a vector over {len(keys)} keys, got {res['kind']}")
    v = np.asarray(res["vals"], np.float64)
    stored = v != zero
    return dict(zip(keys[stored].tolist(), v[stored].tolist()))


def same(name, got: dict, want: dict) -> None:
    if got == want:
        return
    diff = sorted(set(got) ^ set(want))[:3] or \
        [k for k in want if got.get(k) != want[k]][:3]
    raise SmokeFailure(f"{name}: {len(got)} entries served, {len(want)} "
                       f"in the host reference; first differences "
                       f"{[(k, got.get(k), want.get(k)) for k in diff]}")


def close(name, got: float, want: float) -> None:
    # a whole-table total passes 2^24, past which the device's f32
    # accumulation rounds where the host's f64 does not
    require(abs(got - want) <= 2.0 ** -20 * abs(want),
            f"{name}: served {got}, host reference {want}")


# -- data ---------------------------------------------------------------------

def dataset(n):
    from repro.configs.d4m_bench import make_dataset
    return make_dataset(n)


def device_table(rows, cols, vals):
    from repro.core import AssocTensor
    t = AssocTensor.from_triples(rows, cols, vals, aggregate="sum")
    t.rows.block_until_ready()
    return t


def host_table(rows, cols, vals):
    from repro.core import Assoc
    return Assoc(rows, cols, vals, aggregate="sum")


def table_bytes(t) -> int:
    return sum(int(x.nbytes) for x in (t.rows, t.cols, t.vals, t.nnz))


def product_bound(d) -> int:
    """Bytes the planner's cheaper strategy needs for ``A @ B`` at least:
    the dense footprint (A, B, C on 128-padded keyspaces) or, if lower, a
    lower bound of the BSR one (present tiles + tile pairs), from ranks
    alone — no pair list is built, so n = 18 costs seconds."""
    m_keys, ra = np.unique(d["rows"], return_inverse=True)
    k_keys, kk = np.unique(np.concatenate([d["cols"], d["rows2"]]),
                           return_inverse=True)
    n_keys, cb = np.unique(d["cols2"], return_inverse=True)
    ca, rb = kk[:len(ra)], kk[len(ra):]

    def up(x):
        return -(-x // TILE) * TILE

    m, k, n = len(m_keys), len(k_keys), len(n_keys)
    dense = up(m) * up(k) + up(k) * up(n) + up(m) * up(n)
    kb, nb = up(k) // TILE, up(n) // TILE
    a_tiles = np.unique((ra // TILE).astype(np.int64) * kb + ca // TILE)
    b_tiles = np.unique((rb // TILE).astype(np.int64) * nb + cb // TILE)
    pairs = int(np.bincount(a_tiles % kb, minlength=kb).astype(np.int64)
                @ np.bincount(b_tiles // nb, minlength=kb).astype(np.int64))
    bsr_low = (len(a_tiles) + len(b_tiles) + pairs) * TILE * TILE
    return 4 * min(dense, bsr_low)


def planned_product(a, b):
    """(strategy, footprint bytes) of the plan ``spgemm.matmul`` runs."""
    from repro.core import plan_matmul
    ks, a_map, b_map = a.col_space.union(b.row_space)
    na, nb = int(a.nnz), int(b.nnz)
    plan = plan_matmul(np.asarray(a.rows)[:na],
                       np.asarray(a_map)[np.asarray(a.cols)[:na]],
                       np.asarray(b_map)[np.asarray(b.rows)[:nb]],
                       np.asarray(b.cols)[:nb],
                       len(a.row_space), len(ks), len(b.col_space))
    cost = plan.dense_cost if plan.impl == "dense" else plan.bsr_cost
    return plan.impl, 4 * cost


# -- the served phases --------------------------------------------------------

def run_queries(client, jobs, workers=4):
    """Send ``(name, expr, check)`` jobs from client threads; each check
    gets the served result body.  Returns per-job seconds."""
    def one(job):
        name, expr, check = job
        t0 = time.perf_counter()
        out = client.query(expr, {"limit": None})
        dt = time.perf_counter() - t0
        check(out["result"])
        return name, dt

    with ThreadPoolExecutor(workers) as pool:
        return list(pool.map(one, jobs))


def phase_load(reg, n):
    d = dataset(n)
    t0 = time.perf_counter()
    A = reg.register("A", device_table(d["rows"], d["cols"], d["num_vals"]))
    B = reg.register("B", device_table(d["rows2"], d["cols2"],
                                       d["num_vals"]))
    t_load = time.perf_counter() - t0
    hA = host_table(d["rows"], d["cols"], d["num_vals"])
    hB = host_table(d["rows2"], d["cols2"], d["num_vals"])
    require(int(A.nnz) == hA.nnz() and int(B.nnz) == hB.nnz(),
            "device and host tables hold different entry counts")
    log(f"[a] n={n}: 2 device tables, {len(d['rows'])} triples each, "
        f"nnz {int(A.nnz)}/{int(B.nnz)}, loaded in {t_load} s, "
        f"resident {table_bytes(A) + table_bytes(B)} bytes")
    return hA, hB


def phase_select(client, reg, hA, hB):
    from repro.core import Keys, Range, StartsWith
    from repro.serve import TableRef
    A, B = TableRef("A"), TableRef("B")
    rkeys = reg.get("A").row_space.keys
    ckeys = reg.get("B").col_space.keys
    sels = [("Range", Range("100", "101")), ("StartsWith", StartsWith("1234")),
            ("Keys", Keys(["5", "77", "123456", "262143"]))]
    jobs = []
    for name, sel in sels:
        want = host_triples(hA[sel, :])
        jobs.append((f"A[{name}, :]", A[sel, :],
                     lambda r, w=want, n=name: same(n, served_triples(r), w)))
    col_sel = StartsWith("99")
    jobs.append(("B[:, StartsWith]", B[:, col_sel],
                 lambda r, w=host_triples(hB[:, col_sel]):
                     same("col StartsWith", served_triples(r), w)))
    jobs.append(("A.sum(1)", A.sum(axis=1),
                 lambda r, w=host_vector(hA.sum(axis=1), 1):
                     same("degree", served_vector(r, rkeys, 0.0), w)))
    sel = StartsWith("12")
    jobs.append(("A[StartsWith].sum(1)", A[sel, :].sum(axis=1),
                 lambda r, w=host_vector(hA[sel, :].sum(axis=1), 1):
                     same("selected degree", served_vector(r, rkeys, 0.0),
                          w)))
    jobs.append(("B.sum(0)", B.sum(axis=0),
                 lambda r, w=host_vector(hB.sum(axis=0), 0):
                     same("col degree", served_vector(r, ckeys, 0.0), w)))
    times = run_queries(client, jobs)
    log("[b] " + ", ".join(f"{n} {t} s" for n, t in times))


def fitting_product_n(budget):
    from repro.configs.d4m_bench import N_RANGE
    for n in sorted(N_RANGE, reverse=True):
        d = dataset(n)
        need = product_bound(d)
        log(f"[c] n={n}: product needs >= {need} bytes "
            f"(x{HEADROOM} headroom vs {budget})")
        if HEADROOM * need <= budget:
            return n, d
    raise SmokeFailure("no paper n has a product that fits the chip")


def phase_products(client, reg, budget):
    import jax
    from repro.core import DistAssoc, MIN_PLUS, PLUS_TIMES, StartsWith
    from repro.serve import TableRef
    n, d = fitting_product_n(budget)
    A = reg.register("A_prod", device_table(d["rows"], d["cols"],
                                            d["num_vals"]))
    B = reg.register("B_prod", device_table(d["rows2"], d["cols2"],
                                            d["num_vals"]))
    strategy, footprint = planned_product(A, B)
    log(f"[c] products at n={n}: planner chose {strategy!r}, footprint "
        f"{footprint} bytes")
    require(HEADROOM * footprint <= budget,
            f"the planned {strategy} product at n={n} does not fit")
    hA = host_table(d["rows"], d["cols"], d["num_vals"])
    hB = host_table(d["rows2"], d["cols2"], d["num_vals"])
    # the same A as a `layer: "dist"` table on this chip's one-device mesh:
    # its replicate strategy runs the tiled pair-list kernel
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    reg.register("A_dist", DistAssoc.from_triples(
        d["rows"], d["cols"], d["num_vals"], mesh, aggregate="sum"))
    TA, TB = TableRef("A_prod"), TableRef("B_prod")
    sel = StartsWith("1")
    pipelines, products = [], []
    for sr in (PLUS_TIMES, MIN_PLUS):
        zero = sr.zero
        for axis, keys in ((1, A.row_space.keys), (0, B.col_space.keys)):
            want = host_vector(
                hA[sel, :].matmul(hB, sr).sum(axis=axis, semiring=sr), axis)
            pipelines.append((
                f"(A[sel]@B).sum({axis})[{sr.name}]",
                TA[sel, :].matmul(TB, sr).sum(axis=axis, semiring=sr),
                lambda r, w=want, k=keys, z=zero, s=sr.name, a=axis: same(
                    f"pipeline {s} axis {a}", served_vector(r, k, z), w)))
        want = host_triples(hA.matmul(hB, sr))
        products.append((f"A@B[{sr.name}]", TA.matmul(TB, sr),
                         lambda r, w=want, s=sr.name:
                             same(f"product {s}", served_triples(r), w)))
        if sr is PLUS_TIMES:
            products.append((
                "A_dist@B[plus_times]", TableRef("A_dist") @ TB,
                lambda r, w=want: same("dist product", served_triples(r), w)))
    times = run_queries(client, pipelines)
    # one full product at a time: HEADROOM budgets a single product
    times += run_queries(client, products, workers=1)
    log("[c] " + ", ".join(f"{n_} {t} s" for n_, t in times))
    return n


def phase_ingest(client, reg, n, name, batches=4, batch=4096, seed=7):
    from repro.core import StartsWith
    from repro.ingest import IngestTable
    from repro.serve import TableRef
    d = dataset(n)
    reg.register(name, IngestTable(
        device_table(d["rows"], d["cols"], d["num_vals"]),
        aggregate="sum", compact_threshold=1 << 30))
    rng = np.random.default_rng(seed)
    all_r, all_c, all_v = [d["rows"]], [d["cols"]], [d["num_vals"]]

    def ingest(count):
        for _ in range(count):
            # existing keys collide with the base (⊕ = sum) and new ones
            # grow both keyspaces
            keys = rng.integers(0, 2 ** n + 64, size=(2, batch)).astype(str)
            vals = rng.integers(1, 100, size=batch).astype(np.float64)
            out = client.ingest(name, keys[0].tolist(), keys[1].tolist(),
                                vals.tolist())["result"]
            require(out["accepted"] == batch, f"ingest: {out}")
            all_r.append(keys[0])
            all_c.append(keys[1])
            all_v.append(vals)
        return host_table(np.concatenate(all_r), np.concatenate(all_c),
                          np.concatenate(all_v))

    T = TableRef(name)
    sel = StartsWith("12")

    def keys(axis):
        # the served snapshot is memoized, so this is the one it answered
        # from (a compaction folds the same delta: same keyspaces)
        snap = reg.ingest_table(name).snapshot()
        return (snap.row_space if axis == 1 else snap.col_space).keys

    def check_reads(tag, href):
        jobs = [(f"{tag} sum", T.sum(axis=None),
                 lambda r: close(f"{tag} total", r["val"],
                                 float(href.sum(axis=None)))),
                (f"{tag} select", T[sel, :],
                 lambda r: same(f"{tag} select", served_triples(r),
                                host_triples(href[sel, :])))]
        for axis in (1, 0):
            jobs.append((f"{tag} sum({axis})", T.sum(axis=axis),
                         lambda r, a=axis: same(
                             f"{tag} sum({a})", served_vector(r, keys(a), 0.),
                             host_vector(href.sum(axis=a), a))))
        return [t for _, t in run_queries(client, jobs)]

    t0 = time.perf_counter()
    href = ingest(batches - 1)
    t_ingest = time.perf_counter() - t0
    t_cold = check_reads("merge-on-read", href)      # compiles the merge
    # one more batch keeps the padded delta capacity (next power of two),
    # so this merge reruns the compiled programs
    href = ingest(1)
    t_warm = check_reads("warm merge-on-read", href)
    t0 = time.perf_counter()
    folded = reg.ingest_table(name).compact()
    t_compact = time.perf_counter() - t0
    table = reg.ingest_table(name)
    same(f"{name} compacted base", host_triples(table.base.to_assoc()),
         host_triples(href))
    t_after = check_reads("compacted", href)
    info = table.info()
    require(info["merges"] >= 2 and info["compact_errors"] == 0, info)
    log(f"[d] {name} n={n}: {batches - 1} batches of {batch} ingested in "
        f"{t_ingest} s; reads {t_cold} s; after one more batch {t_warm} s; "
        f"compact {folded} in {t_compact} s; reads after {t_after} s; "
        f"merges {info['merges']}, compactions {info['compactions']}")


KERNEL_PROBES = {
    # kernel name → its dispatch at a small shape (impl="auto")
    "range_mask": lambda o: o["range_mask"].lower(
        o["i32"](1024), o["i32"](1024), o["i32"](4)),
    "rank_count": lambda o: o["rank_count"].lower(
        o["i32"](1024), o["i32"](1024)),
    "semiring_matmul": lambda o: o["semiring_matmul"].lower(
        o["f32"](128, 128), o["f32"](128, 128), semiring="min_plus"),
    "bsr_spgemm_reduce": lambda o: o["bsr_spgemm_reduce"].lower(
        o["f32"](128, 128), o["i32"](1, 1), o["f32"](128, 128), axis=1,
        semiring="min_plus"),
    "bsr_pairlist": lambda o: o["bsr_pairlist"].lower(
        o["f32"](1, 128, 128), o["f32"](1, 128, 128), o["i32"](1),
        o["i32"](1), o["i32"](1), n_c=1, semiring="min_plus"),
    "bsr_pairlist_reduce": lambda o: o["bsr_pairlist_reduce"].lower(
        o["f32"](1, 128, 128), o["f32"](1, 128, 128), o["i32"](1),
        o["i32"](1), o["i32"](1), n_o=1, axis=1, semiring="min_plus"),
}


def check_kernels(kernel_stats, must_include):
    """Every kernel the phases traced resolved to ``pallas``, and its
    dispatch compiles to a Mosaic call on this chip."""
    import jax.numpy as jnp
    from repro.kernels.bsr_spgemm.ops import (bsr_pairlist,
                                              bsr_pairlist_reduce,
                                              bsr_spgemm_reduce)
    from repro.kernels.range_extract.ops import range_mask
    from repro.kernels.semiring_matmul.ops import semiring_matmul
    from repro.kernels.sorted_merge.ops import rank_count
    ops = dict(range_mask=range_mask, rank_count=rank_count,
               semiring_matmul=semiring_matmul,
               bsr_spgemm_reduce=bsr_spgemm_reduce,
               bsr_pairlist=bsr_pairlist,
               bsr_pairlist_reduce=bsr_pairlist_reduce,
               f32=lambda *s: jnp.zeros(s, jnp.float32),
               i32=lambda *s: jnp.zeros(s, jnp.int32))
    resolved = {}
    for key in kernel_stats:
        kernel, impl = key.split(":")
        resolved.setdefault(kernel, set()).add(impl)
    require(all(impls == {"pallas"} for impls in resolved.values()),
            f"a kernel did not resolve to pallas: {kernel_stats}")
    require(set(must_include) <= set(resolved),
            f"kernels {set(must_include) - set(resolved)} never ran")
    for kernel in sorted(resolved):
        require(kernel in KERNEL_PROBES, f"no compile probe for {kernel}")
        text = KERNEL_PROBES[kernel](ops).compile().as_text()
        require("tpu_custom_call" in text,
                f"{kernel} did not compile to a Mosaic kernel")
    log(f"[kernels] traced {kernel_stats}; all pallas, all tpu_custom_call")


def run_one_chip(n_data=N_DATA, budget=None):
    """Phases (a)-(d) through the server; returns the final /stats."""
    import jax
    from repro.serve import D4MClient, TableRegistry, start_server

    dev = jax.devices()[0]
    if budget is None:
        budget = dev.memory_stats()["bytes_limit"]
    reg = TableRegistry()
    hA, hB = phase_load(reg, n_data)
    server = start_server(reg, workers=4)
    try:
        client = D4MClient(server.url, timeout=900)
        require(client.health()["status"] == "ok", "server not healthy")
        phase_select(client, reg, hA, hB)
        n_prod = phase_products(client, reg, budget)
        phase_ingest(client, reg, n_data, "I")
        # the int32-linearizable merge path (rank-count kernel) runs where
        # rows·cols < 2^31, i.e. below the paper's top sizes
        phase_ingest(client, reg, n_prod, "I_prod")
        st = client.stats()
    finally:
        server.close()
    srv = st["server"]
    require(srv.get("errors", 0.0) == 0.0,
            f"/stats counted {srv.get('errors')} error responses")
    mem = dev.memory_stats() or {}
    log(f"[stats] {srv['requests']:.0f} requests, 0 errors, p50 "
        f"{srv.get('p50_s')} s, p99 {srv.get('p99_s')} s; "
        f"HBM peak {mem.get('peak_bytes_in_use')} of "
        f"{mem.get('bytes_limit')} bytes")
    return st


# -- four chips: the sharded layer --------------------------------------------

def run_four_chips(n_data=N_DATA, n_prod=14, n_shards=4):
    """DistAssoc on a (4,) data mesh against one device and the host."""
    import jax
    from repro.core import (DistAssoc, MIN_PLUS, PLUS_TIMES, StartsWith,
                            matmul_reduce)
    from repro.ingest import IngestTable

    mesh = jax.make_mesh((n_shards,), ("data",))
    d = dataset(n_data)
    t0 = time.perf_counter()
    D = DistAssoc.from_triples(d["rows"], d["cols"], d["num_vals"], mesh,
                               aggregate="sum")
    D.local.rows.block_until_ready()
    t_load = time.perf_counter() - t0
    shards = D.local.rows.addressable_shards
    require(len({s.device for s in shards}) == n_shards
            and all(s.data.shape[0] == 1 for s in shards),
            f"shards not one per device: {[(s.device, s.index) for s in shards]}")
    h = host_table(d["rows"], d["cols"], d["num_vals"])
    log(f"[dist] n={n_data}: {n_shards} shards on {n_shards} devices, "
        f"loaded in {t_load} s")
    sel = StartsWith("1234")
    same("dist select", host_triples(D[sel, :].to_assoc()),
         host_triples(h[sel, :]))

    dp = dataset(n_prod)
    DA = DistAssoc.from_triples(dp["rows"], dp["cols"], dp["num_vals"],
                                mesh, aggregate="sum")
    A1 = device_table(dp["rows"], dp["cols"], dp["num_vals"])
    B1 = device_table(dp["rows2"], dp["cols2"], dp["num_vals"])
    hA = host_table(dp["rows"], dp["cols"], dp["num_vals"])
    hB = host_table(dp["rows2"], dp["cols2"], dp["num_vals"])
    for sr in (PLUS_TIMES, MIN_PLUS):
        want = host_triples(hA.matmul(hB, sr))
        same(f"one-device product {sr.name}",
             host_triples(A1.matmul(B1, sr).to_assoc()), want)
        for impl in ("auto_dist", "replicate", "all_to_all", "2d"):
            t0 = time.perf_counter()
            got = DA.matmul(B1, sr, impl=impl)
            got.local.rows.block_until_ready()
            dt = time.perf_counter() - t0
            same(f"dist {impl} {sr.name}", host_triples(got.to_assoc()),
                 want)
            log(f"[dist] n={n_prod} matmul {impl} {sr.name}: {dt} s, "
                f"matches one device and host")
    keys = A1.row_space.keys
    want = host_vector(hA.matmul(hB).sum(axis=1), 1)
    one = np.asarray(matmul_reduce(A1, B1, 1))
    dist = np.asarray(DA.matmul_reduce(B1, 1))
    same("one-device matmul_reduce", dict(
        (k, v) for k, v in zip(keys.tolist(), one.tolist()) if v), want)
    same("dist matmul_reduce", dict(
        (k, v) for k, v in zip(keys.tolist(), dist.tolist()) if v), want)
    sq = np.asarray(DA.sqout(reduce=1))
    same("dist sqout(reduce=1)", dict(
        (k, v) for k, v in zip(keys.tolist(), sq.tolist()) if v),
        host_vector(hA.sqout().sum(axis=1), 1))
    log(f"[dist] n={n_prod}: matmul_reduce and sqout(reduce=1) match")

    table = IngestTable(D, aggregate="sum")
    rng = np.random.default_rng(7)
    keys2 = rng.integers(0, 2 ** n_data + 64, size=(2, 4096)).astype(str)
    vals = rng.integers(1, 100, size=4096).astype(np.float64)
    table.insert(keys2[0], keys2[1], vals)
    t0 = time.perf_counter()
    merged = table.snapshot()
    merged.local.rows.block_until_ready()
    dt = time.perf_counter() - t0
    href = host_table(np.concatenate([d["rows"], keys2[0]]),
                      np.concatenate([d["cols"], keys2[1]]),
                      np.concatenate([d["num_vals"], vals]))
    same("dist merge-on-read", host_triples(merged.to_assoc()),
         host_triples(href))
    log(f"[dist] n={n_data}: dist_merge_read of 4096 triples in {dt} s "
        f"matches host")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded DistAssoc phase")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.serve.server import use_compile_cache
    except ImportError as exc:
        log(f"chip_smoke: FAIL: cannot import the repository ({exc})")
        return 1
    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: FAIL: needs a TPU; JAX found platform "
            f"{dev.platform!r} ({len(devices)} device(s))")
        return 1
    if len(devices) < args.chips:
        log(f"chip_smoke: FAIL: --chips {args.chips} but JAX found "
            f"{len(devices)} device(s)")
        return 1
    log(f"chip_smoke: {dev.device_kind}, {len(devices)} device(s), "
        f"jax {jax.__version__}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips()
        else:
            st = run_one_chip()
            check_kernels(st["kernels"],
                          ("range_mask", "rank_count", "bsr_pairlist"))
    except SmokeFailure as exc:
        log(f"chip_smoke: FAIL: {exc}")
        return 1
    log(f"chip_smoke: all phases passed in {time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
