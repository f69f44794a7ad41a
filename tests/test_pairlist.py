"""Scalar-prefetch pair-list BSR kernel + plan cache + multirange selection.

Covers the PR-6 surface: (a) the pair-list kernel body (interpret mode)
against the jnp reference oracle and the host CSR oracle across the full
semiring registry, incl. rectangular shapes, empty pair lists and
capacity overflow; (b) the output-capacity sketch estimator (exact small
cases + forced saturation warning); (c) multirange device selections
(``DISPATCH_STATS["multirange"]``) on ``AssocTensor`` and ``DistAssoc``;
(d) the cross-collect plan cache (second ``collect()`` of a structurally
identical graph is a pure cache hit).
"""
import json
import subprocess
import sys
import textwrap
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Assoc, REGISTRY
from repro.core.assoc_tensor import DISPATCH_STATS
from repro.core.select import Keys, plan_boxes, compile_selector, All
from repro.core.spgemm import estimate_out_nnz, plan_matmul


def _random_pair(n=60, nr=30, nk=30, nc=20, seed=3):
    r = np.random.default_rng(seed)
    ha = Assoc(r.integers(0, nr, n).astype(str),
               r.integers(0, nk, n).astype(str),
               r.uniform(0.5, 5.0, n), aggregate="sum")
    hb = Assoc(r.integers(0, nk, n).astype(str),
               r.integers(0, nc, n).astype(str),
               r.uniform(0.5, 5.0, n), aggregate="sum")
    return ha, hb, ha.to_tensor(), hb.to_tensor()


def _close(got: dict, want: dict, tol=1e-3):
    assert set(got) == set(want)
    for k in want:
        assert abs(got[k] - want[k]) <= tol * (1 + abs(want[k])), \
            (k, got[k], want[k])


# ----------------------- pair-list kernel parity -----------------------------

@pytest.mark.parametrize("sr_name", sorted(REGISTRY))
@pytest.mark.parametrize("kernel_impl", ["ref", "interpret"])
def test_pairlist_matmul_parity(sr_name, kernel_impl):
    """Kernel body (interpret) == jnp oracle (ref) == host CSR oracle."""
    ha, hb, da, db = _random_pair()
    want = ha.matmul(hb, sr_name).to_dict()
    got = da.matmul(db, sr_name, impl="bsr",
                    kernel_impl=kernel_impl).to_assoc().to_dict()
    _close(got, want)


@pytest.mark.parametrize("kernel_impl", ["ref", "interpret", "chunked"])
def test_pairlist_rectangular(kernel_impl):
    """Rectangular blocks: >1 tile on every axis, all three dispatches."""
    ha, hb, da, db = _random_pair(n=300, nr=300, nk=260, nc=200, seed=11)
    want = ha.matmul(hb).to_dict()
    got = da.matmul(db, impl="bsr",
                    kernel_impl=kernel_impl).to_assoc().to_dict()
    _close(got, want)


def test_pairlist_empty_pair_list():
    """Disjoint contraction support → zero tile pairs → empty C, no crash."""
    ha = Assoc(["r0", "r1"], ["k0", "k1"], [1.0, 2.0])
    hb = Assoc(["k7", "k8"], ["c0", "c1"], [3.0, 4.0])
    da, db = ha.to_tensor(), hb.to_tensor()
    for kernel_impl in ("ref", "interpret", "chunked"):
        out = da.matmul(db, impl="bsr", kernel_impl=kernel_impl).to_assoc()
        assert out.to_dict() == {}


@pytest.mark.parametrize("axis", [None, 0, 1])
@pytest.mark.parametrize("sr_name", ["plus_times", "min_plus"])
def test_pairlist_chunks_split_runs(sr_name, axis, monkeypatch):
    """A pair list longer than one SMEM chunk: runs straddling a chunk
    boundary resume from the partial the earlier chunk wrote, and the
    padded tail is dropped — equal to the jnp oracle."""
    from repro.kernels.bsr_spgemm import ops
    from repro.kernels.bsr_spgemm.ops import bsr_pairlist, bsr_pairlist_reduce
    r = np.random.default_rng(9)
    a = jnp.asarray(r.uniform(0.5, 2.0, (3, 128, 128)), jnp.float32)
    b = jnp.asarray(r.uniform(0.5, 2.0, (4, 128, 128)), jnp.float32)
    n_out = 4
    po = np.sort(np.concatenate([np.arange(n_out),
                                 r.integers(0, n_out, 7)])).astype(np.int32)
    pa = jnp.asarray(r.integers(0, 3, po.size), jnp.int32)
    pb = jnp.asarray(r.integers(0, 4, po.size), jnp.int32)
    po = jnp.asarray(po)
    if axis is None:
        call = partial(bsr_pairlist, a, b, pa, pb, po, n_c=n_out,
                       semiring=sr_name)
    else:
        call = partial(bsr_pairlist_reduce, a, b, pa, pb, po, n_o=n_out,
                       axis=axis, semiring=sr_name)
    want = np.asarray(call(impl="ref"))
    monkeypatch.setattr(ops, "MAX_PAIRS", 3)      # 11 pairs → 4 chunks
    for fn in (bsr_pairlist, bsr_pairlist_reduce):
        fn.clear_cache()                          # retrace with the patch
    got = np.asarray(call(impl="interpret"))
    for fn in (bsr_pairlist, bsr_pairlist_reduce):
        fn.clear_cache()
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("sr_name", sorted(REGISTRY))
def test_pairlist_reduce_parity(sr_name, axis):
    """Fused pair-list reduce (interpret) == materialize-then-reduce."""
    ha, hb, da, db = _random_pair(seed=5)
    sr = REGISTRY[sr_name]
    # oracle: the SAME device strategy, materialized then ⊕-folded
    c = da.matmul(db, sr_name, impl="bsr", kernel_impl="ref").to_assoc()
    adj = c.adj.toarray()
    mask = adj != 0
    # axis=1 folds over columns (vector over rows); axis=0 over rows
    if sr.add_kind == "sum":
        want = np.where(mask, adj, 0.0).sum(axis=axis)
    elif sr.add_kind == "max":
        want = np.where(mask, adj, -np.inf).max(axis=axis, initial=-np.inf)
    else:
        want = np.where(mask, adj, np.inf).min(axis=axis, initial=np.inf)
    got_full = np.asarray(da.matmul_reduce(db, axis, sr_name, impl="bsr",
                                           kernel_impl="interpret"))
    # compare on the support of C only (identity rows/cols differ)
    space = da.row_space if axis == 1 else db.col_space
    keys = list(c.row) if axis == 1 else list(c.col)
    idx, _ = space.rank(np.asarray(keys))
    np.testing.assert_allclose(got_full[idx], want, rtol=1e-3, atol=1e-3)


def test_pairlist_capacity_overflow_warns():
    """BSR path with a too-small out_capacity warns and flags overflow."""
    ha, hb, da, db = _random_pair(seed=9)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = da.matmul(db, impl="bsr", kernel_impl="ref", out_capacity=8)
    assert out.overflow
    assert any("capacity" in str(w.message).lower() for w in caught)


def test_pairlist_pairs_sorted_by_c():
    """plan_matmul's pair lists are grouped by pair_c (kernel contract)."""
    r = np.random.default_rng(2)
    n, m, k, nc = 400, 300, 300, 300
    ra, ca = r.integers(0, m, n), r.integers(0, k, n)
    rb, cb = r.integers(0, k, n), r.integers(0, nc, n)
    plan = plan_matmul(ra.astype(np.int32), ca.astype(np.int32),
                       rb.astype(np.int32), cb.astype(np.int32),
                       m, k, nc, impl="bsr")
    assert (np.diff(plan.pair_c) >= 0).all()


# ----------------------- output-capacity estimator ---------------------------

def test_estimator_upper_bounds_and_tightens():
    """Estimate ≥ true nnz(C); on hub-heavy inputs ≪ product count."""
    r = np.random.default_rng(4)
    n = 500
    # hub-heavy: every A col and B row is the same hub → products = n*n
    # but C support is only |rows(A)| x |cols(B)|
    ra = r.integers(0, 40, n).astype(np.int32)
    ca = np.zeros(n, np.int32)
    rb = np.zeros(n, np.int32)
    cb = r.integers(0, 40, n).astype(np.int32)
    plan = plan_matmul(ra, ca, rb, cb, 40, 1, 40, impl="bsr")
    est = estimate_out_nnz(plan)
    true_nnz = len(np.unique(ra)) * len(np.unique(cb))
    assert est >= true_nnz
    assert est < plan.products  # tighter than the raw product count


def test_estimator_saturation_warns_and_falls_back():
    """A sketch with absurdly few bins saturates → warn + provable bound."""
    r = np.random.default_rng(6)
    n = 2000
    ra = r.integers(0, 3000, n).astype(np.int32)
    ca = r.integers(0, 600, n).astype(np.int32)
    rb = r.integers(0, 600, n).astype(np.int32)
    cb = r.integers(0, 3000, n).astype(np.int32)
    plan = plan_matmul(ra, ca, rb, cb, 3000, 600, 3000, impl="bsr")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        est = estimate_out_nnz(plan, bins=8)
    assert est >= 1
    assert any("saturated" in str(w.message) for w in caught)


def test_estimator_capacity_never_truncates():
    """Default (estimator-sized) BSR matmul never loses entries."""
    for seed in (1, 2, 3):
        ha, hb, da, db = _random_pair(n=120, seed=seed)
        want = ha.matmul(hb).to_dict()
        got = da.matmul(db, impl="bsr", kernel_impl="ref").to_assoc().to_dict()
        _close(got, want)


# ----------------------- multirange selections -------------------------------

def _grid_tensor(nr=12, nc=10, seed=0):
    r = np.random.default_rng(seed)
    rows = [f"r{i:02d}" for i in range(nr)]
    cols = [f"c{i:02d}" for i in range(nc)]
    tr, tc = r.choice(rows, 6 * nr), r.choice(cols, 6 * nr)
    tv = r.uniform(1, 5, 6 * nr)
    return Assoc(tr, tc, tv, aggregate="sum")


def test_plan_boxes_two_runs():
    a = _grid_tensor()
    t = a.to_tensor()
    rc = compile_selector(Keys(["r01", "r02", "r07", "r08"]), t.row_space)
    cc = compile_selector(All(), t.col_space)
    boxes, rg, cg = plan_boxes(rc, cc, len(t.row_space), len(t.col_space))
    assert not rg and not cg
    assert boxes.shape == (2, 4)
    np.testing.assert_array_equal(boxes[:, 0], [1, 7])  # run starts


def test_plan_boxes_gather_fallback():
    """>4 boxes → membership gather, not an unbounded OR chain."""
    a = _grid_tensor(nr=20)
    t = a.to_tensor()
    scattered = [f"r{i:02d}" for i in range(0, 20, 2)]  # 10 singleton runs
    rc = compile_selector(Keys(scattered), t.row_space)
    cc = compile_selector(All(), t.col_space)
    boxes, rg, cg = plan_boxes(rc, cc, len(t.row_space), len(t.col_space))
    assert rg  # row axis falls back to gather


def test_multirange_dispatch_and_parity():
    a = _grid_tensor(seed=3)
    t = a.to_tensor()
    sel = ["r01", "r02", "r03", "r07", "r08"]
    before = dict(DISPATCH_STATS)
    sub = t[Keys(sel), :]
    assert DISPATCH_STATS["multirange"] == before["multirange"] + 1
    _close(sub.to_assoc().to_dict(), a[sel, :].to_dict())


def test_multirange_both_axes():
    """≤4 boxes from 2 row runs × 2 col runs, exact vs host oracle."""
    a = _grid_tensor(nr=16, nc=12, seed=5)
    t = a.to_tensor()
    rsel = ["r01", "r02", "r09", "r10"]
    csel = ["c00", "c01", "c06", "c07"]
    before = dict(DISPATCH_STATS)
    sub = t[Keys(rsel), Keys(csel)]
    assert DISPATCH_STATS["multirange"] == before["multirange"] + 1
    _close(sub.to_assoc().to_dict(), a[rsel, csel].to_dict())


DIST_MULTIRANGE_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    from repro.core import Assoc
    from repro.core.assoc_tensor import DISPATCH_STATS
    from repro.core.dist_assoc import DistAssoc
    from repro.core.select import Keys

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(1)
    rows = [f"r{i:02d}" for i in range(16)]
    cols = [f"c{i:02d}" for i in range(10)]
    A = Assoc(rng.choice(rows, 80), rng.choice(cols, 80),
              rng.uniform(1, 5, 80), aggregate="sum")
    D = DistAssoc.from_assoc(A, mesh)
    sel = ["r01", "r02", "r03", "r09", "r10"]
    before = dict(DISPATCH_STATS)
    sub = D[Keys(sel), :]
    assert DISPATCH_STATS["multirange"] == before["multirange"] + 1
    got, want = sub.to_assoc().to_dict(), A[sel, :].to_dict()
    assert set(got) == set(want)
    for k in want:
        assert abs(got[k] - want[k]) < 1e-3 * (1 + abs(want[k]))

    # distributed bsr matmul parity while we have the mesh up
    B = Assoc(rng.choice(cols, 60), rng.choice(rows, 60),
              rng.uniform(1, 5, 60), aggregate="sum")
    Dt = B.to_tensor()
    want2 = A.matmul(B).to_dict()
    got2 = D.matmul(Dt, impl="bsr", kernel_impl="ref").to_assoc().to_dict()
    assert set(got2) == set(want2)
    for k in want2:
        assert abs(got2[k] - want2[k]) < 1e-3 * (1 + abs(want2[k]))
    print(json.dumps({"ok": True}))
""")


@pytest.mark.slow
def test_dist_multirange_and_bsr_8dev():
    p = subprocess.run([sys.executable, "-c", DIST_MULTIRANGE_PROG],
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    last = [l for l in p.stdout.strip().splitlines() if l.startswith("{")][-1]
    assert json.loads(last)["ok"], p.stdout


def test_dist_bsr_matmul_parity_1dev():
    """Per-shard bsr strategy == coo strategy == host, on a 1-shard mesh."""
    from repro.core.dist_assoc import DistAssoc
    mesh = jax.make_mesh((1,), ("data",))
    ha, hb, _, db = _random_pair(seed=13)
    D = DistAssoc.from_assoc(ha, mesh)
    want = ha.matmul(hb).to_dict()
    for impl, kw in [("coo", {}), ("bsr", {"kernel_impl": "ref"}),
                     ("bsr", {"kernel_impl": "interpret"})]:
        got = D.matmul(db, impl=impl, **kw).to_assoc().to_dict()
        _close(got, want)


# ----------------------- cross-collect plan cache ----------------------------

def _pipeline(da, db):
    """A multi-node graph (single-node graphs take the planner-free fast
    path): (A @ B) ⊗ (A @ B) — the hash-consed square."""
    sq = da.lazy() @ db.lazy().T
    return sq * sq


def test_plan_cache_second_collect_hits():
    from repro.core import PLAN_STATS

    ha, hb, da, db = _random_pair(seed=21)
    r1 = _pipeline(da, db).collect()
    assert PLAN_STATS["plan_misses"] == 1
    assert PLAN_STATS["plan_hits"] == 0
    # structurally identical graph over the SAME sources → pure hit
    r2 = _pipeline(da, db).collect()
    assert PLAN_STATS["plan_misses"] == 1
    assert PLAN_STATS["plan_hits"] == 1
    _close(r2.to_assoc().to_dict(), r1.to_assoc().to_dict(), tol=1e-6)


def test_plan_cache_distinct_sources_miss():
    from repro.core import PLAN_STATS

    _, _, da, db = _random_pair(seed=22)
    _, _, da2, db2 = _random_pair(seed=23)
    _pipeline(da, db).collect()
    _pipeline(da2, db2).collect()  # different source arrays → new key
    assert PLAN_STATS["plan_misses"] == 2
    assert PLAN_STATS["plan_hits"] == 0


def test_plan_cache_clear_forces_miss():
    from repro.core import PLAN_STATS, clear_plan_cache

    _, _, da, db = _random_pair(seed=24)
    _pipeline(da, db).collect()
    clear_plan_cache()
    _pipeline(da, db).collect()
    assert PLAN_STATS["plan_misses"] == 2
    assert PLAN_STATS["plan_hits"] == 0
