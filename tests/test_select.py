"""The unified selector algebra: compilation, composition, 3-layer parity.

One D4M query language across the stack: every selector compiles against a
KeySpace (range or index-set form) and must return the same entries on the
host ``Assoc``, the device ``AssocTensor``, and the sharded ``DistAssoc``.
"""
import jax
import numpy as np
import pytest

from repro.core import (All, Assoc, AssocTensor, Keys, KeySpace, Mask, Match,
                        Positions, Range, StartsWith, Where)
from repro.core import keyspace as keyspace_mod
from repro.core import select
from repro.core.dist_assoc import DistAssoc
from repro.core.select import as_selector, compile_selector


# ---------------------------------------------------------------------------
# compilation against a KeySpace
# ---------------------------------------------------------------------------

KEYS = ["alpha", "beta", "bet", "gamma", "delta", "log-01", "log-02", "zz"]


@pytest.fixture
def space():
    return KeySpace(KEYS)


def _keys_of(comp, ks):
    return ks.keys[comp.positions()].tolist()


def test_keys_compile(space):
    c = compile_selector(Keys(["beta", "zz", "nope"]), space)
    assert _keys_of(c, space) == ["beta", "zz"]


def test_range_compile_right_inclusive(space):
    c = compile_selector(Range("bet", "delta"), space)
    assert _keys_of(c, space) == ["bet", "beta", "delta"]
    assert c.is_range


def test_range_exclusive_bounds(space):
    c = compile_selector(Range("bet", "delta", inclusive=(False, False)),
                         space)
    assert _keys_of(c, space) == ["beta"]


def test_range_open_ends(space):
    assert _keys_of(compile_selector(Range(None, "bet"), space), space) == \
        ["alpha", "bet"]
    assert _keys_of(compile_selector(Range("log-01", None), space), space) == \
        ["log-01", "log-02", "zz"]


def test_startswith_compile(space):
    c = compile_selector(StartsWith("log-"), space)
    assert _keys_of(c, space) == ["log-01", "log-02"]
    assert c.is_range  # prefix block is contiguous in sorted order
    # prefix list (D4M string-list form) → union of ranges
    c2 = compile_selector(StartsWith("bet,log-,"), space)
    assert _keys_of(c2, space) == ["bet", "beta", "log-01", "log-02"]


def test_startswith_next_string_carry():
    # a prefix ending in the maximal code point carries into the shorter one
    top = chr(0x10FFFF)
    ks = KeySpace(["a" + top, "a" + top + "x", "b"])
    c = compile_selector(StartsWith("a" + top), ks)
    assert _keys_of(c, ks) == ["a" + top, "a" + top + "x"]


def test_match_where_mask(space):
    assert _keys_of(compile_selector(Match(r"^log-\d+$"), space), space) == \
        ["log-01", "log-02"]
    assert _keys_of(compile_selector(Where(lambda k: k.endswith("a")), space),
                    space) == ["alpha", "beta", "delta", "gamma"]
    bits = np.zeros(len(space), bool)
    bits[[0, 3]] = True
    assert compile_selector(Mask(bits), space).positions().tolist() == [0, 3]


def test_mask_wrong_length_raises(space):
    with pytest.raises(ValueError):
        compile_selector(Mask(np.zeros(3, bool)), space)


def test_positions_and_slice(space):
    assert compile_selector(Positions([1, 3]), space).positions().tolist() == \
        [1, 3]
    assert compile_selector(slice(0, 3), space).positions().tolist() == \
        [0, 1, 2]
    assert compile_selector(Positions(-1), space).positions().tolist() == \
        [len(space) - 1]
    with pytest.raises(IndexError):
        compile_selector(Positions([99]), space)


def test_composition(space):
    sw = StartsWith("be")
    assert _keys_of(compile_selector(sw & Keys(["beta"]), space), space) == \
        ["beta"]
    assert _keys_of(compile_selector(sw | Keys(["zz"]), space), space) == \
        ["bet", "beta", "zz"]
    inv = compile_selector(~All(), space)
    assert inv.count == 0
    assert compile_selector(~Keys([]), space).count == len(space)


def test_contiguous_set_normalizes_to_range(space):
    # an index set that happens to be contiguous compiles to a rank range
    c = compile_selector(Keys(["log-01", "log-02"]), space)
    assert c.is_range


def test_as_selector_forms():
    assert isinstance(as_selector(":"), All)
    assert isinstance(as_selector(slice(None)), All)
    assert isinstance(as_selector("a,:,b,"), Range)
    assert isinstance(as_selector("a,b,"), Keys)
    assert isinstance(as_selector(("a", "b")), Range)
    assert isinstance(as_selector(np.array([1, 2])), Positions)
    assert isinstance(as_selector(np.array([1.5])), Keys)
    assert isinstance(as_selector(np.array([True, False])), Mask)


# ---------------------------------------------------------------------------
# compilation + union caches
# ---------------------------------------------------------------------------

def test_compile_cache_hits_on_repeat(space):
    select.clear_compile_cache()
    select.reset_cache_stats()
    sel = StartsWith("log-")
    compile_selector(sel, space)
    misses = select.CACHE_STATS["misses"]
    assert misses >= 1 and select.CACHE_STATS["hits"] == 0
    compile_selector(sel, space)
    assert select.CACHE_STATS["hits"] == 1
    assert select.CACHE_STATS["misses"] == misses
    # an equal-content KeySpace (different object) still hits: content hash
    compile_selector(sel, KeySpace(KEYS))
    assert select.CACHE_STATS["hits"] == 2


def test_assoc_repeated_query_hits_cache():
    a = Assoc(["a", "b", "c"], ["x", "y", "z"], [1.0, 2.0, 3.0])
    a["a,:,b,", :]
    select.reset_cache_stats()
    a["a,:,b,", :]
    assert select.CACHE_STATS["hits"] >= 2   # row range + col ":" both cached
    assert select.CACHE_STATS["misses"] == 0


def test_keys_cache_no_itemsize_collision(space):
    # ['ab'] and ['a','b'] have identical UTF-32 payloads; the cache key
    # must include the itemsize so they never share an entry
    select.clear_compile_cache()
    c1 = compile_selector(Keys(["ab"]), space)
    c2 = compile_selector(Keys(["a", "b"]), space)
    assert c1.positions().tolist() != c2.positions().tolist() or \
        c1.count == c2.count == 0
    ks = KeySpace(["a", "b", "ab"])
    assert _keys_of(compile_selector(Keys(["ab"]), ks), ks) == ["ab"]
    assert _keys_of(compile_selector(Keys(["a", "b"]), ks), ks) == ["a", "b"]


def test_cached_results_are_immutable(space):
    # cached Compiled index sets and union maps are shared process-wide;
    # caller mutation must fail loudly instead of poisoning the cache
    select.clear_compile_cache()
    c = compile_selector(Keys(["alpha", "bet", "zz"]), space)
    with pytest.raises(ValueError):
        c.positions()[:] = 0
    keyspace_mod.clear_union_cache()
    x, y = KeySpace(["a", "q"]), KeySpace(["b", "r"])
    _, s_map, _ = x.union(y)
    with pytest.raises(ValueError):
        s_map[:] = 99
    assert x.union(y)[1].tolist() == s_map.tolist()


def test_int_tuple_is_positions_not_range():
    # (0, 1) keeps the paper's ints-are-positions rule (like [0, 1]);
    # key-payload tuples are inclusive ranges
    a = Assoc(["r1", "r2", "r3"], ["c"] * 3, [1.0, 2.0, 3.0])
    assert a[(0, 1), :].to_dict() == a[[0, 1], :].to_dict()
    assert isinstance(as_selector((0, 1)), Positions)
    assert isinstance(as_selector(("a", "b")), Range)
    assert isinstance(as_selector((1.5, 2.5)), Range)


def test_range_open_bound_no_none_key_collision():
    # a keyspace containing the literal key "None" must not share a cache
    # entry with an open-bound Range
    select.clear_compile_cache()
    ks = KeySpace(["Alpha", "Beta", "None", "Zed"])
    open_lo = compile_selector(Range(None, "Zed"), ks)
    closed = compile_selector(Range("None", "Zed"), ks)
    assert open_lo.positions().tolist() == [0, 1, 2, 3]
    assert closed.positions().tolist() == [2, 3]


def test_setitem_tuple_and_mask_match_getitem_semantics():
    # 2-tuples mean inclusive Range and bool arrays mean Mask on BOTH the
    # get and set sides
    a = Assoc(["a", "b", "c"], ["x", "x", "x"], [1.0, 2.0, 3.0])
    a[("a", "c"), :] = 9.0
    assert a.to_dict() == {("a", "x"): 9.0, ("b", "x"): 9.0, ("c", "x"): 9.0}
    b = Assoc(["a", "b", "c"], ["x", "x", "x"], [1.0, 2.0, 3.0])
    b[np.array([True, False, True]), :] = 5.0
    assert b.get("a", "x") == 5.0 and b.get("c", "x") == 5.0
    assert b.get("b", "x") == 2.0
    # plain python bool LISTS are masks on both sides too
    c = Assoc(["a", "b", "c"], ["x", "x", "x"], [1.0, 2.0, 3.0])
    assert c[[True, False, True], :].to_dict() == \
        {("a", "x"): 1.0, ("c", "x"): 3.0}
    c[[True, False, True], :] = 7.0
    assert c.get("a", "x") == 7.0 and c.get("b", "x") == 2.0


def test_where_compiles_uncached(space):
    # per-query lambdas must not fill (or periodically wipe) the cache
    select.clear_compile_cache()
    select.reset_cache_stats()
    for _ in range(3):
        compile_selector(Where(lambda k: True), space)
    assert select.CACHE_STATS == {"hits": 0, "misses": 0}
    assert len(select._COMPILE_CACHE) == 0


def test_union_memo():
    keyspace_mod.clear_union_cache()
    x = KeySpace(["a", "b"])
    y = KeySpace(["b", "c"])
    x.union(y)
    assert keyspace_mod.UNION_STATS == {"hits": 0, "misses": 1,
                                        "evictions": 0}
    x.union(y)
    assert keyspace_mod.UNION_STATS == {"hits": 1, "misses": 1,
                                        "evictions": 0}
    # repeated device adds on the same keyspace pair reuse the merge
    d1 = AssocTensor.from_triples(["a"], ["x"], [1.0], capacity=8)
    d2 = AssocTensor.from_triples(["b"], ["y"], [2.0], capacity=8)
    d1.add(d2)
    before = keyspace_mod.UNION_STATS["hits"]
    d1.add(d2)
    assert keyspace_mod.UNION_STATS["hits"] > before


# ---------------------------------------------------------------------------
# 3-layer parity: Assoc == AssocTensor == DistAssoc for every selector form
# ---------------------------------------------------------------------------

ROWS = ["apple", "apricot", "banana", "cherry", "date", "fig", "grape",
        "kiwi", "lemon", "mango"]


def _triple_set():
    rng = np.random.default_rng(7)
    rows = np.asarray(ROWS * 3)
    cols = np.asarray([f"c{i % 5}" for i in range(len(rows))])
    vals = np.round(rng.uniform(0.5, 9.5, len(rows)), 2)
    return rows, cols, vals


@pytest.fixture(scope="module")
def layers():
    rows, cols, vals = _triple_set()
    host = Assoc(rows, cols, vals, aggregate="sum")
    dev = AssocTensor.from_triples(rows, cols, vals, aggregate="sum",
                                   capacity=64)
    mesh = jax.make_mesh((1,), ("data",))
    dist = DistAssoc.from_triples(rows, cols, vals, mesh, aggregate="sum")
    return host, dev, dist


def _dict_close(a, b):
    if set(a) != set(b):
        return False
    return all(abs(a[k] - b[k]) < 1e-3 * (1 + abs(a[k])) for k in a)


MASK_BITS = np.zeros(len(set(ROWS)), bool)
MASK_BITS[[0, 4, 7]] = True

PARITY_SELECTORS = [
    ("explicit-keys", Keys(["banana", "kiwi", "nope"])),
    ("string-list", "banana,kiwi,"),
    ("range-string", "banana,:,fig,"),
    ("range-obj", Range("banana", "fig")),
    ("startswith", StartsWith("ap,")),
    ("match", Match("an")),
    ("where", Where(lambda k: len(k) == 4)),
    ("mask", Mask(MASK_BITS)),
    ("all", ":"),
    ("composed-or", StartsWith("ap,") | Keys(["mango"])),
    ("composed-and-not", StartsWith("a,b,") & ~Keys(["banana"])),
    ("empty", Keys(["nothing-matches"])),
]


@pytest.mark.parametrize("name,sel", PARITY_SELECTORS,
                         ids=[n for n, _ in PARITY_SELECTORS])
def test_three_layer_parity(layers, name, sel):
    host, dev, dist = layers
    want = host[sel, :].to_dict()
    got_dev = dev[sel, :].to_assoc().to_dict()
    got_dist = dist[sel, :].to_assoc().to_dict()
    assert _dict_close(got_dev, want), (name, got_dev, want)
    assert _dict_close(got_dist, want), (name, got_dist, want)


def test_parity_col_selector_and_both_axes(layers):
    host, dev, dist = layers
    want = host[StartsWith("ap,"), "c0,c3,"].to_dict()
    got_dev = dev[StartsWith("ap,"), "c0,c3,"].to_assoc().to_dict()
    got_dist = dist[StartsWith("ap,"), "c0,c3,"].to_assoc().to_dict()
    assert _dict_close(got_dev, want) and _dict_close(got_dist, want)


def test_parity_full_range_is_identity(layers):
    host, dev, dist = layers
    want = host.to_dict()
    assert _dict_close(host[":", ":"].to_dict(), want)
    assert _dict_close(dev[":", ":"].to_assoc().to_dict(), want)
    assert _dict_close(dist[":", ":"].to_assoc().to_dict(), want)


def test_parity_empty_result(layers):
    host, dev, dist = layers
    assert host["zzz,:,zzzz,", :].to_dict() == {}
    assert dev["zzz,:,zzzz,", :].to_assoc().to_dict() == {}
    assert dist["zzz,:,zzzz,", :].to_assoc().to_dict() == {}


# ---------------------------------------------------------------------------
# device specifics
# ---------------------------------------------------------------------------

def test_device_getitem_under_jit():
    dev = AssocTensor.from_triples(["a", "b", "c"], ["x", "x", "y"],
                                   [1.0, 2.0, 3.0], capacity=8)

    @jax.jit
    def q(t):
        return t[StartsWith("a,b,"), :]

    out = q(dev)
    assert out.to_assoc().to_dict() == {("a", "x"): 1.0, ("b", "x"): 2.0}
    # non-contiguous set → gather path, still jit-safe
    @jax.jit
    def q2(t):
        return t[Keys(["a", "c"]), :]

    assert q2(dev).to_assoc().to_dict() == {("a", "x"): 1.0, ("c", "y"): 3.0}


def test_device_setitem_scalar():
    dev = AssocTensor.from_triples(["a", "b"], ["x", "y"], [1.0, 2.0],
                                   capacity=8)
    dev[Keys(["b"]), :] = 9.0
    assert dev.to_assoc().to_dict() == {("a", "x"): 1.0, ("b", "y"): 9.0}
    with pytest.raises(TypeError):
        dev[Keys(["b"]), :] = "str"


def test_host_setitem_selector_fill():
    a = Assoc(["r1", "r2"], ["c1", "c2"], [1.0, 2.0])
    a[Keys(["r1", "r2"]), "c1,"] = 5.0
    assert a.get("r1", "c1") == 5.0 and a.get("r2", "c1") == 5.0
    assert a.get("r2", "c2") == 2.0
    a["r1,:,r2,", ":"] = 0.5     # range-string selector fill
    assert a.get("r2", "c2") == 0.5


def test_empty_assoc_and_numeric_keyspace_edges():
    assert Assoc()["a,:,b,", :].to_dict() == {}
    assert Assoc()[:, :].to_dict() == {}
    b = Assoc([10.0, 20.0, 30.0], [1.0, 1.0, 1.0], [5.0, 6.0, 7.0])
    # range syntax on numeric keys compares numerically (not lexically)
    assert b["10.0,:,20.0,", :].to_dict() == {(10.0, 1.0): 5.0,
                                              (20.0, 1.0): 6.0}
    assert b[Keys(["abc"]), :].to_dict() == {}   # unparseable → empty


def test_sorted_intersect_string_and_empty():
    """The timsort-merge intersection (satellite) on string + empty inputs."""
    from repro.core import sorted_intersect
    i = np.asarray(["ab", "cd", "zz"])
    j = np.asarray(["abcd", "cd", "zz"])
    k, im, jm = sorted_intersect(i, j)
    assert k.tolist() == ["cd", "zz"]
    np.testing.assert_array_equal(i[im], k)
    np.testing.assert_array_equal(j[jm], k)
    k2, _, _ = sorted_intersect(np.asarray([], dtype=np.int64),
                                np.asarray([1, 2]))
    assert len(k2) == 0

# ---------------------------------------------------------------------------
# dispatch-path coverage: the membership-gather fallback and the
# plan_boxes >4-interval-run spill, on BOTH device layers (DISPATCH_STATS
# pins which execution path actually ran; the autouse conftest fixture
# zeroes the counters before each test)
# ---------------------------------------------------------------------------

WIDE_ROWS = [f"r{i:02d}" for i in range(20)]
WIDE_COLS = [f"d{i:02d}" for i in range(20)]


@pytest.fixture(scope="module")
def wide_layers():
    """20×20 keyspace — wide enough that an every-other-key selection
    forms 10 interval runs (>4, the plan_boxes box budget)."""
    rng = np.random.default_rng(11)
    rows = np.asarray(WIDE_ROWS * 4)
    cols = np.asarray([WIDE_COLS[(3 * i) % 20] for i in range(len(rows))])
    vals = np.round(rng.uniform(0.5, 9.5, len(rows)), 2)
    host = Assoc(rows, cols, vals, aggregate="sum")
    dev = AssocTensor.from_triples(rows, cols, vals, aggregate="sum",
                                   capacity=128)
    mesh = jax.make_mesh((1,), ("data",))
    dist = DistAssoc.from_triples(rows, cols, vals, mesh, aggregate="sum")
    return host, dev, dist


SCATTER_ROWS = Keys(WIDE_ROWS[::2])          # ranks 0,2,…,18 → 10 runs
SCATTER_COLS = Keys(WIDE_COLS[::2])
# 5 runs of 2 — interval-decomposable but over the 4-box budget
SPILL_ROWS = Keys([k for i, k in enumerate(WIDE_ROWS) if i % 4 in (0, 1)])


def _q(arr, ij):
    got = arr[ij[0], ij[1]]
    return got.to_dict() if isinstance(got, Assoc) else \
        got.to_assoc().to_dict()


def _dispatch_of(arr, ij):
    from repro.core import DISPATCH_STATS, reset_all_stats
    reset_all_stats()
    got = _q(arr, ij)
    fired = [k for k, v in DISPATCH_STATS.items() if v]
    assert len(fired) == 1, DISPATCH_STATS
    return fired[0], got


@pytest.mark.parametrize("layer", ["device", "dist"])
def test_scattered_both_axes_takes_gather(wide_layers, layer):
    host, dev, dist = wide_layers
    arr = dev if layer == "device" else dist
    want = _q(host, (SCATTER_ROWS, SCATTER_COLS))
    kind, got = _dispatch_of(arr, (SCATTER_ROWS, SCATTER_COLS))
    assert kind == "gather"        # 10 runs/axis → no boxes fit → 2 masks
    assert _dict_close(got, want), (got, want)


@pytest.mark.parametrize("layer", ["device", "dist"])
def test_scattered_one_axis_takes_hybrid(wide_layers, layer):
    host, dev, dist = wide_layers
    arr = dev if layer == "device" else dist
    want = _q(host, (SCATTER_ROWS, All()))
    kind, got = _dispatch_of(arr, (SCATTER_ROWS, All()))
    assert kind == "hybrid"        # col axis one open box + row mask
    assert _dict_close(got, want), (got, want)


@pytest.mark.parametrize("layer", ["device", "dist"])
def test_run_spill_over_box_budget_falls_back(wide_layers, layer):
    # 5 interval runs is one over the 4-box budget: plan_boxes must spill
    # the row axis to a membership gather instead of dropping a run
    host, dev, dist = wide_layers
    arr = dev if layer == "device" else dist
    want = _q(host, (SPILL_ROWS, All()))
    kind, got = _dispatch_of(arr, (SPILL_ROWS, All()))
    assert kind == "hybrid"
    assert _dict_close(got, want), (got, want)
    # …and the same 5-run set on BOTH axes double-spills to plain gather
    want2 = _q(host, (SPILL_ROWS, Keys([k for i, k in enumerate(WIDE_COLS)
                                        if i % 4 in (0, 1)])))
    kind2, got2 = _dispatch_of(arr, (SPILL_ROWS,
                                     Keys([k for i, k in enumerate(WIDE_COLS)
                                           if i % 4 in (0, 1)])))
    assert kind2 == "gather"
    assert _dict_close(got2, want2), (got2, want2)


@pytest.mark.parametrize("layer", ["device", "dist"])
def test_box_product_spill_keeps_boxable_axis(wide_layers, layer):
    # 2 row runs × 3 col runs = 6 boxes > 4: the planner keeps the row
    # boxes (≤4) and spills only the col axis to a gather (counted as
    # "multirange" — >1 box; "hybrid" is reserved for the 1-box+gather
    # shape)
    host, dev, dist = wide_layers
    two_row_runs = Keys(WIDE_ROWS[0:3] + WIDE_ROWS[8:11])
    three_col_runs = Keys([WIDE_COLS[0], WIDE_COLS[5], WIDE_COLS[10]])
    want = _q(host, (two_row_runs, three_col_runs))
    arr = dev if layer == "device" else dist
    kind, got = _dispatch_of(arr, (two_row_runs, three_col_runs))
    assert kind == "multirange"
    assert _dict_close(got, want), (got, want)


@pytest.mark.parametrize("layer", ["device", "dist"])
def test_few_runs_stay_on_multirange(wide_layers, layer):
    # control: 2 runs × 2 runs = 4 boxes fits the budget → pure multirange
    host, dev, dist = wide_layers
    rows2 = Keys(WIDE_ROWS[0:2] + WIDE_ROWS[10:12])
    cols2 = Keys([WIDE_COLS[0], WIDE_COLS[9]])
    want = _q(host, (rows2, cols2))
    arr = dev if layer == "device" else dist
    kind, got = _dispatch_of(arr, (rows2, cols2))
    assert kind == "multirange"
    assert _dict_close(got, want), (got, want)


# ---------------------------------------------------------------------------
# result-sized compaction of eager device selections
# ---------------------------------------------------------------------------
#
# Eager selections of a table whose capacity is at least 8 × 256 move the
# kept entries into a power-of-two buffer of ≥256 slots, in stored order;
# the whole-capacity lexsort (coo_compact) stays the reference.

SIZED_ROWS = [f"r{i:04d}" for i in range(2000)]
SIZED_COLS = [f"c{i:03d}" for i in range(600)]


def _sized_triples(string_vals):
    rows, cols = [], []
    for i in range(len(SIZED_ROWS)):
        for j in range(1 + i % 4):
            rows.append(SIZED_ROWS[i])
            cols.append(SIZED_COLS[(7 * i + 13 * j) % len(SIZED_COLS)])
    rows, cols = np.asarray(rows), np.asarray(cols)
    vals = (np.arange(len(rows)) * 37) % 97 + 1.0
    if string_vals:
        vals = np.asarray([f"v{int(v):02d}" for v in vals])
    return rows, cols, vals


@pytest.fixture(scope="module", params=["numeric", "string"])
def sized_table(request):
    rows, cols, vals = _sized_triples(request.param == "string")
    return (Assoc(rows, cols, vals),
            AssocTensor.from_triples(rows, cols, vals, capacity=8192))


# one selector pair per dispatch kind, as analysis/probes._selector_kinds
SIZED_KINDS = [
    ("range", (Range(SIZED_ROWS[100], SIZED_ROWS[160]), All())),
    ("multirange", (Keys(SIZED_ROWS[10:20] + SIZED_ROWS[300:310]), All())),
    ("hybrid", (Range(SIZED_ROWS[100], SIZED_ROWS[400]),
                Keys(SIZED_COLS[::5][:40]))),
    ("gather", (Keys(SIZED_ROWS[::5][:60]), Keys(SIZED_COLS[::3][:100]))),
]


def _whole_capacity(t, ij):
    """The selection as the whole-capacity lexsort compacts it."""
    from repro.core.assoc_tensor import coo_compact
    r, c, v, nnz = coo_compact(t.rows, t.cols, t.vals,
                               t._selection_keep(ij))
    return AssocTensor(r, c, v, nnz, t.row_space, t.col_space, t.val_space)


def _same_selection(got, ref):
    """Equal entries in equal order, equal nnz and keyspaces: ``got``'s
    slots are the first ``got.capacity`` of the reference's."""
    k = got.capacity
    assert int(got.nnz) == int(ref.nnz)
    assert got.row_space is ref.row_space and got.col_space is ref.col_space
    assert got.val_space is ref.val_space
    for a, b in ((got.rows, ref.rows), (got.cols, ref.cols),
                 (got.vals, ref.vals)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:k])
    assert got.to_assoc().to_dict() == ref.to_assoc().to_dict()


@pytest.mark.parametrize("kind,ij", SIZED_KINDS,
                         ids=[k for k, _ in SIZED_KINDS])
def test_sized_compaction_matches_whole_capacity(sized_table, kind, ij):
    from repro.core import COMPACT_STATS, DISPATCH_STATS, reset_all_stats
    host, dev = sized_table
    ref = _whole_capacity(dev, ij)
    reset_all_stats()
    got = dev[ij]
    assert [k for k, v in DISPATCH_STATS.items() if v] == [kind]
    assert COMPACT_STATS == {"sized": 1, "full": 0}
    assert got.capacity == 256 and 0 < int(got.nnz) <= 256
    _same_selection(got, ref)
    assert got.to_assoc().to_dict() == host[ij].to_dict()


ONE_ROWS = [f"q{i:04d}" for i in range(5000)]


@pytest.fixture(scope="module")
def one_per_row():
    """5000 rows of one entry each in a 16384-slot table: a row range of
    n keys keeps exactly n entries; results above 16384 / 8 = 2048 slots
    take the whole-capacity path."""
    rows = np.asarray(ONE_ROWS)
    cols = np.asarray([f"c{i % 7}" for i in range(len(rows))])
    vals = np.arange(1.0, len(rows) + 1.0)
    return (Assoc(rows, cols, vals),
            AssocTensor.from_triples(rows, cols, vals, capacity=16384))


@pytest.mark.parametrize("count,path,cap", [
    (0, "sized", 256), (1, "sized", 256), (256, "sized", 256),
    (257, "sized", 512), (2048, "sized", 2048), (2049, "full", 16384)])
def test_sized_compaction_buckets_and_falls_back(one_per_row, count, path,
                                                 cap):
    from repro.core import COMPACT_STATS, reset_all_stats
    host, dev = one_per_row
    ij = ((Range(ONE_ROWS[10], ONE_ROWS[9 + count]) if count
           else Keys(["absent"])), All())
    ref = _whole_capacity(dev, ij)
    reset_all_stats()
    got = dev[ij]
    assert COMPACT_STATS == {"sized": int(path == "sized"),
                             "full": int(path == "full")}
    assert got.capacity == cap and int(got.nnz) == count
    _same_selection(got, ref)
    assert got.to_assoc().to_dict() == host[ij].to_dict()


def test_selection_of_sized_selection(one_per_row):
    from repro.core import COMPACT_STATS, reset_all_stats
    host, dev = one_per_row
    outer = (Range(ONE_ROWS[100], ONE_ROWS[1599]), All())
    inner = (Range(ONE_ROWS[400], ONE_ROWS[449]), "c1,c3,")
    reset_all_stats()
    first = dev[outer]
    assert first.capacity == 2048
    got = first[inner]
    assert COMPACT_STATS == {"sized": 2, "full": 0}
    _same_selection(got, _whole_capacity(first, inner))
    assert got.to_assoc().to_dict() == host[outer][inner].to_dict()


def test_sized_selection_feeds_add_and_matmul(one_per_row):
    host, dev = one_per_row
    a_sel = (Range(ONE_ROWS[0], ONE_ROWS[99]), All())
    b_sel = (Range(ONE_ROWS[50], ONE_ROWS[149]), All())
    a, b = dev[a_sel], dev[b_sel]
    ha, hb = host[a_sel], host[b_sel]
    assert a.capacity == b.capacity == 256
    assert _dict_close((a + b).to_assoc().to_dict(), (ha + hb).to_dict())
    assert _dict_close((a @ b.transpose()).to_assoc().to_dict(),
                       (ha @ hb.transpose()).to_dict())


def test_traced_selection_keeps_whole_capacity_compaction(one_per_row):
    from repro.analysis.probes import PROBES
    from repro.core import COMPACT_STATS, reset_all_stats
    _, dev = one_per_row
    ij = (Range(ONE_ROWS[10], ONE_ROWS[29]), All())
    eager = dev[ij]
    reset_all_stats()
    traced = jax.jit(lambda x: x._select_eager(ij))(dev)
    assert COMPACT_STATS == {"sized": 0, "full": 1}
    assert traced.capacity == dev.capacity
    _same_selection(eager, traced)
    # the HLO probes lower every dispatch kind with no host read
    hlo = dict(PROBES["AssocTensor.__getitem__"]())
    assert set(hlo) == {"range", "multirange", "hybrid", "gather"}
    assert all("sort" in text for text in hlo.values())
    reset_all_stats()
    assert COMPACT_STATS == {"sized": 0, "full": 0}
