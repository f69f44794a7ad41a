"""Device AssocTensor vs the host Assoc (paper semantics on padded COO)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, st

from repro.core import Assoc, AssocTensor, MAX_PLUS, PLUS_TIMES

keys = st.text(alphabet="abcd", min_size=1, max_size=2)
vals = st.floats(min_value=0.5, max_value=50, allow_nan=False,
                 allow_subnormal=False, width=32)
triples = st.lists(st.tuples(keys, keys, vals), min_size=1, max_size=16)


def make_pair(ts, aggregate="min"):
    r, c, v = zip(*ts)
    host = Assoc(list(r), list(c), np.asarray(v), aggregate=aggregate)
    dev = AssocTensor.from_triples(np.asarray(r), np.asarray(c),
                                   np.asarray(v), aggregate=aggregate,
                                   capacity=64)
    return host, dev


@given(triples)
def test_roundtrip(ts):
    host, dev = make_pair(ts)
    assert dev.to_assoc().to_dict() == pytest.approx(host.to_dict())


@given(triples)
def test_constructor_sum(ts):
    host, dev = make_pair(ts, aggregate="sum")
    assert dev.to_assoc().to_dict() == pytest.approx(host.to_dict())


@given(triples, triples)
def test_add_matches_host(ts1, ts2):
    h1, d1 = make_pair(ts1)
    h2, d2 = make_pair(ts2)
    got = d1.add(d2).to_assoc().to_dict()
    assert got == pytest.approx((h1 + h2).to_dict())


@given(triples, triples)
def test_mul_matches_host(ts1, ts2):
    h1, d1 = make_pair(ts1)
    h2, d2 = make_pair(ts2)
    got = d1.mul(d2).to_assoc().to_dict()
    assert got == pytest.approx((h1 * h2).to_dict())


@given(triples, triples)
def test_matmul_matches_host(ts1, ts2):
    h1, d1 = make_pair(ts1)
    h2, d2 = make_pair(ts2)
    got = d1.matmul(d2, use_kernel=False).to_assoc().to_dict()
    assert got == pytest.approx((h1 @ h2).to_dict(), rel=1e-4, abs=1e-5)


def test_max_plus_add():
    d1 = AssocTensor.from_triples(["a"], ["x"], [3.0], capacity=8)
    d2 = AssocTensor.from_triples(["a"], ["x"], [5.0], capacity=8)
    out = d1.add(d2, semiring=MAX_PLUS).to_assoc()
    assert out.get("a", "x") == 5.0  # ⊕ = max


def test_string_values_pointer_scheme():
    dev = AssocTensor.from_triples(
        ["r1", "r2"], ["c", "c"], np.asarray(["beta", "alpha"]), capacity=8)
    assert not dev.numeric
    back = dev.to_assoc()
    assert back.get("r1", "c") == "beta" and back.get("r2", "c") == "alpha"
    # min-aggregation on ranks == dictionary min
    dup = AssocTensor.from_triples(
        ["r", "r"], ["c", "c"], np.asarray(["zeta", "alpha"]),
        aggregate="min", capacity=8)
    assert dup.to_assoc().get("r", "c") == "alpha"


def test_extract_rank_range():
    dev = AssocTensor.from_triples(["a", "b", "c"], ["x", "x", "x"],
                                   [1.0, 2.0, 3.0], capacity=8)
    sub = dev[("a", "b"), ":"]   # right-inclusive D4M range
    assert sub.to_assoc().to_dict() == {("a", "x"): 1.0, ("b", "x"): 2.0}


def test_to_assoc_counts_whole_capacity_copies():
    from repro.core import TRANSFER_STATS, reset_all_stats
    cap = 40
    dev = AssocTensor.from_triples(["a", "b"], ["x", "y"], [1.0, 2.0],
                                   capacity=cap)
    reset_all_stats()
    assert dev.to_assoc().nnz() == 2
    assert TRANSFER_STATS == {"to_host_bytes": 12 * cap,
                              "to_host_calls": 1, "entries_returned": 0}
    reset_all_stats()
    assert set(TRANSFER_STATS.values()) == {0}


def test_reduce_rows():
    dev = AssocTensor.from_triples(["a", "a", "b"], ["x", "y", "x"],
                                   [1.0, 2.0, 4.0], aggregate="sum",
                                   capacity=8)
    vec = np.asarray(dev.reduce_rows())
    assert vec[0] == 3.0 and vec[1] == 4.0  # rows sorted: a, b


def test_matmul_with_kernel_interpret():
    d1 = AssocTensor.from_triples(["r", "r"], ["k1", "k2"], [2.0, 3.0],
                                  capacity=8)
    d2 = AssocTensor.from_triples(["k1", "k2"], ["c", "c"], [5.0, 7.0],
                                  capacity=8)
    # route through the Pallas semiring matmul in interpret mode
    from repro.kernels.semiring_matmul import ops as K
    import repro.core.assoc_tensor as AT

    out_ref = d1.matmul(d2, use_kernel=False).to_assoc().to_dict()
    assert out_ref == {("r", "c"): 31.0}
