"""Per-kernel allclose sweeps vs the pure-jnp ref oracles (interpret mode).

Each Pallas kernel is exercised over a shape/dtype grid; interpret=True
executes the kernel body on CPU (TPU is the deployment target).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(42)


# --------------------------- semiring matmul --------------------------------
from repro.kernels.semiring_matmul.ops import semiring_matmul
from repro.kernels.semiring_matmul.ref import semiring_matmul_ref


@pytest.mark.parametrize("sr", ["plus_times", "max_plus", "min_plus",
                                "max_min", "max_times"])
@pytest.mark.parametrize("shape", [(32, 48, 16), (128, 128, 128),
                                   (70, 90, 130), (200, 300, 140)])
def test_semiring_matmul(sr, shape):
    m, k, n = shape
    a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    out = semiring_matmul(a, b, semiring=sr, impl="interpret")
    ref = semiring_matmul_ref(a, b, semiring=sr)
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_semiring_matmul_dtypes(dtype):
    a = jnp.asarray(rng.normal(size=(64, 64)).astype(dtype))
    b = jnp.asarray(rng.normal(size=(64, 64)).astype(dtype))
    out = semiring_matmul(a, b, semiring="plus_times", impl="interpret")
    ref = semiring_matmul_ref(a, b, semiring="plus_times")
    np.testing.assert_allclose(out, ref, rtol=3e-3, atol=3e-3)


# --------------------------- flash attention --------------------------------
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@pytest.mark.parametrize("case", [
    dict(b=2, h=4, kv=2, sq=256, sk=256, d=64, causal=True, window=None),
    dict(b=1, h=4, kv=4, sq=512, sk=512, d=32, causal=True, window=128),
    dict(b=2, h=2, kv=1, sq=256, sk=512, d=64, causal=False, window=None),
    dict(b=1, h=8, kv=8, sq=128, sk=128, d=128, causal=True, window=None),
])
def test_flash_attention(case):
    c = dict(case)
    causal, window = c.pop("causal"), c.pop("window")
    q = jnp.asarray(rng.normal(size=(c["b"], c["h"], c["sq"], c["d"])).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(c["b"], c["kv"], c["sk"], c["d"])).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(c["b"], c["kv"], c["sk"], c["d"])).astype(np.float32))
    qo = c["sk"] - c["sq"] if (causal and c["sk"] > c["sq"]) else 0
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_off=qo, bq=128, bk=128, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window, q_off=qo)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


def test_flash_attention_bf16():
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, causal=True, bq=128, bk=128,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=0.05, atol=0.05)


# --------------------------- sorted merge -----------------------------------
from repro.kernels.sorted_merge.ops import merge_positions, rank_count
from repro.kernels.sorted_merge.ref import rank_count_ref


@pytest.mark.parametrize("ni,nj", [(64, 64), (300, 500), (8, 1024),
                                   (9000, 2100)])
def test_rank_count(ni, nj):
    i = jnp.asarray(np.unique(rng.integers(0, 10000, ni)).astype(np.int32))
    j = jnp.asarray(np.unique(rng.integers(0, 10000, nj)).astype(np.int32))
    r1, h1 = rank_count(i, j, impl="interpret")
    r2, h2 = rank_count_ref(i, j)
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(h1, h2)


def test_merge_positions_union_semantics():
    i = jnp.asarray(np.asarray([1, 3, 5, 7], np.int32))
    j = jnp.asarray(np.asarray([2, 3, 8], np.int32))
    i_pos, j_pos, j_dup = merge_positions(i, j, impl="interpret")
    union = np.union1d(np.asarray(i), np.asarray(j))
    np.testing.assert_array_equal(union[np.asarray(i_pos)], np.asarray(i))
    np.testing.assert_array_equal(union[np.asarray(j_pos)], np.asarray(j))
    np.testing.assert_array_equal(np.asarray(j_dup), [False, True, False])


# --------------------------- segment reduce ---------------------------------
from repro.kernels.segment_reduce.ops import aggregate_runs, segment_scan
from repro.kernels.segment_reduce.ref import segment_scan_ref


@pytest.mark.parametrize("n,comb", [(256, "sum"), (1024, "min"),
                                    (2048, "max"), (256, "max")])
def test_segment_scan(n, comb):
    keys = jnp.asarray(np.sort(rng.integers(0, n // 8, n)).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=n).astype(np.float32))
    out = segment_scan(keys, vals, combine=comb, impl="interpret")
    ref = segment_scan_ref(keys, vals, combine=comb)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_aggregate_runs_sums():
    keys = jnp.asarray(np.asarray([0, 0, 1, 3, 3, 3], np.int32))
    vals = jnp.asarray(np.asarray([1., 2., 5., 1., 1., 1.], np.float32))
    k, v, heads = aggregate_runs(keys, vals, combine="sum", impl="ref")
    v, heads = np.asarray(v), np.asarray(heads)
    np.testing.assert_array_equal(heads, [True, False, True, True, False, False])
    assert v[0] == 3.0 and v[2] == 5.0 and v[3] == 3.0


# --------------------------- range extract ----------------------------------
from repro.kernels.range_extract.ops import range_mask
from repro.kernels.range_extract.ref import range_mask_ref


@pytest.mark.parametrize("n,box", [(64, (2, 9, 0, 50)), (300, (0, 300, 10, 20)),
                                   (1024, (5, 5, 0, 1)), (8, (0, 8, 0, 8))])
def test_range_mask(n, box):
    from repro.core.sorted_ops import INT_SENTINEL
    rows = np.sort(rng.integers(0, 32, n)).astype(np.int32)
    cols = rng.integers(0, 32, n).astype(np.int32)
    rows[-n // 4:] = INT_SENTINEL  # sentinel tail never kept
    cols[-n // 4:] = INT_SENTINEL
    bounds = jnp.asarray(box, jnp.int32)
    out = range_mask(jnp.asarray(rows), jnp.asarray(cols), bounds,
                     impl="interpret")
    ref = range_mask_ref(jnp.asarray(rows), jnp.asarray(cols),
                         bounds.reshape(1, 4))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    valid = rows != INT_SENTINEL
    want = (valid & (rows >= box[0]) & (rows < box[1])
            & (cols >= box[2]) & (cols < box[3])).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(out), want)


# --------------------------- bsr spgemm -------------------------------------
from repro.kernels.bsr_spgemm.ops import bsr_spgemm, make_block_mask
from repro.kernels.bsr_spgemm.ref import bsr_spgemm_ref


from repro.core.semiring import REGISTRY as _SR_REGISTRY


# semiring-generic accumulation: the block-skip kernel must match the jnp
# oracle for EVERY registered algebra, not just the MXU-friendly ones
@pytest.mark.parametrize("sr", sorted(_SR_REGISTRY))
@pytest.mark.parametrize("mb,kb,n", [(2, 2, 128), (4, 3, 256),
                                     (1, 5, 128)])
def test_bsr_spgemm(sr, mb, kb, n):
    a = jnp.asarray(rng.normal(size=(mb * 128, kb * 128)).astype(np.float32))
    mask = jnp.asarray((rng.random((mb, kb)) > 0.5).astype(np.int32))
    b = jnp.asarray(rng.normal(size=(kb * 128, n)).astype(np.float32))
    out = bsr_spgemm(a, mask, b, semiring=sr, impl="interpret")
    ref = bsr_spgemm_ref(a, mask, b, semiring=sr)
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_make_block_mask():
    rows = jnp.asarray(np.asarray([0, 130, 300], np.int32))
    cols = jnp.asarray(np.asarray([5, 200, 130], np.int32))
    valid = jnp.asarray(np.asarray([True, True, False]))
    m = np.asarray(make_block_mask(rows, cols, valid, 3, 2))
    assert m[0, 0] == 1 and m[1, 1] == 1 and m.sum() == 2
