"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode runs a kernel body on the CPU but never asks the TPU
compiler, which rejects blocks off the 8×128 tiling and lane↔sublane
relayouts that interpret mode accepts.  Here each kernel's jitted dispatch
(``impl="pallas"``) lowers and compiles at real widths for one chip of a
described ``v5e:2x2`` topology — nothing runs — and the compiled text
must hold the Mosaic kernel (``tpu_custom_call``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bsr_spgemm.ops import (bsr_pairlist, bsr_pairlist_reduce,
                                          bsr_spgemm_reduce)
from repro.kernels.range_extract.ops import range_mask
from repro.kernels.semiring_matmul.ops import semiring_matmul
from repro.kernels.sorted_merge.ops import rank_count

F32, I32 = jnp.float32, jnp.int32
N_TILES, N_PAIRS = 256, 4096          # packed 128×128 tiles / tile pairs
N_BASE, N_DELTA = 2 ** 21, 2 ** 17    # paper n=18 table / an ingest delta


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _compiled_text(one_chip, fn, shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return fn.lower(*args, impl="pallas", **static).compile().as_text()


def _pairs(n_pairs=N_PAIRS):
    return [((N_TILES, 128, 128), F32), ((N_TILES, 128, 128), F32),
            ((n_pairs,), I32), ((n_pairs,), I32), ((n_pairs,), I32)]


@pytest.mark.parametrize("sr", ["plus_times", "min_plus"])
def test_pairlist_compiles(one_chip, sr):
    text = _compiled_text(one_chip, bsr_pairlist, _pairs(), n_c=1024,
                          semiring=sr)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("axis", [None, 0, 1])
def test_long_pairlist_compiles(one_chip, axis):
    # 2^18 pairs: three int32 lists of 3 MiB, past the 1 MiB of SMEM that
    # one kernel call's scalar-prefetch operands may take
    if axis is None:
        text = _compiled_text(one_chip, bsr_pairlist, _pairs(2 ** 18),
                              n_c=1024, semiring="plus_times")
    else:
        text = _compiled_text(one_chip, bsr_pairlist_reduce, _pairs(2 ** 18),
                              n_o=128, axis=axis, semiring="plus_times")
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("sr", ["plus_times", "min_plus"])
def test_pairlist_reduce_compiles(one_chip, sr, axis):
    text = _compiled_text(one_chip, bsr_pairlist_reduce, _pairs(), n_o=128,
                          axis=axis, semiring=sr)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("sr", ["plus_times", "min_plus"])
def test_dense_fused_reduce_compiles(one_chip, sr, axis):
    text = _compiled_text(one_chip, bsr_spgemm_reduce,
                          [((4096, 4096), F32), ((32, 32), I32),
                           ((4096, 4096), F32)], axis=axis, semiring=sr)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sr", ["plus_times", "min_plus"])
def test_semiring_matmul_compiles(one_chip, sr):
    text = _compiled_text(one_chip, semiring_matmul,
                          [((4096, 4096), F32), ((4096, 4096), F32)],
                          semiring=sr)
    assert "tpu_custom_call" in text


def test_range_mask_compiles(one_chip):
    text = _compiled_text(one_chip, range_mask,
                          [((N_BASE,), I32), ((N_BASE,), I32), ((4,), I32)])
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fn", [rank_count], ids=["rank_count"])
def test_sorted_merge_compiles(one_chip, fn):
    text = _compiled_text(one_chip, fn,
                          [((N_BASE,), I32), ((N_DELTA,), I32)])
    assert "tpu_custom_call" in text


def test_ingest_merge_compiles_without_a_table_sort(one_chip):
    # the whole-table base ⊕ delta at the ingest cell's capacities (a
    # 2^22-slot base, a 2^18-slot delta): no sort of the table's size —
    # the only sorts are XLA's own over the delta-sized scatter indices
    from repro.ingest.merge import REMAP_SLOTS, _merge_read_prog
    base, delta = 2 ** 22, 2 ** 18
    args = [jax.ShapeDtypeStruct((n,), d, sharding=one_chip) for n, d in
            [(base, I32), (base, I32), (base, F32), (delta, I32),
             (delta, I32), (delta, F32), (delta, I32), (delta, jnp.bool_),
             (REMAP_SLOTS, I32), (REMAP_SLOTS, I32)]]
    text = _merge_read_prog("sum", "shift").lower(
        *args, out_cap=base).compile().as_text()
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert not any(f"[{base}]" in ln for ln in sorts), sorts
    assert all(f"[{delta}]" in ln for ln in sorts), sorts


def test_sized_compaction_compiles_without_sort(one_chip):
    # the eager selection's result-sized compaction at the n = 18 capacity:
    # a prefix count, binary searches and 256-element gathers, no sort
    from repro.core.assoc_tensor import _compact_sized
    args = [jax.ShapeDtypeStruct((N_BASE,), d, sharding=one_chip)
            for d in (I32, I32, F32, jnp.bool_)]
    text = _compact_sized.lower(*args, 256).compile().as_text()
    assert " sort(" not in text
    assert "s32[256]" in text
