"""Jitted wrappers + block-mask construction from padded COO."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.semiring import get_semiring
from repro.kernels import resolve_impl
from .bsr_spgemm import bsr_spgemm_pallas, bsr_spgemm_reduce_pallas
from .pairlist import bsr_pairlist_pallas, bsr_pairlist_reduce_pallas
from .ref import (bsr_pairlist_ref, bsr_pairlist_reduce_ref, bsr_spgemm_ref,
                  bsr_spgemm_reduce_ref)


def make_block_mask(rows, cols, valid, mb: int, kb: int, *, bm=128, bk=128):
    """Per-tile presence mask from COO coordinates (int32 [MB, KB])."""
    r = jnp.where(valid, rows // bm, mb)
    c = jnp.where(valid, cols // bk, kb)
    mask = jnp.zeros((mb + 1, kb + 1), jnp.int32).at[r, c].add(1, mode="drop")
    return (mask[:mb, :kb] > 0).astype(jnp.int32)


@partial(jax.jit, static_argnames=("semiring", "impl", "bm", "bn", "bk"))
def bsr_spgemm(a, block_mask, b, *, semiring="plus_times", impl="auto",
               bm: int = 128, bn: int = 128, bk: int | None = None):
    sr = get_semiring(semiring)
    impl = resolve_impl("bsr_spgemm", impl)
    if impl == "ref":
        return bsr_spgemm_ref(a, block_mask, b, semiring=sr, bm=bm, bk=bk)
    return bsr_spgemm_pallas(a, block_mask, b, semiring=sr, bm=bm, bn=bn,
                             bk=bk, interpret=(impl == "interpret"))


@partial(jax.jit, static_argnames=("axis", "semiring", "impl",
                                   "bm", "bn", "bk"))
def bsr_spgemm_reduce(a, block_mask, b, *, axis: int,
                      semiring="plus_times", impl="auto",
                      bm: int = 128, bn: int = 128, bk: int | None = None):
    """Fused ``⊕-reduce(A ⊗.⊕ B, axis)`` → vector ([M] for axis=1, [N] for 0).

    The product is never materialized: the Pallas kernel folds tile
    products into a VMEM vector-of-partials accumulator and this wrapper
    ⊕-folds the residual 128 lanes / 8 sublanes.  The jnp ref path is the
    unfused oracle (materialize-then-reduce) used on non-TPU backends.
    """
    sr = get_semiring(semiring)
    impl = resolve_impl("bsr_spgemm_reduce", impl)
    if impl == "ref":
        return bsr_spgemm_reduce_ref(a, block_mask, b, axis=axis,
                                     semiring=sr, bm=bm, bk=bk)
    part = bsr_spgemm_reduce_pallas(a, block_mask, b, axis=axis, semiring=sr,
                                    bm=bm, bn=bn, bk=bk,
                                    interpret=(impl == "interpret"))
    return sr.add_reduce(part, axis=axis)


# ---------------------------------------------------------------------------
# Pair-list dispatch: the default BSR-strategy execution (see pairlist.py).
# Pairs MUST arrive grouped (sorted) by pair_c / pair_o — plan_matmul's
# invariant; the kernel's VMEM-resident output accumulation depends on it.
# ---------------------------------------------------------------------------

# pairs per kernel call: three int32 lists of this length take 192 KiB of
# the 1 MiB SMEM a v5e core has for scalar-prefetch operands
MAX_PAIRS = 16384


def _chunked_pairlist(kernel, acc, pair_a, pair_b, pair_o):
    """Feed the sorted pair list through ``kernel`` in SMEM-sized chunks.

    ``acc`` holds one extra ⊕-identity slot: pad pairs point at it (they
    sort after every real slot) and it is dropped on return.  A run of
    pairs split across two chunks resumes from the partial the first
    chunk wrote (the kernels start each run from the accumulator's value).
    """
    n_pairs = pair_a.shape[0]
    chunk = min(n_pairs, MAX_PAIRS)
    n_chunks = -(-n_pairs // chunk)
    pad = n_chunks * chunk - n_pairs
    pa = jnp.pad(pair_a, (0, pad))
    pb = jnp.pad(pair_b, (0, pad))
    po = jnp.pad(pair_o, (0, pad), constant_values=acc.shape[0] - 1)

    def step(i, acc):
        take = lambda x: jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk)
        return kernel(take(pa), take(pb), take(po), acc)

    return jax.lax.fori_loop(0, n_chunks, step, acc)[:-1]


@partial(jax.jit, static_argnames=("n_c", "semiring", "impl"))
def bsr_pairlist(a_tiles, b_tiles, pair_a, pair_b, pair_c, *, n_c: int,
                 semiring="plus_times", impl="auto"):
    """Pair-list BSR contraction → packed C tiles ``[n_c, bm, bn]``."""
    sr = get_semiring(semiring)
    impl = resolve_impl("bsr_pairlist", impl)
    if impl == "ref":
        return bsr_pairlist_ref(a_tiles, b_tiles, pair_a, pair_b, pair_c,
                                n_c=n_c, semiring=sr)
    bm, bn = a_tiles.shape[1], b_tiles.shape[2]
    c_tiles = jnp.full((n_c + 1, bm, bn), sr.zero, jnp.float32)
    return _chunked_pairlist(
        partial(bsr_pairlist_pallas, a_tiles, b_tiles, semiring=sr,
                interpret=(impl == "interpret")),
        c_tiles, pair_a, pair_b, pair_c)


@partial(jax.jit, static_argnames=("n_o", "axis", "semiring", "impl"))
def bsr_pairlist_reduce(a_tiles, b_tiles, pair_a, pair_b, pair_o, *,
                        n_o: int, axis: int, semiring="plus_times",
                        impl="auto"):
    """Fused pair-list ``⊕-reduce(A ⊗.⊕ B, axis)`` → ``[n_o, 128]``
    per-output-block vectors (block-rows for axis=1, block-cols for 0).

    C tiles never exist: the Pallas kernel folds each tile product into a
    lane/sublane partial accumulator in VMEM, and this wrapper ⊕-folds the
    residual 128 lanes / 8 sublanes.
    """
    sr = get_semiring(semiring)
    impl = resolve_impl("bsr_pairlist_reduce", impl)
    if impl == "ref":
        return bsr_pairlist_reduce_ref(a_tiles, b_tiles, pair_a, pair_b,
                                       pair_o, n_o=n_o, axis=axis,
                                       semiring=sr)
    acc_shape = ((a_tiles.shape[1], 128) if axis == 1
                 else (8, b_tiles.shape[2]))
    partials = jnp.full((n_o + 1,) + acc_shape, sr.zero, jnp.float32)
    part = _chunked_pairlist(
        partial(bsr_pairlist_reduce_pallas, a_tiles, b_tiles, axis=axis,
                semiring=sr, interpret=(impl == "interpret")),
        partials, pair_a, pair_b, pair_o)
    return sr.add_reduce(part, axis=2 if axis == 1 else 1)
