"""Pallas TPU kernels: block-sparse (BSR) × dense semiring matmul + fusions.

The large-scale associative-array product (and MoE-style masked compute)
is block-sparse: most 128×128 tiles of the adjacency are entirely empty.
Both kernels carry a per-tile presence mask in SMEM and **skip the MXU
work for empty tiles** (`@pl.when`) — the TPU analogue of CSR's "touch
only stored entries", lifted from element granularity (gather-hostile) to
MXU-tile granularity (systolic-friendly).

Two entry points:

* :func:`bsr_spgemm_pallas` — materializes ``C = A ⊗.⊕ B``.  A is
  dense-stored but block-masked ([MB, KB] int32 mask); B is dense.
* :func:`bsr_spgemm_reduce_pallas` — the **fused epilogue**: computes the
  row (``axis=1``) or column (``axis=0``) ⊕-reduction of C while holding
  only a vector-of-partials accumulator in VMEM — C itself never exists in
  any memory space.  Because ⊕ is associative and commutative,
  ``⊕_j ⊕_k A[i,k] ⊗ B[k,j]`` folds tile products straight into a
  [bm, 128]-lane (or [8, bn]-sublane) accumulator; the final 128-lane (or
  8-sublane) fold happens in jnp outside the kernel.  This is the Graphulo
  server-side-combine pushdown for ``sqin``/``sqout``/degree queries.

Accumulation is semiring-generic for every registered algebra: ``(+,×)``
contracts on the MXU, everything else on the VPU as one rank-1 update per
k (:func:`_tile_product`).  Skipped tiles still stream
through VMEM (BlockSpec prefetch is unconditional) — the win is MXU/VPU
time, and HBM→VMEM for A could be further elided with a scalar-prefetch
index map (left as a §Perf note).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import Semiring, get_semiring


def _tile_product(a, b, *, sr: Semiring):
    """One-tile semiring contraction ``[bm, bk] ⊗.⊕ [bk, bn] → [bm, bn]``."""
    if sr.mxu:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    # VPU path: one rank-1 ⊗-update per k, unrolled over the tile — A's
    # column k is lane-broadcast and B's row k sublane-broadcast.  A
    # [bm, bk, bn] broadcast would need a lane→sublane relayout, which the
    # TPU compiler rejects.
    part = sr.mul(a[:, 0:1], b[0:1, :])
    for k in range(1, a.shape[1]):
        part = sr.add(part, sr.mul(a[:, k:k + 1], b[k:k + 1, :]))
    return part


def _kernel(mask_ref, a_ref, b_ref, o_ref, acc_ref, *, sr: Semiring, nk: int):
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, sr.zero)

    present = mask_ref[i, k] != 0

    @pl.when(present)
    def _compute():
        part = _tile_product(a_ref[...], b_ref[...], sr=sr)
        acc_ref[...] = sr.add(acc_ref[...], part)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def bsr_spgemm_pallas(a: jnp.ndarray, block_mask: jnp.ndarray,
                      b: jnp.ndarray, *, semiring="plus_times",
                      bm: int = 128, bn: int = 128, bk: int | None = None,
                      interpret: bool = False) -> jnp.ndarray:
    """a [M,K] (block-masked), block_mask [M/bm, K/bk] int32, b [K,N]."""
    sr = get_semiring(semiring)
    if bk is None:
        bk = 128  # mask granularity; non-MXU semirings sub-slab internally
    m, kdim = a.shape
    n = b.shape[1]
    assert m % bm == 0 and kdim % bk == 0 and n % bn == 0
    assert block_mask.shape == (m // bm, kdim // bk), block_mask.shape
    nk = kdim // bk

    return pl.pallas_call(
        functools.partial(_kernel, sr=sr, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(block_mask, a, b)


# ---------------------------------------------------------------------------
# Fused ⊗.⊕ + ⊕-reduce: the epilogue that never materializes C.
# ---------------------------------------------------------------------------

def _reduce_rows_kernel(mask_ref, a_ref, b_ref, o_ref, acc_ref, *,
                        sr: Semiring, nj: int, nk: int):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, sr.zero)

    present = mask_ref[i, k] != 0

    @pl.when(present)
    def _compute():
        part = _tile_product(a_ref[...], b_ref[...], sr=sr)  # [bm, bn]
        acc = acc_ref[...]                                   # [bm, 128]
        for c0 in range(0, part.shape[1], 128):
            acc = sr.add(acc, part[:, c0:c0 + 128])
        acc_ref[...] = acc

    @pl.when((j == nj - 1) & (k == nk - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _reduce_cols_kernel(mask_ref, a_ref, b_ref, o_ref, acc_ref, *,
                        sr: Semiring, ni: int, nk: int):
    i, k = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, sr.zero)

    present = mask_ref[i, k] != 0

    @pl.when(present)
    def _compute():
        part = _tile_product(a_ref[...], b_ref[...], sr=sr)  # [bm, bn]
        acc = acc_ref[...]                                   # [8, bn]
        for r0 in range(0, part.shape[0], 8):
            acc = sr.add(acc, part[r0:r0 + 8, :])
        acc_ref[...] = acc

    @pl.when((i == ni - 1) & (k == nk - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def bsr_spgemm_reduce_pallas(a: jnp.ndarray, block_mask: jnp.ndarray,
                             b: jnp.ndarray, *, axis: int,
                             semiring="plus_times",
                             bm: int = 128, bn: int = 128,
                             bk: int | None = None,
                             interpret: bool = False) -> jnp.ndarray:
    """Fused ``⊕-reduce(A ⊗.⊕ B, axis)`` with C kept only as VMEM partials.

    Returns lane/sublane **partials**: ``[M, 128]`` for ``axis=1`` (caller
    ⊕-folds the 128 lanes) or ``[8, N]`` for ``axis=0`` (caller ⊕-folds the
    8 sublanes) — the tails the VPU cannot reduce across cheaply in-kernel.
    """
    sr = get_semiring(semiring)
    if bk is None:
        bk = 128
    m, kdim = a.shape
    n = b.shape[1]
    assert axis in (0, 1), axis
    assert m % bm == 0 and kdim % bk == 0 and n % bn == 0
    assert block_mask.shape == (m // bm, kdim // bk), block_mask.shape
    ni, nj, nk = m // bm, n // bn, kdim // bk

    if axis == 1:
        return pl.pallas_call(
            functools.partial(_reduce_rows_kernel, sr=sr, nj=nj, nk=nk),
            grid=(ni, nj, nk),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, 128), lambda i, j, k: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bm, 128), jnp.float32)],
            interpret=interpret,
        )(block_mask, a, b)

    return pl.pallas_call(
        functools.partial(_reduce_cols_kernel, sr=sr, ni=ni, nk=nk),
        grid=(nj, ni, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda j, i, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda j, i, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((8, bn), lambda j, i, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((8, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, bn), jnp.float32)],
        interpret=interpret,
    )(block_mask, a, b)
