"""Pallas TPU kernel: blocked dense semiring matmul.

The paper's associative-array multiplication ``C = A ⊗.⊕ B`` reduces to a
sparse-matrix product on the adjacency matrices.  On TPU we densify onto
MXU-aligned tiles (see DESIGN.md §2) and contract with the semiring:

  * ``(+,×)``  — ``jnp.dot`` on the 128×128 MXU, fp32 accumulation;
  * ``(max,+) / (min,+) / (max,min) / (max,×)`` — no MXU analogue exists
    (the systolic array hard-wires multiply-accumulate), so the contraction
    runs on the VPU as one rank-1 ⊗-update per k, ⊕-folded into the tile
    (the BSR kernels' shared ``_tile_product``).

Every block is 128×128, on the TPU's 8×128 tiling for every semiring.

Grid is (M/bm, N/bn, K/bk) with the K dimension innermost/sequential; a
VMEM scratch accumulator carries partial ⊕ results across K steps and is
flushed to the output tile on the last step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import Semiring, get_semiring
from repro.kernels.bsr_spgemm.bsr_spgemm import _tile_product


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, sr: Semiring, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, sr.zero)

    part = _tile_product(a_ref[...], b_ref[...], sr=sr)
    acc_ref[...] = sr.add(acc_ref[...], part)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def semiring_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                           semiring="plus_times",
                           bm: int = 128, bn: int = 128, bk: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """C[i,j] = ⊕_k A[i,k] ⊗ B[k,j].  A: [M,K], B: [K,N] (padded multiples)."""
    sr = get_semiring(semiring)
    m, kdim = a.shape
    k2, n = b.shape
    assert kdim == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        (m, n, kdim, bm, bn, bk)
    nk = kdim // bk

    return pl.pallas_call(
        functools.partial(_kernel, sr=sr, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
