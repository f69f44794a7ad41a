"""Pallas TPU kernel: sorted-set index maps via tiled rank counting.

The paper's sorted union/intersection builds index maps with a scalar merge
loop — serial, branchy, hostile to vector units.  The TPU-native
reformulation: the merged position of ``i[m]`` is
``m + |{n : j[n] < i[m]}|`` (and the duplicate test is ``∃n : j[n] ==
i[m]``), so the whole merge becomes a *rank count* — for every I element,
count J elements below it.  I rides dense in VMEM as ``[R, 128]`` blocks
(any element order works: each lane counts for its own key); J streams
through SMEM in ``bj``-key blocks, and every J key is one scalar compared
against the whole I block on the VPU — compares are cheap; random gathers
are not.  No operand needs a lane↔sublane relayout.  The J-block grid axis
is innermost and sequential, and the output blocks accumulate across it
exactly like the matmul kernels accumulate across K.

Output per I element: ``rank`` (# of J strictly below) and ``hit``
(# of J equal to it).  Union positions / intersection maps derive from
these in ops.py with pure elementwise math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_UNROLL = 8   # J keys per loop step (fori_loop itself only takes unroll=1)


def _kernel(i_ref, j_ref, rank_ref, hit_ref, *, bj: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        rank_ref[...] = jnp.zeros_like(rank_ref)
        hit_ref[...] = jnp.zeros_like(hit_ref)

    iv = i_ref[...]                                   # [R, 128] I keys

    def step(t, carry):
        lt, eq = carry
        for u in range(_UNROLL):
            jv = j_ref[t * _UNROLL + u]               # one J key (SMEM)
            lt = lt + (jv < iv).astype(jnp.int32)
            eq = eq + (jv == iv).astype(jnp.int32)
        return lt, eq

    zero = jnp.zeros_like(iv)
    lt, eq = jax.lax.fori_loop(0, bj // _UNROLL, step, (zero, zero))
    rank_ref[...] += lt
    hit_ref[...] += eq


def rank_count_pallas(i: jnp.ndarray, j: jnp.ndarray, *, rows: int = 64,
                      bj: int = 1024, interpret: bool = False):
    """For each key of i [Ni], its rank and hit count in sorted j [Nj].

    ``i`` is ``[Ni/128, 128]`` int32 (a reshaped key vector; ``rows`` of it
    per block) and ``j`` is ``[Nj]`` int32 with ``Nj % bj == 0``.  Both are
    sentinel-padded (sentinel = int32 max counts below no valid key).
    Returns ``(rank, hit)`` shaped like ``i``.
    """
    ni_rows, lanes = i.shape
    nj = j.shape[0]
    assert lanes == 128 and ni_rows % rows == 0 and rows % 8 == 0, i.shape
    assert nj % bj == 0 and bj % _UNROLL == 0, (nj, bj)
    blk = pl.BlockSpec((rows, 128), lambda ib, jb: (ib, 0))
    return pl.pallas_call(
        functools.partial(_kernel, bj=bj),
        grid=(ni_rows // rows, nj // bj),
        in_specs=[blk, pl.BlockSpec((bj,), lambda ib, jb: (jb,),
                                    memory_space=pltpu.SMEM)],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct(i.shape, jnp.int32)] * 2,
        interpret=interpret,
    )(i, j)
