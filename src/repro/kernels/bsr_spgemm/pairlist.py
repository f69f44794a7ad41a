"""Scalar-prefetch pair-list Pallas kernels: packed-tile BSR ⊗.⊕ BSR.

The Graphulo-style planner (``repro.core.spgemm``) reduces ``A ⊗.⊕ B``
to a *pair list*: packed present tiles ``a_tiles [nA, 128, 128]`` /
``b_tiles [nB, 128, 128]`` plus int32 arrays ``(pair_a, pair_b, pair_c)``
saying which A tile contracts with which B tile into which C tile.  The
previous execution gathered ``a_tiles[pair_a[p0:p0+chunk]]`` on host-driven
chunks and ⊕-scattered each einsum result — every pair's tiles were
**copied** into a fresh batched operand before the MXU ever saw them.

Here the pair list itself becomes the schedule: it rides in SMEM as
scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``) and drives a
1-D grid over pairs whose ``index_map``s read ``pair_a[p]``/``pair_b[p]``
directly — each step DMAs exactly the two 128² tiles it contracts, no
materialized gather.  The ⊕-scatter is fused in VMEM: pairs arrive
**grouped by ``pair_c``** (the planner sorts them), so a C tile lives in a
VMEM accumulator across its run of pairs and is flushed to HBM exactly
once — the accumulation trick of ``bsr_spgemm_reduce`` extended to full C.

Contract (asserted by the ``ops.py`` dispatch):

* ``pair_c`` is sorted ascending and covers ``0..n_c-1`` (every C tile
  has ≥1 contributing pair — true by construction in ``plan_matmul``);
  same for ``pair_o`` in the reduce variant.
* all three pair arrays are int32 of one length ``n_pairs ≥ 1``.

The pair arrays live in SMEM (1 MiB on v5e), so one call takes a bounded
chunk of the list.  The C tiles are an input aliased to the output: a
run of pairs starts from the tile's current value (the ⊕-identity, or
the partial an earlier chunk left when a run straddles two calls), so
``ops.py`` can feed a long list through in chunks.

The ⊗-product runs on the MXU for ``mxu`` semirings and on the VPU
otherwise, via the shared :func:`_tile_product`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import Semiring, get_semiring
from .bsr_spgemm import _tile_product


def _group_edges(pc_ref, p, n_pairs):
    """(first, last) flags for the run of equal ``pc`` values around p."""
    prev = pc_ref[jnp.maximum(p - 1, 0)]
    nxt = pc_ref[jnp.minimum(p + 1, n_pairs - 1)]
    first = (p == 0) | (pc_ref[p] != prev)
    last = (p == n_pairs - 1) | (pc_ref[p] != nxt)
    return first, last


def _pairlist_kernel(pa_ref, pb_ref, pc_ref, a_ref, b_ref, c_ref, o_ref,
                     acc_ref, *, sr: Semiring, n_pairs: int):
    p = pl.program_id(0)
    first, last = _group_edges(pc_ref, p, n_pairs)

    @pl.when(first)
    def _init():
        acc_ref[...] = c_ref[0]

    part = _tile_product(a_ref[0], b_ref[0], sr=sr)
    acc_ref[...] = sr.add(acc_ref[...], part)

    @pl.when(last)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def bsr_pairlist_pallas(a_tiles: jnp.ndarray, b_tiles: jnp.ndarray,
                        pair_a: jnp.ndarray, pair_b: jnp.ndarray,
                        pair_c: jnp.ndarray, c_tiles: jnp.ndarray, *,
                        semiring="plus_times",
                        interpret: bool = False) -> jnp.ndarray:
    """Pair-list contraction ⊕-accumulated into packed C tiles
    ``c_tiles [n_c, bm, bn]`` (updated in place and returned).

    ``pair_c`` must be sorted ascending (one contiguous VMEM-resident run
    per C tile — the Pallas output-revisiting contract).
    """
    sr = get_semiring(semiring)
    n_pairs = pair_a.shape[0]
    bm, bk = a_tiles.shape[1], a_tiles.shape[2]
    bn = b_tiles.shape[2]
    c_spec = pl.BlockSpec((1, bm, bn), lambda p, pa, pb, pc: (pc[p], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda p, pa, pb, pc: (pa[p], 0, 0)),
            pl.BlockSpec((1, bk, bn), lambda p, pa, pb, pc: (pb[p], 0, 0)),
            c_spec,
        ],
        out_specs=c_spec,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_pairlist_kernel, sr=sr, n_pairs=n_pairs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c_tiles.shape, jnp.float32),
        input_output_aliases={5: 0},
        interpret=interpret,
    )(pair_a, pair_b, pair_c, a_tiles, b_tiles, c_tiles)


# ---------------------------------------------------------------------------
# Fused pair-list ⊕-reduce: per-output-block partial vectors, C never exists.
# ---------------------------------------------------------------------------

def _pairlist_reduce_kernel(pa_ref, pb_ref, po_ref, a_ref, b_ref, c_ref,
                            o_ref, acc_ref, *, sr: Semiring, axis: int,
                            n_pairs: int):
    p = pl.program_id(0)
    first, last = _group_edges(po_ref, p, n_pairs)

    @pl.when(first)
    def _init():
        acc_ref[...] = c_ref[0]

    part = _tile_product(a_ref[0], b_ref[0], sr=sr)      # [bm, bn]
    if axis == 1:
        acc = acc_ref[...]                               # [bm, 128]
        for c0 in range(0, part.shape[1], 128):
            acc = sr.add(acc, part[:, c0:c0 + 128])
    else:
        acc = acc_ref[...]                               # [8, bn]
        for r0 in range(0, part.shape[0], 8):
            acc = sr.add(acc, part[r0:r0 + 8, :])
    acc_ref[...] = acc

    @pl.when(last)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def bsr_pairlist_reduce_pallas(a_tiles: jnp.ndarray, b_tiles: jnp.ndarray,
                               pair_a: jnp.ndarray, pair_b: jnp.ndarray,
                               pair_o: jnp.ndarray, partials: jnp.ndarray, *,
                               axis: int, semiring="plus_times",
                               interpret: bool = False) -> jnp.ndarray:
    """Pair-list fused reduce ⊕-accumulated into lane/sublane partials per
    output block (``partials``, updated in place and returned).

    ``pair_o`` groups pairs by output *block-row* (``axis=1``) or
    *block-col* (``axis=0``) and must be sorted ascending.  ``partials``
    is ``[n_o, bm, 128]`` (axis=1) or ``[n_o, 8, bn]`` (axis=0); the
    caller ⊕-folds the residual lanes/sublanes (exactly as
    :func:`bsr_spgemm_reduce_pallas`).
    """
    sr = get_semiring(semiring)
    assert axis in (0, 1), axis
    n_pairs = pair_a.shape[0]
    bm, bk = a_tiles.shape[1], a_tiles.shape[2]
    bn = b_tiles.shape[2]
    acc_shape = (bm, 128) if axis == 1 else (8, bn)
    assert partials.shape[1:] == acc_shape, (partials.shape, acc_shape)
    o_spec = pl.BlockSpec((1,) + acc_shape,
                          lambda p, pa, pb, po: (po[p], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda p, pa, pb, po: (pa[p], 0, 0)),
            pl.BlockSpec((1, bk, bn), lambda p, pa, pb, po: (pb[p], 0, 0)),
            o_spec,
        ],
        out_specs=o_spec,
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_pairlist_reduce_kernel, sr=sr, axis=axis,
                          n_pairs=n_pairs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(partials.shape, jnp.float32),
        input_output_aliases={5: 0},
        interpret=interpret,
    )(pair_a, pair_b, pair_o, a_tiles, b_tiles, partials)
