"""Jitted wrappers: union/intersection index maps from rank counts.

These back the device AssocTensor's keyspace alignment (the paper's §II.C
index maps).  ``merge_index_maps`` reproduces exactly the contract of
``repro.core.sorted_ops.sorted_union_padded`` but with the Pallas
rank-count kernel as the inner primitive.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sorted_ops import INT_SENTINEL
from repro.kernels import resolve_impl
from .ref import rank_count_ref
from .sorted_merge import rank_count_pallas


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@partial(jax.jit, static_argnames=("impl",))
def rank_count(i, j, *, impl: str = "auto"):
    impl = resolve_impl("rank_count", impl)
    if impl == "ref":
        return rank_count_ref(i, j)
    ni, nj = i.shape[0], j.shape[0]
    rows = min(64, _round_up(-(-ni // 128), 8))
    ip = jnp.pad(i, (0, _round_up(ni, rows * 128) - ni),
                 constant_values=INT_SENTINEL).reshape(-1, 128)
    bj = min(1024, _round_up(nj, 8))
    jp = jnp.pad(j, (0, _round_up(nj, bj) - nj), constant_values=INT_SENTINEL)
    rank, hit = rank_count_pallas(ip, jp, rows=rows, bj=bj,
                                  interpret=(impl == "interpret"))
    # sentinel tails in J inflate nothing (< any valid key is False), but
    # sentinel I entries count all valid J — callers mask by validity.
    return rank.reshape(-1)[:ni], hit.reshape(-1)[:ni]


@partial(jax.jit, static_argnames=("impl",))
def overlay_scatter(i, j, *, impl: str = "auto"):
    """Union destination slots for an LSM overlay merge (base ⊕ delta).

    ``i``/``j`` are sorted, repetition-free, sentinel-padded int32 key
    arrays (base and delta linearized (row, col) keys).  Returns
    ``(i_dst, j_dst, j_dup)``: scatter destinations into a
    ``len(i) + len(j)`` output where a key present in both collapses onto
    one shared slot (``j_dup`` flags those delta entries so the caller can
    ⊕-combine instead of overwrite), and sentinel entries are routed to
    the out-of-bounds slot so ``.at[dst].set(..., mode="drop")`` discards
    them without a mask pass."""
    i_pos, j_pos, j_dup = merge_positions(i, j, impl=impl)
    oob = jnp.int32(i.shape[0] + j.shape[0])
    i_dst = jnp.where(i != INT_SENTINEL, i_pos, oob)
    j_dst = jnp.where(j != INT_SENTINEL, j_pos, oob)
    return i_dst, j_dst, j_dup


@partial(jax.jit, static_argnames=("impl",))
def merge_positions(i, j, *, impl: str = "auto"):
    """UNION positions for two sorted, repetition-free, sentinel-padded
    int32 arrays — duplicates collapse onto one shared slot.

    A duplicate shrinks the union by one, so every element must also
    subtract the number of collapsed pairs BELOW it: that count is the
    exclusive cumsum of its own side's hit flags (both sides are sorted, so
    pairs below i[m] are exactly the matched i's before m)."""
    r_ij, hit_ij = rank_count(i, j, impl=impl)    # J below / matching each I
    r_ji, hit_ji = rank_count(j, i, impl=impl)    # I below / matching each J
    dup_below_i = jnp.cumsum(hit_ij) - hit_ij     # exclusive
    dup_below_j = jnp.cumsum(hit_ji) - hit_ji
    ni, nj = i.shape[0], j.shape[0]
    i_pos = jnp.arange(ni, dtype=jnp.int32) + r_ij - dup_below_i
    j_pos = jnp.arange(nj, dtype=jnp.int32) + r_ji - dup_below_j
    j_dup = hit_ji > 0
    return i_pos, j_pos, j_dup
