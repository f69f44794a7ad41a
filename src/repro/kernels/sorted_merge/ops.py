"""Sorted-set merges: rank counts, and the two-key LSM merge primitives.

``rank_count`` / ``merge_positions`` are the union and intersection index
maps of one-key sorted sets (the paper's §II.C index maps) with the Pallas
rank-count kernel as the inner primitive.

:func:`pair_search`, :func:`spread` and :func:`pack` are what the ingest
merge (:mod:`repro.ingest.merge`) is built from, on (row, col) pairs of
any keyspace size — no key is linearized into one integer:

* :func:`pair_search` — the lexicographic lower bound of each query pair
  in a sorted pair list: ``log2(n)`` rounds of gathers, one per query;
* :func:`spread` / :func:`pack` — move every entry of an array right (left)
  by its own amount, where the amounts never decrease along the array:
  one round per bit of the largest amount, each an element-wise select
  between the array and a copy of it shifted by a power of two.  No
  scatter, no sort, and the work is ``n · log2(max amount)`` element moves.
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


# -- two-key LSM merge primitives ---------------------------------------------

def _key_code(x):
    """A stored rank ``r`` as the query code ``2r + 1`` (sentinels stay)."""
    return jnp.where(x == INT_SENTINEL, INT_SENTINEL, 2 * x + 1)


def pair_search(kr, kc, qr, qc, *, coded: bool = False):
    """Lower bound of each query pair in a sorted key-pair list.

    ``(kr, kc)`` are sorted by (row, col), repetition-free and
    sentinel-padded; ``(qr, qc)`` are query pairs (sentinel rows are
    padding).  Returns ``(pos, hit)``: ``pos`` the number of key pairs
    below the query, ``hit`` whether the pair at ``pos`` equals it.

    With ``coded=True`` the queries are codes against the keys' keyspace:
    ``2r + 1`` for a key of rank ``r`` and ``2p`` for a key absent from it
    that sorts before the key of rank ``p`` — so a pair of keys the
    keyspace lacks still finds its place, and never hits.
    """
    n = kr.shape[0]
    lo = jnp.zeros(qr.shape, jnp.int32)
    hi = jnp.full(qr.shape, n, jnp.int32)

    def key(i):
        i = jnp.minimum(i, n - 1)
        r, c = kr[i], kc[i]
        return (_key_code(r), _key_code(c)) if coded else (r, c)

    def step(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        r, c = key(mid)
        less = (r < qr) | ((r == qr) & (c < qc))
        active = lo < hi
        return (jnp.where(active & less, mid + 1, lo),
                jnp.where(active & ~less, mid, hi))

    pos, _ = jax.lax.fori_loop(0, n.bit_length(), step, (lo, hi))
    r, c = key(pos)
    hit = (pos < n) & (qr != INT_SENTINEL) & (r == qr) & (c == qc)
    return pos, hit


def _shifted(x, by: int, fill):
    """``x`` moved ``by`` slots right (``by > 0``) or left, ``fill`` in."""
    pad = jnp.full((abs(by),), fill, x.dtype)
    if by > 0:
        return jnp.concatenate([pad, x[:-by]])
    return jnp.concatenate([x[-by:], pad])


def _move(cols, amount, valid, nbits: int, right: bool):
    n = amount.shape[0]
    fills = [fill for _, fill in cols]
    arrays = [a for a, _ in cols] + [amount]
    fills.append(0)
    for k in (range(nbits - 1, -1, -1) if right else range(nbits)):
        step = 1 << k
        if step >= n:
            continue
        by = step if right else -step
        go = valid & ((arrays[-1] & step) != 0)
        stay = valid & ~go
        came = _shifted(go, by, False)
        arrays = [jnp.where(came, _shifted(a, by, f), jnp.where(stay, a, f))
                  for a, f in zip(arrays, fills)]
        valid = came | stay
    return arrays[:-1], valid


def spread(cols, amount, valid, nbits: int):
    """Move each valid entry ``i`` right by ``amount[i]``.

    ``cols`` is a list of ``(array, fill)``; ``amount`` must not decrease
    over the valid entries, and ``i + amount[i]`` must stay below the
    length and never repeat.  Bits go from the highest (``nbits - 1``)
    down: after the bits above ``k`` entry ``i`` sits at ``i + a·2^k``
    with ``a`` non-decreasing in ``i``, so no two entries ever meet.
    Returns the moved arrays and the moved validity mask.
    """
    return _move(cols, amount, valid, nbits, right=True)


def pack(cols, amount, valid, nbits: int):
    """Move each valid entry ``i`` left by ``amount[i]`` (non-decreasing
    over the valid entries, targets distinct and ``>= 0``): the inverse
    of :func:`spread`, from the lowest bit up, for the same reason."""
    return _move(cols, amount, valid, nbits, right=False)
