"""The ingest programs: a delta folded batch by batch, and base ⊕ delta.

Both are zero-collective and never densify (declared via ``@contract``,
proven by ``d4mcheck``), and neither sorts anything larger than a batch:

* :func:`_append_prog` — one canonical triple batch into the device
  delta.  The delta is first carried onto the batch's grown keyspaces
  (:func:`_rerank`); the batch finds its place in the delta and in the
  base by lexicographic search on (row, col) pairs
  (:func:`~repro.kernels.sorted_merge.ops.pair_search`), and the merge is
  :func:`_overlay`.  Each delta entry keeps its lower bound in the base.
* :func:`_merge_read_prog` — the whole-table ``base ⊕ delta`` that a
  compaction (and a read that needs the whole table) runs.  The delta
  brings its lower bounds, so the merge searches nothing: base entries
  move by log-step shifts (``pack`` over ⊕-cancelled entries, ``spread``
  over inserted ones), the delta's entries land in the gaps, and the
  work is ``capb · log2(capd)`` element moves plus scatters of the
  delta's size — valid for keyspaces of any size, since no (row, col)
  pair is linearized into one integer.
* :func:`_dist_merge_prog` — sharded overlay merge: delta triples are
  routed to their owning row shard on host (key-partitioned at insert),
  so the merge is one shard-local concat + canonicalize under
  ``shard_map`` with **zero collectives**; the optional rank-translation
  gathers rerank the resident base onto the union keyspaces in the same
  program.

Programs are cached builders (``functools.lru_cache``) keyed on the
aggregate and the rerank form; array shapes key jit's own cache.  The
device table keeps every shape in a bounded set — one delta capacity, one
batch capacity per batch size, a base capacity that only grows by
doubling — so a stream of inserts, reads and compactions reuses the
programs its first batches compiled.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.analysis.contracts import contract
from repro.core.coo import SENT, dedup_sorted_coo
from repro.kernels.sorted_merge.ops import pack, pair_search, spread

__all__ = ["AGG_OPS", "REMAP_SLOTS", "append", "merge_read", "dist_merge"]

# Device/dist ingest aggregates: restricted to the associative AND
# commutative monoids (jnp.lexsort gives no stability guarantee, so an
# order-sensitive ⊕ like "concat" is host-layer-only).
AGG_OPS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _agg_op(aggregate: str):
    op = AGG_OPS.get(aggregate)
    if op is None:
        raise ValueError(
            f"device ingest aggregate must be one of {sorted(AGG_OPS)}, "
            f"got {aggregate!r} (host-layer tables accept any Assoc "
            f"aggregator)")
    return op


# New keys per axis that a rerank takes as insertion points; past that
# it takes a full rank map (:func:`_rerank`).
REMAP_SLOTS = 256


def _rerank(x, aux, remap: str):
    """Ranks onto a grown keyspace.  ``remap="shift"``: ``aux`` holds the
    old ranks before which new keys were inserted (sorted, sentinel-
    padded), and a rank moves up by the count at or below it — element-
    wise compares, no gather.  ``remap="map"``: ``aux`` is the old → new
    rank map, one gather."""
    ok = x != SENT
    if remap == "shift":
        new = x + jnp.sum(aux[None, :] <= x[:, None], axis=1,
                          dtype=jnp.int32)
    else:
        new = aux[jnp.clip(x, 0, aux.shape[0] - 1)]
    return jnp.where(ok, new, SENT)


def _overlay(op, base, delta, pos, hit, out_cap: int):
    """Canonical ``base ⊕ delta`` with no sort.

    ``base`` and ``delta`` are ``(rows, cols, vals, extras)`` in canonical
    order (``extras``: further per-entry arrays that travel with their
    entries), ``pos``/``hit`` each delta entry's lower bound in the base
    (:func:`~repro.kernels.sorted_merge.ops.pair_search`).  A delta entry
    that hits ⊕-combines into its base entry (base value on the left) and
    unstores it if the result is zero; the others are inserted.  Base
    entries :func:`pack` over the unstored ones and :func:`spread` over
    the inserted ones; inserted entries land in the gaps by one scatter
    of the delta's size.  Returns the merged columns, ``out_cap`` long.
    """
    br, bc, bv, bx = base
    dr, dc, dv, dx = delta
    grow = out_cap - br.shape[0]
    fills = [SENT, SENT, jnp.zeros((), bv.dtype)] + [jnp.zeros((), x.dtype)
                                                     for x in bx]
    cols = [br, bc, bv] + list(bx)
    if grow:
        cols = [jnp.concatenate([a, jnp.full((grow,), f, a.dtype)])
                for a, f in zip(cols, fills)]
    n, nd = out_cap, dr.shape[0]
    ok = dr != SENT
    dup, add = ok & hit, ok & ~hit
    at = jnp.minimum(pos, n - 1)
    merged = op(cols[2][at], dv)
    gone = dup & (merged == 0)
    cols[2] = cols[2].at[jnp.where(dup & ~gone, pos, n)].set(merged,
                                                             mode="drop")
    gone_at = jnp.zeros(n, jnp.int32).at[jnp.where(gone, pos, n)].set(
        1, mode="drop")
    added_at = jnp.zeros(n + 1, jnp.int32).at[
        jnp.where(add, pos, n + 1)].add(1, mode="drop")
    shift = jnp.cumsum(added_at)[:n]          # inserted at or before i
    gone_cum = jnp.cumsum(gone_at)
    valid = (cols[0] != SENT) & (gone_at == 0)
    nbits = nd.bit_length()
    arrays = cols + [shift]                 # each shift travels with its entry
    fills.append(jnp.int32(0))

    def unstore(args):
        arrays, valid = args
        return pack(list(zip(arrays, fills)), gone_cum - gone_at, valid,
                    nbits)

    arrays, valid = jax.lax.cond(gone.any(), unstore, lambda a: a,
                                 (arrays, valid))
    arrays, valid = spread(list(zip(arrays, fills)), arrays[-1], valid,
                           nbits)
    # the gaps: lower bound, less the unstored base entries below it, plus
    # the inserted entries before this one
    gone_below = jnp.where(pos > 0, gone_cum[jnp.clip(pos - 1, 0, n - 1)], 0)
    slot = jnp.where(add, pos - gone_below + jnp.cumsum(add) - add, n)
    out = [a.at[slot].set(d, mode="drop")
           for a, d in zip(arrays[:-1], [dr, dc, dv] + list(dx))]
    return out, (out[0] != SENT).sum().astype(jnp.int32)


@contract(collectives=0, name="ingest.append",
          note="one triple batch into the delta: two pair searches, "
               "log-step moves and batch-sized scatters; no sort, no "
               "collectives, O(delta capacity) memory")
@functools.lru_cache(maxsize=16)
def _append_prog(aggregate: str, remap: str):
    """The delta ⊕ one canonical batch, the delta first carried onto the
    batch's grown keyspaces.  Batch entries also take their lower bounds
    in the base (``xqr``/``xqc`` code their keys against the base's
    keyspaces), which the delta keeps beside each entry until the next
    whole-table merge."""
    op = _agg_op(aggregate)

    @jax.jit
    def go(br, bc, dr, dc, dv, dp, dh, xr, xc, xv, xqr, xqc, rmap, cmap):
        dr = _rerank(dr, rmap, remap)
        dc = _rerank(dc, cmap, remap)
        xp, xh = pair_search(br, bc, xqr, xqc, coded=True)
        yp, yh = pair_search(dr, dc, xr, xc)
        (r, c, v, p, h), nnz = _overlay(op, (dr, dc, dv, [dp, dh]),
                                        (xr, xc, xv, [xp, xh]), yp, yh,
                                        dr.shape[0])
        return r, c, v, p, h, nnz

    return go


@contract(collectives=0, name="ingest.merge_read",
          note="whole-table base ⊕ delta from the delta's stored lower "
               "bounds: log-step moves over the base and delta-sized "
               "scatters; the base is never sorted, output O(capb)")
@functools.lru_cache(maxsize=16)
def _merge_read_prog(aggregate: str, remap: str):
    """base ⊕ delta into ``out_cap`` slots, the base first carried onto
    the delta's keyspaces."""
    op = _agg_op(aggregate)

    @partial(jax.jit, static_argnames=("out_cap",))
    def go(br, bc, bv, dr, dc, dv, dp, dh, rmap, cmap, *, out_cap):
        br = _rerank(br, rmap, remap)
        bc = _rerank(bc, cmap, remap)
        (r, c, v), nnz = _overlay(op, (br, bc, bv, []), (dr, dc, dv, []),
                                  dp, dh, out_cap)
        return r, c, v, nnz

    return go


@contract(collectives=0, name="ingest.dist_merge_read",
          note="shard-local overlay merge: delta is pre-routed to the "
               "owning row shard, so zero collectives")
@functools.lru_cache(maxsize=16)
def _dist_merge_prog(mesh, aggregate: str, rerank: bool):
    op = _agg_op(aggregate)
    spec = {"rows": P("data", None), "cols": P("data", None),
            "vals": P("data", None), "nnz": P("data")}
    dspec = P("data", None)

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(spec, dspec, dspec, dspec, P(), P()),
             out_specs=spec, check_vma=False)
    def go(a, dr, dc, dv, rmap, cmap):
        a0 = jax.tree.map(lambda x: x[0], a)
        br, bc, bv = a0["rows"], a0["cols"], a0["vals"]
        if rerank:
            ok = br != SENT
            br = jnp.where(ok, rmap[jnp.clip(br, 0, rmap.shape[0] - 1)],
                           SENT)
            bc = jnp.where(ok, cmap[jnp.clip(bc, 0, cmap.shape[0] - 1)],
                           SENT)
        rows = jnp.concatenate([br, dr[0]])
        cols = jnp.concatenate([bc, dc[0]])
        vals = jnp.concatenate([bv, dv[0]])
        r, c, v, n = dedup_sorted_coo(rows, cols, vals, op)
        return {"rows": r[None], "cols": c[None], "vals": v[None],
                "nnz": n[None]}

    return go


# -- eager wrappers (what IngestTable calls) --------------------------------

def append(base, delta, batch, rmap, cmap, aggregate: str, remap: str):
    """Fold one canonical batch ``(rows, cols, vals, row_codes,
    col_codes)`` into ``delta`` ``(rows, cols, vals, pos, hit)``; returns
    the new delta and its entry count."""
    prog = _append_prog(aggregate, remap)
    *out, nnz = prog(base.rows, base.cols, *delta, *batch, rmap, cmap)
    return tuple(out), nnz


def merge_read(base, delta, rmap, cmap, aggregate: str, remap: str,
               out_cap: int):
    """Whole-table ``base ⊕ delta`` → canonical ``(r, c, v, nnz)`` of
    ``out_cap`` slots."""
    prog = _merge_read_prog(aggregate, remap)
    return prog(base.rows, base.cols, base.vals, *delta, rmap, cmap,
                out_cap=out_cap)


def dist_merge(mesh, a_dict, dr, dc, dv, rmap, cmap, aggregate: str,
               rerank: bool):
    """Run the sharded overlay merge program; returns the output COO dict
    (per-shard arrays of length ``capb + capd``)."""
    prog = _dist_merge_prog(mesh, aggregate, rerank)
    return prog(a_dict, dr, dc, dv, rmap, cmap)
