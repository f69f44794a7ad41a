"""Merge-on-read programs: base COO ⊕ delta overlay, on device.

The LSM read path has three compiled programs, all zero-collective and
never densifying (declared via ``@contract``, proven by ``d4mcheck``):

* :func:`_delta_canon_prog` — canonicalize a raw (unsorted, duplicated)
  delta buffer into sorted merged COO: the device work an append batch
  triggers at read time.  One :func:`~repro.core.coo.dedup_sorted_coo`
  pass, nothing else.
* :func:`_merge_read_prog` — single-device overlay merge.  Base is
  already canonical (sorted by (row, col) ⇔ sorted by linearized key),
  so after canonicalizing delta the union layout comes from the
  ``sorted_merge`` rank-count kernel (:func:`overlay_scatter` →
  ``merge_positions``): scatter base, then gather-⊕-scatter delta onto
  the shared slots, then one compaction.  O(capb + capd) work and
  memory — the base is never re-sorted and nothing is densified.
* :func:`_dist_merge_prog` — sharded overlay merge: delta triples are
  routed to their owning row shard on host (key-partitioned at insert),
  so the merge is one shard-local concat + canonicalize under
  ``shard_map`` with **zero collectives**; the optional rank-translation
  gathers rerank the resident base onto the union keyspaces in the same
  program.

All programs are cached builders (``functools.lru_cache``) keyed on the
aggregate name only; array shapes key jit's own trace cache, and ingest
pads delta buffers to power-of-two capacities so sustained streaming
reuses a handful of traces instead of recompiling per batch.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.analysis.contracts import contract
from repro.core.assoc_tensor import coo_compact
from repro.core.coo import SENT, dedup_sorted_coo
from repro.kernels.sorted_merge.ops import overlay_scatter

__all__ = ["AGG_OPS", "delta_canon", "merge_read", "dist_merge"]

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


@contract(collectives=0, name="ingest.append",
          note="delta-buffer canonicalize: one dedup pass, no collectives, "
               "O(cap) memory")
@functools.lru_cache(maxsize=16)
def _delta_canon_prog(aggregate: str):
    op = _agg_op(aggregate)

    @jax.jit
    def go(rows, cols, vals):
        return dedup_sorted_coo(rows, cols, vals, op)

    return go


@contract(collectives=0, name="ingest.merge_read",
          note="overlay merge via the sorted_merge rank-count kernel: "
               "base is never re-sorted, output is O(capb + capd)")
@functools.lru_cache(maxsize=16)
def _merge_read_prog(aggregate: str):
    """base ⊕ delta overlay; ``ncols`` is a traced scalar so a growing
    column keyspace never retraces."""
    op = _agg_op(aggregate)

    @jax.jit
    def go(br, bc, bv, dr, dc, dv, ncols):
        dr, dc, dv, _ = dedup_sorted_coo(dr, dc, dv, op)
        cap = br.shape[0] + dr.shape[0]
        # linearized (row, col) keys: canonical COO order IS linear-key
        # order, so both sides are sorted & repetition-free as the
        # rank-count kernel requires (callers guard nr*ncols < 2**31)
        kb = jnp.where(br != SENT, br * ncols + bc, SENT)
        kd = jnp.where(dr != SENT, dr * ncols + dc, SENT)
        i_dst, j_dst, j_dup = overlay_scatter(kb, kd)
        out_r = jnp.full(cap, SENT, jnp.int32).at[i_dst].set(br, mode="drop")
        out_c = jnp.full(cap, SENT, jnp.int32).at[i_dst].set(bc, mode="drop")
        out_v = jnp.zeros(cap, bv.dtype).at[i_dst].set(bv, mode="drop")
        # delta lands second: a duplicate gathers the base value from the
        # shared slot and ⊕-combines base-on-the-left (host combine order)
        cur = out_v.at[j_dst].get(mode="fill", fill_value=0.0)
        merged = jnp.where(j_dup, op(cur, dv), dv)
        out_r = out_r.at[j_dst].set(dr, mode="drop")
        out_c = out_c.at[j_dst].set(dc, mode="drop")
        out_v = out_v.at[j_dst].set(merged, mode="drop")
        # zero-drop parity with from_triples: ⊕-cancelled entries unstore
        keep = (out_r != SENT) & (out_v != 0.0)
        return coo_compact(out_r, out_c, out_v, keep)

    return go


@functools.lru_cache(maxsize=16)
def _merge_concat_prog(aggregate: str):
    """Fallback overlay merge (concat + one canonicalize) for keyspaces
    too large to linearize into int32 — same result, O(cap log cap)."""
    op = _agg_op(aggregate)

    @jax.jit
    def go(br, bc, bv, dr, dc, dv):
        rows = jnp.concatenate([br, dr])
        cols = jnp.concatenate([bc, dc])
        vals = jnp.concatenate([bv, dv])
        return dedup_sorted_coo(rows, cols, vals, op)

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

def delta_canon(rows, cols, vals, aggregate: str):
    """Canonicalize one padded raw delta buffer → (r, c, v, nnz)."""
    return _delta_canon_prog(aggregate)(rows, cols, vals)


def merge_read(base, dr, dc, dv, aggregate: str, *, nrows: int, ncols: int):
    """Overlay-merge a base AssocTensor's triples with a padded raw delta;
    returns canonical (r, c, v, nnz) of length ``capb + capd``."""
    if nrows * max(ncols, 1) < 2**31 - 1:
        prog = _merge_read_prog(aggregate)
        return prog(base.rows, base.cols, base.vals, dr, dc, dv,
                    jnp.int32(max(ncols, 1)))
    prog = _merge_concat_prog(aggregate)
    return prog(base.rows, base.cols, base.vals, dr, dc, dv)


def dist_merge(mesh, a_dict, dr, dc, dv, rmap, cmap, aggregate: str,
               rerank: bool):
    """Run the sharded overlay merge program; returns the output COO dict
    (per-shard arrays of length ``capb + capd``)."""
    prog = _dist_merge_prog(mesh, aggregate, rerank)
    return prog(a_dict, dr, dc, dv, rmap, cmap)
