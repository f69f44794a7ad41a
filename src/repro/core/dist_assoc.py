"""Distributed associative arrays: the "Distributed" D of D4M on a mesh.

Historically D4M distributes via Accumulo tablet servers: tables are
row-range-partitioned and algebra pushes down to the servers (Graphulo).
The mesh-native mapping: a ``DistAssoc`` is an ``AssocTensor`` whose COO
triples are **row-rank-range partitioned over the `data` axis** (tablet ↔
shard), and the paper's operations decompose as:

  * element-wise ⊕ / ⊗ — row partitions are disjoint and aligned, so both
    are embarrassingly parallel ``shard_map`` calls (zero collectives);
  * array product ``A ⊗.⊕ B`` — contraction keys live on the row axis of B,
    so with B **broadcast** (replicated triples) each shard computes a
    LOCAL sparse product against its own rows: an expand-join on rank
    triples (:func:`repro.core.coo.expand_join_coo`) plus one canonical
    merge, never densifying.  Row supports are disjoint ⇒ the result is
    row-sharded on the same boundaries with **zero collectives** — the
    Graphulo server-side pattern with the combine elided entirely;
  * fused reductions (``matmul_reduce`` / ``sqout(reduce=)`` / degree) —
    each shard ⊕-folds its products straight into a dense vector and the
    partials merge with exactly **one** psum-family collective
    (:func:`repro.core.semiring.mesh_combine`);
  * global reductions (row/col ⊕-sums) — local segment scatter + the same
    one collective.

Shards keep the full keyspaces (host-side, cheap) and static capacity
``cap / n_shards``; re-sharding for elasticity is a host-side split by
row-rank ranges (same code path the checkpoint restore uses).

The product supports three *communication strategies*, chosen per multiply
by the host cost model (:func:`repro.core.spgemm.plan_dist_matmul`) from
the exact per-block product counts the planner already computes:

  * ``replicate`` — broadcast-B as above: **0** collectives, moves
    ``P·nnz(B)`` triples at staging.  Wins while B is small.
  * ``all_to_all`` — B stays sharded by contraction range (a resident
    ``DistAssoc`` B is reused *in place*: the monotone
    :meth:`KeySpace.union` rank maps keep its row partition a contiguous
    contraction partition); each shard expand-joins the replicated A
    triples against its own B block, buckets the partial products by
    destination row shard, and **one** packed ``all_to_all`` delivers
    them for the ⊕-merge.  B's triples never replicate.
  * ``2d`` — SUMMA-flavored grid ``(pr, pc)`` picked by
    :func:`repro.core.spgemm.suggest_grid`: B splits into ``pc``
    contraction blocks (each staged to ``pr`` shards), A never moves, and
    ``pc`` rounds of shard-local expand-join interleave with ``pc−1``
    ring ``ppermute`` shifts of the packed block.  Wins the square /
    hub-heavy regime where both replication and bucket padding hurt.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.analysis.contracts import contract

from .assoc_tensor import (AssocTensor, DISPATCH_STATS, _bump_dispatch,
                           coo_axis_mask_keep, coo_compact, coo_mask_keep,
                           coo_range_keep)
from .coo import (SENT, bucket_coo_by_range, dedup_sorted_coo,
                  expand_join_coo)
from .expr import EwiseAdd, EwiseMul, MatMul, Select, Source
from .keyspace import KeySpace
from .semiring import (PLUS_TIMES, get_semiring, mesh_combine,
                       scatter_combine)
from .spgemm import (BSR_AUTO_EXPAND, TILE, _round_up, pad_to_cap,
                     plan_dist_matmul)

__all__ = ["DistAssoc"]


# ---------------------------------------------------------------------------
# Cached shard_map programs.  A bare shard_map call re-traces and re-lowers
# on EVERY invocation (there is no dispatch cache outside jit) — on an
# 8-shard CPU mesh that is seconds per call.  The matmul-family programs are
# pure functions of (mesh, semiring, static sizes), so one lru_cache'd
# jit(shard_map(...)) per signature makes repeated products dispatch-cheap.
# Semiring is a frozen dataclass and Mesh is hashable: both key cleanly.
# ---------------------------------------------------------------------------

_COO_SPEC = ("rows", "cols", "vals")

def _local_coo_spec():
    """PartitionSpec tree of the per-shard COO dict (``_local_spec``'s
    static twin, so cached program builders need no instance)."""
    return {"rows": P("data", None), "cols": P("data", None),
            "vals": P("data", None), "nnz": P("data")}

# auto-strategy crossover for DistAssoc.matmul: below this per-shard
# expand-join size the jit-safe coo shard_map program wins (one fused
# dispatch, no host loop); above it the tiled pair-list strategy's
# O(products-touched) work beats the full expansion buffer.  Lives in
# spgemm so the distribution cost model can price the switch (its host
# planning rescans B per shard).
_BSR_AUTO_EXPAND = BSR_AUTO_EXPAND


@functools.lru_cache(maxsize=256)
def _matmul_prog(mesh: Mesh, sr, expand: int, out_cap: int):
    spec = {k: P("data", None) for k in _COO_SPEC}
    out_spec = {"rows": P("data", None), "cols": P("data", None),
                "vals": P("data", None), "nnz": P("data"),
                "true_nnz": P("data")}

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(spec, P(), P(), P()),
             out_specs=out_spec, check_vma=False)
    def go(a, br, bc, bv):
        pr, pc, pv, _ = expand_join_coo(
            a["rows"][0], a["cols"][0], a["vals"][0], br, bc, bv,
            sr.mul, zero=sr.zero, expand=expand)
        r, c, v, nnz = dedup_sorted_coo(pr, pc, pv, sr.add, zero=sr.zero)
        r, c, v = pad_to_cap(r, c, v, out_cap, sr.zero)
        # true (pre-clamp) nnz rides along so the eager caller can surface
        # per-shard capacity overflow instead of truncating silently
        return {"rows": r[None], "cols": c[None], "vals": v[None],
                "nnz": jnp.minimum(nnz, out_cap)[None],
                "true_nnz": nnz[None]}

    return go


@functools.lru_cache(maxsize=256)
def _matmul_reduce_prog(mesh: Mesh, sr, expand: int, n_out: int, axis: int):
    spec = {k: P("data", None) for k in _COO_SPEC}

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(spec, P(), P(), P()),
             out_specs=P(), check_vma=False)
    def go(a, br, bc, bv):
        pr, pc, pv, _ = expand_join_coo(
            a["rows"][0], a["cols"][0], a["vals"][0], br, bc, bv,
            sr.mul, zero=sr.zero, expand=expand)
        keys = pr if axis == 1 else pc
        vec = jnp.full((n_out,), sr.zero, jnp.float32)
        vec = scatter_combine(vec, keys, pv, sr)  # SENT keys drop
        return mesh_combine(vec, "data", sr)

    return go


@functools.lru_cache(maxsize=256)
def _col_reduce_prog(mesh: Mesh, sr, nc: int, dt):
    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(P("data"), P("data"), P("data")),
             out_specs=P(), check_vma=False)
    def go(cols, vals, rows):
        ok = rows[0] != SENT
        vec = jnp.full((nc,), sr.zero, dt)
        vec = scatter_combine(vec, jnp.where(ok, cols[0], nc),
                              jnp.where(ok, vals[0], sr.zero), sr)
        return mesh_combine(vec, "data", sr)

    return go


@functools.lru_cache(maxsize=256)
def _col_degree_prog(mesh: Mesh, nc: int):
    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
             out_specs=P(), check_vma=False)
    def go(cols, rows):
        ok = rows[0] != SENT
        vec = jnp.zeros((nc,), jnp.int32)
        vec = vec.at[jnp.where(ok, cols[0], nc)].add(
            jnp.where(ok, 1, 0).astype(jnp.int32), mode="drop")
        return jax.lax.psum(vec, "data")

    return go


@functools.lru_cache(maxsize=256)
def _matvec_prog(mesh: Mesh, sr, nr: int, dt):
    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(P("data"), P("data"), P("data"), P()),
             out_specs=P(), check_vma=False)
    def go(rows, cols, vals, xv):
        ok = rows[0] != SENT
        contrib = sr.mul(jnp.where(ok, vals[0], sr.zero).astype(dt),
                         xv[jnp.clip(cols[0], 0, xv.shape[0] - 1)]
                         .astype(dt))
        y = jnp.full((nr,), sr.zero, dt)
        y = scatter_combine(y, jnp.where(ok, rows[0], nr),
                            jnp.where(ok, contrib, sr.zero), sr)
        return mesh_combine(y, "data", sr)

    return go


def _shard_selection_keep(a0, row_gather: bool, col_gather: bool,
                          bnds, rm, cm):
    """Shard-local keep mask for a compiled selection — the one dispatch
    body shared by ``__getitem__`` and ``__setitem__`` (range kernel /
    multirange OR / hybrid / double-gather, exactly as
    ``AssocTensor._selection_keep``).  ``bnds`` is the ``[k, 4]`` box list
    from ``select.plan_boxes`` (k static inside the shard_map trace)."""
    if row_gather and col_gather:
        return coo_mask_keep(a0["rows"], a0["cols"], rm, cm)
    keep = coo_range_keep(a0["rows"], a0["cols"], bnds[0])
    for i in range(1, bnds.shape[0]):
        keep = keep | coo_range_keep(a0["rows"], a0["cols"], bnds[i])
    if row_gather:
        keep = keep & coo_axis_mask_keep(a0["rows"], rm)
    if col_gather:
        keep = keep & coo_axis_mask_keep(a0["cols"], cm)
    return keep


@functools.lru_cache(maxsize=256)
def _reduce_add_n_prog(mesh: Mesh, sr, axis: int, n_out: int, n_terms: int):
    """Fused ``⊕-reduce(t₁ ⊕ t₂ ⊕ …, axis)`` over aligned sharded terms.

    The planner's Reduce-through-EwiseAdd rewrite lands here: instead of
    materializing the ⊕-merged array (a concat + sort per shard) and then
    reducing it, every term's triples scatter straight into one dense
    partial vector and the partials merge with exactly **one** psum-family
    collective — same contract as ``_matmul_reduce_prog``.
    """
    spec = _local_coo_spec()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(spec,) * n_terms,
             out_specs=P(), check_vma=False)
    def go(*parts):
        vec = jnp.full((n_out,), sr.zero, jnp.float32)
        for p in parts:
            ok = p["rows"][0] != SENT
            keys = p["rows"][0] if axis == 1 else p["cols"][0]
            vec = scatter_combine(vec, jnp.where(ok, keys, n_out),
                                  jnp.where(ok, p["vals"][0], sr.zero), sr)
        return mesh_combine(vec, "data", sr)

    return go


@functools.lru_cache(maxsize=256)
def _select_prog(mesh: Mesh, row_gather: bool, col_gather: bool):
    """Shard-local selection program (``__getitem__``'s executor).

    Cached by dispatch kind only: the box list / masks ride in as traced
    arguments, so every selection with the same (mesh, dispatch) shape
    reuses one compiled program instead of re-tracing a bare shard_map
    per call.
    """
    spec = _local_coo_spec()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(spec, P(), P(), P()),
             out_specs=spec, check_vma=False)
    def go(a, bnds, rm, cm):
        a0 = jax.tree.map(lambda x: x[0], a)
        # same raw-array primitives as AssocTensor — layers cannot drift
        keep = _shard_selection_keep(a0, row_gather, col_gather,
                                     bnds, rm, cm)
        r, c, v, nnz = coo_compact(a0["rows"], a0["cols"], a0["vals"], keep)
        return {"rows": r[None], "cols": c[None], "vals": v[None],
                "nnz": nnz[None]}

    return go


@functools.lru_cache(maxsize=256)
def _setvals_prog(mesh: Mesh, row_gather: bool, col_gather: bool):
    """Selector-targeted value overwrite (``__setitem__``'s executor).

    The scalar rides in as a traced argument — assigning a different
    value hits the same compiled program.
    """
    spec = _local_coo_spec()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(spec, P(), P(), P(), P()),
             out_specs=P("data", None), check_vma=False)
    def go(a, bnds, rm, cm, val):
        a0 = jax.tree.map(lambda x: x[0], a)
        keep = _shard_selection_keep(a0, row_gather, col_gather,
                                     bnds, rm, cm)
        return jnp.where(keep, val.astype(a0["vals"].dtype),
                         a0["vals"])[None]

    return go


@functools.lru_cache(maxsize=256)
def _ewise_prog(mesh: Mesh, sr, op: str):
    """Element-wise ⊕ / ⊗ program: disjoint aligned row partitions, so the
    whole operation is one shard-local canonical merge, zero collectives."""
    spec = _local_coo_spec()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
             check_vma=False)
    def go(a, b):
        # keyspaces are host metadata; inside shard_map the algebra runs
        # on raw rank arrays via the same canonicalization primitive the
        # single-device AssocTensor uses.
        a0 = jax.tree.map(lambda x: x[0], a)
        b0 = jax.tree.map(lambda x: x[0], b)
        rows = jnp.concatenate([a0["rows"], b0["rows"]])
        cols = jnp.concatenate([a0["cols"], b0["cols"]])
        vals = jnp.concatenate([a0["vals"], b0["vals"]])
        if op == "add":
            r, c, v, n = dedup_sorted_coo(rows, cols, vals, sr.add,
                                          zero=sr.zero)
            out = {"rows": r, "cols": c, "vals": v, "nnz": n}
        else:
            src = jnp.concatenate([
                jnp.zeros(a0["rows"].shape[0], jnp.int32),
                jnp.ones(b0["rows"].shape[0], jnp.int32)])
            r, c, v, n = dedup_sorted_coo(
                rows, cols, vals, sr.add, zero=sr.zero,
                require_pair=True, pair_op=sr.mul, src=src)
            cap = min(a0["rows"].shape[0], b0["rows"].shape[0])
            out = {"rows": r[:cap], "cols": c[:cap], "vals": v[:cap],
                   "nnz": jnp.minimum(n, cap)}
        return {"rows": out["rows"][None], "cols": out["cols"][None],
                "vals": out["vals"][None], "nnz": out["nnz"][None]}

    return go


# ---------------------------------------------------------------------------
# Sharded-B communication strategies.  The partial-product exchange and the
# ring shift both move ONE packed int32 array (rows, cols, bitcast values
# stacked on a trailing axis) — three separate collectives would triple the
# trip count the contracts pin down.
# ---------------------------------------------------------------------------

def _pack_coo(rows, cols, vals):
    """Stack COO triples into one int32 array (vals bitcast) — the unit a
    single collective can move."""
    return jnp.stack(
        [rows, cols,
         jax.lax.bitcast_convert_type(vals.astype(jnp.float32), jnp.int32)],
        axis=-1)


def _unpack_coo(packed):
    return (packed[..., 0], packed[..., 1],
            jax.lax.bitcast_convert_type(packed[..., 2], jnp.float32))


@contract(collectives=1, name="dist.matmul_all_to_all",
          note="sharded-B product: one packed all_to_all of partial "
               "products, B never replicated")
@functools.lru_cache(maxsize=256)
def _matmul_a2a_prog(mesh: Mesh, sr, expand: int, bucket_cap: int,
                     out_cap: int, n_shards: int):
    """Sharded-B all-to-all product program.

    A's triples arrive replicated (``[n_shards, cap]``, flattened in the
    body); each shard expand-joins them against its OWN contraction block
    of B, buckets the partial products by destination row shard
    (:func:`bucket_coo_by_range` over the result's ``row_bounds``), and
    exactly one ``all_to_all`` of the packed ``[P, bucket_cap, 3]`` buffer
    delivers every product to the shard owning its output row, where one
    canonical merge ⊕-dedups them.  ``true_nnz`` rides along for the
    overflow warning, as in ``_matmul_prog``.
    """
    b_spec = {k: P("data", None) for k in _COO_SPEC}
    out_spec = {"rows": P("data", None), "cols": P("data", None),
                "vals": P("data", None), "nnz": P("data"),
                "true_nnz": P("data")}

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(P(), P(), P(), b_spec, P(), P()),
             out_specs=out_spec, check_vma=False)
    def go(ar, ac, av, b, bm, bounds):
        # rerank the resident B block's rows onto the merged contraction
        # space in-program (bm is monotone, so the block stays sorted);
        # staged B passes the identity map
        rb0 = b["rows"][0]
        okb = rb0 != SENT
        rb = jnp.where(okb, bm[jnp.clip(rb0, 0, bm.shape[0] - 1)], SENT)
        pr, pc, pv, _ = expand_join_coo(
            ar.reshape(-1), ac.reshape(-1), av.reshape(-1),
            rb, b["cols"][0], b["vals"][0],
            sr.mul, zero=sr.zero, expand=expand)
        br, bc, bv = bucket_coo_by_range(pr, pc, pv, bounds, n_shards,
                                         bucket_cap, zero=sr.zero)
        got = jax.lax.all_to_all(_pack_coo(br, bc, bv), "data",
                                 split_axis=0, concat_axis=0, tiled=True)
        rows, cols, vals = _unpack_coo(got)
        r, c, v, nnz = dedup_sorted_coo(rows.reshape(-1), cols.reshape(-1),
                                        vals.reshape(-1), sr.add,
                                        zero=sr.zero)
        r, c, v = pad_to_cap(r, c, v, out_cap, sr.zero)
        return {"rows": r[None], "cols": c[None], "vals": v[None],
                "nnz": jnp.minimum(nnz, out_cap)[None],
                "true_nnz": nnz[None]}

    return go


@contract(collectives=3, name="dist.matmul_2d",
          note="SUMMA-style grid: pc−1 packed ring ppermutes "
               "(probe grid 2×4 → 3); A never moves")
@functools.lru_cache(maxsize=256)
def _matmul_ring_prog(mesh: Mesh, sr, pr: int, pc: int, round_expand: int,
                      out_cap: int):
    """2D-grid ring product program.

    Shard ``s = (g, p)`` (``g = s // pc``) keeps its own A rows and starts
    with B contraction block ``p``; each of the ``pc`` rounds contracts
    the resident block locally, then one ``ppermute`` ring-shifts the
    packed block within the group (``pc−1`` shifts total — the last round
    skips it).  Output rows never leave their owner shard, so the round
    buffers concat + one canonical merge finish the product with no
    further communication.
    """
    a_spec = {k: P("data", None) for k in _COO_SPEC}
    out_spec = {"rows": P("data", None), "cols": P("data", None),
                "vals": P("data", None), "nnz": P("data"),
                "true_nnz": P("data")}
    n_shards = pr * pc
    perm = [(s, (s // pc) * pc + ((s % pc) - 1) % pc)
            for s in range(n_shards)]

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(a_spec, a_spec),
             out_specs=out_spec, check_vma=False)
    def go(a, b):
        ar, ac, av = a["rows"][0], a["cols"][0], a["vals"][0]
        bpk = _pack_coo(b["rows"][0], b["cols"][0], b["vals"][0])
        parts = []
        for rnd in range(pc):
            br, bc, bv = _unpack_coo(bpk)
            parts.append(expand_join_coo(ar, ac, av, br, bc, bv, sr.mul,
                                         zero=sr.zero,
                                         expand=round_expand)[:3])
            if rnd + 1 < pc:
                bpk = jax.lax.ppermute(bpk, "data", perm)
        rows = jnp.concatenate([p[0] for p in parts])
        cols = jnp.concatenate([p[1] for p in parts])
        vals = jnp.concatenate([p[2] for p in parts])
        r, c, v, nnz = dedup_sorted_coo(rows, cols, vals, sr.add,
                                        zero=sr.zero)
        r, c, v = pad_to_cap(r, c, v, out_cap, sr.zero)
        return {"rows": r[None], "cols": c[None], "vals": v[None],
                "nnz": jnp.minimum(nnz, out_cap)[None],
                "true_nnz": nnz[None]}

    return go


@contract(collectives=1, name="dist.matmul_reduce_all_to_all",
          note="sharded-B fused epilogue: one mesh_combine, no exchange "
               "of partial products needed")
@functools.lru_cache(maxsize=256)
def _matmul_reduce_a2a_prog(mesh: Mesh, sr, expand: int, n_out: int,
                            axis: int):
    """Sharded-B twin of ``_matmul_reduce_prog``: each shard folds the
    products of ITS contraction block straight into the dense output
    vector, and the one psum-family collective both merges the partials
    and replaces the partial-product exchange — the all-to-all variant of
    the fused epilogue is no chattier than the replicate one."""
    b_spec = {k: P("data", None) for k in _COO_SPEC}

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(), P(), P(), b_spec, P()),
             out_specs=P(), check_vma=False)
    def go(ar, ac, av, b, bm):
        rb0 = b["rows"][0]
        okb = rb0 != SENT
        rb = jnp.where(okb, bm[jnp.clip(rb0, 0, bm.shape[0] - 1)], SENT)
        pr, pc, pv, _ = expand_join_coo(
            ar.reshape(-1), ac.reshape(-1), av.reshape(-1),
            rb, b["cols"][0], b["vals"][0],
            sr.mul, zero=sr.zero, expand=expand)
        keys = pr if axis == 1 else pc
        vec = jnp.full((n_out,), sr.zero, jnp.float32)
        vec = scatter_combine(vec, keys, pv, sr)  # SENT keys drop
        return mesh_combine(vec, "data", sr)

    return go


@contract(collectives=0, name="dist.matmul_bsr",
          note="one shard_map for the whole tiled product: per-shard "
               "pair lists ride in as traced operands")
@functools.lru_cache(maxsize=256)
def _matmul_bsr_prog(mesh: Mesh, sr, n_a: int, n_c: int, m: int, n: int,
                     out_cap: int, kernel_impl: str):
    """Single-program tiled (BSR pair-list) replicate-strategy product.

    Replaces the eager per-shard host loop: every shard packs its own A
    tiles from traced scatter targets, contracts its planned tile-pair
    list against the once-packed replicated B tiles
    (:func:`repro.kernels.bsr_spgemm.ops.bsr_pairlist` — the
    scalar-prefetch Pallas kernel on TPU, the jnp oracle elsewhere), and
    extracts canonical COO from its C tiles — one dispatch for the whole
    mesh instead of ``n_shards`` planner+kernel round-trips.  Per-shard
    plans are padded to uniform static sizes on host: dummy pairs target
    the extra C slot ``n_c`` (discarded), padded entries/blocks scatter
    out of bounds (dropped) or land past ``(m, n)`` (filtered).
    """
    shard1 = P("data", None)
    out_spec = {"rows": shard1, "cols": shard1, "vals": shard1,
                "nnz": P("data"), "true_nnz": P("data")}

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(shard1, shard1, shard1, shard1, P(),
                       shard1, shard1, shard1, P("data", None, None)),
             out_specs=out_spec, check_vma=False)
    def go(av, tof, lr, lc, b_tiles, pa, pb, pcc, cblk):
        from repro.kernels.bsr_spgemm.ops import bsr_pairlist
        a_tiles = jnp.full((n_a, TILE, TILE), sr.zero, jnp.float32)
        a_tiles = a_tiles.at[tof[0], lr[0], lc[0]].set(
            av[0].astype(jnp.float32), mode="drop")
        c_tiles = bsr_pairlist(a_tiles, b_tiles, pa[0], pb[0], pcc[0],
                               n_c=n_c + 1, semiring=sr, impl=kernel_impl)
        c_use = c_tiles[:n_c]                      # drop the dummy slot
        iota = jnp.arange(TILE, dtype=jnp.int32)
        rows_g = (cblk[0][:, 0, None, None] * TILE
                  + iota[None, :, None])
        cols_g = (cblk[0][:, 1, None, None] * TILE
                  + iota[None, None, :])
        rows_g = jnp.broadcast_to(rows_g, c_use.shape).reshape(-1)
        cols_g = jnp.broadcast_to(cols_g, c_use.shape).reshape(-1)
        vals_g = c_use.reshape(-1)
        keep = (vals_g != sr.zero) & (rows_g < m) & (cols_g < n)
        r, c, v, nnz = coo_compact(rows_g, cols_g, vals_g, keep)
        r, c, v = pad_to_cap(r, c, v, out_cap, sr.zero)
        return {"rows": r[None], "cols": c[None], "vals": v[None],
                "nnz": jnp.minimum(nnz, out_cap)[None],
                "true_nnz": nnz[None]}

    return go


@dataclasses.dataclass
class _MatmulSetup:
    """Host-side product prologue state shared by every strategy.

    ``a_*_h`` / ``counts`` / ``b_rows_h`` feed the distribution cost model
    (:func:`repro.core.spgemm.plan_dist_matmul`); the ``b_*_h`` triples are
    already in the merged contraction rank space, sorted by row, and back
    both the staging paths and the lazily built replicated-B tensor.
    """

    a_loc: AssocTensor             # sharded stacked triples, logical-coerced
    a_cols: jnp.ndarray            # device [P, cap] contraction-space cols
    a_rows_h: np.ndarray
    a_cols_h: np.ndarray
    counts: np.ndarray             # [P, cap] exact per-entry product counts
    ks: KeySpace                   # merged contraction keyspace
    b_col_space: KeySpace
    b_resident: bool               # B is a DistAssoc on this mesh
    b_repl: Optional[AssocTensor]  # replicated reranked B (lazy if resident)
    b_other: Optional["DistAssoc"]
    b_map: np.ndarray              # B row rank → merged rank (monotone)
    b_rows_h: np.ndarray           # sorted valid merged contraction ranks
    b_cols_h: np.ndarray
    b_vals_h: np.ndarray
    a2a_bounds: Optional[np.ndarray]   # resident B's mapped partition


class DistAssoc:
    """Row-partitioned AssocTensor over a mesh's ``data`` axis."""

    # eager metadata default (mirrors AssocTensor.overflow): matmul sets an
    # instance attribute when a shard truncated its result
    overflow = False

    def __init__(self, local: AssocTensor, mesh: Mesh, *,
                 row_bounds: np.ndarray):
        """``local``: stacked per-shard COO [n_shards, cap_local] arrays
        (leading axis sharded over `data`).  ``row_bounds``: shard row-rank
        boundaries, len n_shards+1."""
        self.local = local
        self.mesh = mesh
        self.row_bounds = row_bounds

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_triples(rows, cols, vals, mesh: Mesh, *, aggregate="min",
                     capacity_per_shard: Optional[int] = None) -> "DistAssoc":
        n_shards = mesh.shape["data"]
        row_space = KeySpace(np.asarray(rows))
        col_space = KeySpace(np.asarray(cols))
        r, _ = row_space.rank(np.asarray(rows))
        # contiguous rank ranges (tablet splits)
        bounds = np.linspace(0, len(row_space), n_shards + 1).astype(np.int64)
        shard_of = np.searchsorted(bounds[1:], r, side="right")
        cap = capacity_per_shard or int(
            max(8, np.ceil(max(np.bincount(shard_of, minlength=n_shards).max(), 1) / 8) * 8))

        locs = []
        rows_np, cols_np, vals_np = (np.asarray(rows), np.asarray(cols),
                                     np.asarray(vals))
        for s in range(n_shards):
            m = shard_of == s
            locs.append(AssocTensor.from_triples(
                rows_np[m] if m.any() else rows_np[:0],
                cols_np[m] if m.any() else cols_np[:0],
                vals_np[m] if m.any() else vals_np[:0],
                aggregate=aggregate, capacity=cap,
                row_space=row_space, col_space=col_space))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *locs)
        sharded = jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(mesh, P(*( ("data",) + (None,) * (x.ndim - 1))))),
            stacked)
        return DistAssoc(sharded, mesh, row_bounds=bounds)

    @staticmethod
    def from_assoc(a, mesh: Mesh, *, aggregate="min",
                   capacity_per_shard: Optional[int] = None) -> "DistAssoc":
        """Shard a host Assoc over the mesh (host ⇄ device ⇄ dist pipeline)."""
        r, c, v = a.triples()
        return DistAssoc.from_triples(
            r, c, v, mesh, aggregate=aggregate,
            capacity_per_shard=capacity_per_shard)

    # -- conversions -----------------------------------------------------------
    def to_assoc(self):
        """Gather all shards to a host Assoc (small-data paths/tests)."""
        from .assoc import Assoc
        n_shards = self.mesh.shape["data"]
        host = jax.tree.map(np.asarray, self.local)   # one device→host copy
        merged = None
        for s in range(n_shards):
            local = jax.tree.map(lambda x: x[s], host)
            a = local.to_assoc()
            merged = a if merged is None else merged + a if a.nnz() else merged
        return merged

    def gather_replicated(self) -> AssocTensor:
        """All shards' triples as ONE replicated device AssocTensor.

        The broadcast-B step of the distributed product: shard row supports
        are disjoint and individually canonical, so the gather is a pure
        re-sort + compaction (:func:`coo_compact`) of the concatenated
        arrays — no ⊕-merge, and crucially no zero-drop: a stored ``0.0``
        (legitimate under min/max-family semirings whose ⊕-identity is
        ±inf) must survive chained products.
        """
        repl = NamedSharding(self.mesh, P())
        rows, cols, vals = (jax.device_put(x.reshape(-1), repl)
                            for x in (self.local.rows, self.local.cols,
                                      self.local.vals))
        r, c, v, nnz = coo_compact(rows, cols, vals, rows != SENT)
        return AssocTensor(r, c, v, nnz, self.local.row_space,
                           self.local.col_space, self.local.val_space)

    def _local_spec(self):
        """Per-shard COO dict + its shard_map PartitionSpec tree."""
        a_dict = {"rows": self.local.rows, "cols": self.local.cols,
                  "vals": self.local.vals, "nnz": self.local.nnz}
        spec = {k: P(*(("data",) + (None,) * (v.ndim - 1)))
                for k, v in a_dict.items()}
        return a_dict, spec

    # -- element-wise (alignment-free: row ranges are disjoint) -----------------
    def _ewise(self, other: "DistAssoc", op: str, semiring) -> "DistAssoc":
        sr = get_semiring(semiring)
        a_dict, _ = self._local_spec()
        b_dict = {"rows": other.local.rows, "cols": other.local.cols,
                  "vals": other.local.vals, "nnz": other.local.nnz}
        go = _ewise_prog(self.mesh, sr, op)
        out = go(a_dict, b_dict)
        new_local = AssocTensor(out["rows"], out["cols"], out["vals"],
                                out["nnz"], self.local.row_space,
                                self.local.col_space, self.local.val_space)
        return DistAssoc(new_local, self.mesh, row_bounds=self.row_bounds)

    @contract(collectives=0, note="shard-local ⊕: disjoint aligned rows")
    def add(self, other, semiring=PLUS_TIMES):
        return self._ewise(other, "add", semiring)

    @contract(collectives=0, note="shard-local ⊗: disjoint aligned rows")
    def mul(self, other, semiring=PLUS_TIMES):
        return self._ewise(other, "mul", semiring)

    def __add__(self, other):
        # thin wrapper over the one-node graph (lazy/eager share one path);
        # expression operands defer to the Node's reflected operator
        if not isinstance(other, DistAssoc):
            return NotImplemented
        return EwiseAdd(Source(self), Source(other)).collect()

    def __mul__(self, other):
        if not isinstance(other, DistAssoc):
            return NotImplemented
        return EwiseMul(Source(self), Source(other)).collect()

    # -- lazy expressions (the deferred pipeline API, repro.core.expr) ----------
    def lazy(self) -> Source:
        """Wrap as a lazy expression Source (see ``Assoc.lazy``)."""
        return Source(self)

    # -- selection (the D4M query surface, sharded) ------------------------------
    def _compiled_selection(self, ij):
        """Compile (row_sel, col_sel) once on host → shard-broadcast forms.

        Shared prologue of ``__getitem__`` and ``__setitem__``: returns
        ``(row_gather, col_gather, bounds, rmask, cmask)`` — the ``[k, 4]``
        rank-box list for the Pallas range kernel (``select.plan_boxes``:
        one box for a contiguous selection, ≤4 OR-composed boxes for a
        multi-interval one) plus membership masks for any scattered axis.
        Dispatch mirrors ``AssocTensor._selection_keep``.
        """
        from .select import compile_selector, plan_boxes

        rc = compile_selector(ij[0], self.local.row_space)
        cc = compile_selector(ij[1], self.local.col_space)
        nr = max(len(self.local.row_space), 1)
        nc = max(len(self.local.col_space), 1)
        boxes, row_gather, col_gather = plan_boxes(rc, cc, nr, nc)
        bounds = jnp.asarray(boxes, jnp.int32)
        rmask = (jnp.asarray(np.pad(rc.mask(), (0, nr - rc.n)))
                 if row_gather else jnp.zeros((1,), bool))
        cmask = (jnp.asarray(np.pad(cc.mask(), (0, nc - cc.n)))
                 if col_gather else jnp.zeros((1,), bool))
        if row_gather and col_gather:
            _bump_dispatch("gather")
        elif len(boxes) > 1:
            _bump_dispatch("multirange")
        elif row_gather or col_gather:
            _bump_dispatch("hybrid")
        else:
            _bump_dispatch("range")
        return row_gather, col_gather, bounds, rmask, cmask

    @contract(collectives=0,
              note="selection is shard-local: compiled boxes/masks broadcast")
    def __getitem__(self, ij) -> "DistAssoc":
        # thin wrapper over the one-node graph (lazy/eager one path)
        i, j = ij
        return Select(Source(self), i, j).collect()

    def _select_eager(self, ij) -> "DistAssoc":
        """D4M selection ``A[row_sel, col_sel]`` on a sharded array.

        The selector compiles **once on host** against the (replicated)
        keyspaces — every selector form the host ``Assoc`` takes works
        here — then executes shard-locally with zero collectives: row
        partitions are disjoint, so each shard masks and compacts its own
        COO triples.  Dispatch mirrors ``AssocTensor._selection_keep``:
        both axes contiguous → the shared Pallas range-mask kernel
        (``repro.kernels.range_extract``); ONE contiguous axis (e.g. a
        single-interval ``Match``/``StartsWith``) → the range kernel for
        that axis plus one membership gather for the other; both scattered
        → two gathers.  Nothing densifies.
        """
        row_gather, col_gather, bounds, rmask, cmask = \
            self._compiled_selection(ij)
        a_dict, _ = self._local_spec()
        go = _select_prog(self.mesh, row_gather, col_gather)
        out = go(a_dict, bounds, rmask, cmask)
        new_local = AssocTensor(out["rows"], out["cols"], out["vals"],
                                out["nnz"], self.local.row_space,
                                self.local.col_space, self.local.val_space)
        return DistAssoc(new_local, self.mesh, row_bounds=self.row_bounds)

    @contract(collectives=0,
              note="scalar assignment is shard-local over stored entries")
    def __setitem__(self, ij, value) -> None:
        """Selector-targeted scalar assignment, sharded (in place).

        The ROADMAP ``DistAssoc.__setitem__`` pushdown, mirroring the
        ``__getitem__`` structure exactly: the selector compiles once on
        host, then each shard overwrites the values of its own *stored*
        entries inside the selection — zero collectives, nothing
        densifies.  Semantics match ``AssocTensor.__setitem__``: numeric
        scalar, support unchanged (inserting new entries is a host-side
        ``from_triples``).
        """
        if (not isinstance(value, (int, float, np.integer, np.floating))
                or isinstance(value, (bool, np.bool_))):
            raise TypeError("DistAssoc __setitem__ takes a numeric scalar")
        if not self.local.numeric:
            raise TypeError("DistAssoc __setitem__ requires numeric values")
        row_gather, col_gather, bounds, rmask, cmask = \
            self._compiled_selection(ij)
        a_dict, _ = self._local_spec()
        go = _setvals_prog(self.mesh, row_gather, col_gather)
        new_vals = go(a_dict, bounds, rmask, cmask, jnp.float32(value))
        self.local = AssocTensor(self.local.rows, self.local.cols, new_vals,
                                 self.local.nnz, self.local.row_space,
                                 self.local.col_space,
                                 self.local.val_space)

    # -- global reductions --------------------------------------------------------
    @contract(collectives=1, note="local segment scatter + one mesh_combine")
    def col_reduce(self, semiring=PLUS_TIMES) -> jnp.ndarray:
        """⊕ over rows per column → dense [n_cols] (one collective)."""
        sr = get_semiring(semiring)
        go = _col_reduce_prog(self.mesh, sr, len(self.local.col_space),
                              self.local.vals.dtype)
        return go(self.local.cols, self.local.vals, self.local.rows)

    @contract(collectives=1, note="disjoint-support concat as one collective")
    def row_reduce(self, semiring=PLUS_TIMES) -> jnp.ndarray:
        """⊕ over cols per row → dense [n_rows] (one collective).

        Row supports are disjoint, so the psum-family combine is a pure
        concatenation of shard partials; reuses the col-reduce program
        with the row ranks as the scatter keys.
        """
        sr = get_semiring(semiring)
        go = _col_reduce_prog(self.mesh, sr, len(self.local.row_space),
                              self.local.vals.dtype)
        return go(self.local.rows, self.local.vals, self.local.rows)

    @contract(collectives=1, note="one psum of per-shard counts")
    def col_degree(self) -> jnp.ndarray:
        """Stored-entry count per column → dense int32 [n_cols] (one psum).

        The Graphulo degree-table idiom: the logical() + column-⊕ fusion
        runs shard-locally (one segment scatter over the shard's triples)
        and the per-shard partial counts merge with a single ``psum``.
        """
        go = _col_degree_prog(self.mesh, len(self.local.col_space))
        return go(self.local.cols, self.local.rows)

    @contract(collectives=1, note="per-shard y rows + one mesh_combine")
    def matmul_dense_vec(self, x: jnp.ndarray, semiring=PLUS_TIMES) -> jnp.ndarray:
        """y = A ⊗.⊕ x for a dense vector over the column keyspace.

        Row partitions are disjoint: every shard produces its own y rows;
        combining is a concatenation expressed as one psum-family
        collective of disjoint supports (the Graphulo pushdown pattern).
        Accumulates in the promoted values/operand dtype rather than
        hardcoded float32.
        """
        sr = get_semiring(semiring)
        dt = jnp.result_type(self.local.vals.dtype, x.dtype)
        go = _matvec_prog(self.mesh, sr, len(self.local.row_space), dt)
        return go(self.local.rows, self.local.cols, self.local.vals, x)

    # -- array multiplication (Graphulo pushdown, sharded) -----------------------
    def _as_replicated_operand(self, other) -> AssocTensor:
        """Coerce the B operand to a replicated device AssocTensor."""
        from .assoc import Assoc
        if isinstance(other, DistAssoc):
            return other.gather_replicated()
        if isinstance(other, AssocTensor):
            return other
        if isinstance(other, Assoc):
            return other.to_tensor()
        raise TypeError(f"cannot multiply DistAssoc by {type(other)!r}")

    def _matmul_setup(self, other) -> "_MatmulSetup":
        """Shared product prologue: logical() strings, align the contraction
        keyspace, and collect the host metadata the distribution cost model
        runs on (exact per-entry product counts, B's sorted contraction
        ranks, B's own partition bounds when it is mesh-resident).

        Semiring-independent — this is the sharded twin of
        ``spgemm._contraction_aligned``: alignment is pure key/rank work.
        """
        a_loc = self.local.logical() if not self.local.numeric else self.local
        b_resident = isinstance(other, DistAssoc) and other.mesh == self.mesh
        b_repl = None
        if b_resident:
            b_loc = (other.local.logical() if not other.local.numeric
                     else other.local)
            b_row_space, b_col_space = b_loc.row_space, b_loc.col_space
        else:
            b_t = self._as_replicated_operand(other)
            b_t = b_t.logical() if not b_t.numeric else b_t
            b_row_space, b_col_space = b_t.row_space, b_t.col_space
        ks, a_map, b_map = a_loc.col_space.union(b_row_space)
        b_map = np.asarray(b_map, np.int32)

        # device: rerank the sharded A cols onto the contraction space
        ok = a_loc.rows != SENT
        cm = jnp.asarray(a_map) if len(a_map) else jnp.zeros(1, jnp.int32)
        # the gather keeps A's row sharding: a mesh with Explicit axes
        # (jax.make_mesh's default) cannot infer it from a replicated table
        a_cols = jnp.where(
            ok, cm.at[jnp.clip(a_loc.cols, 0, cm.shape[0] - 1)].get(
                out_sharding=a_loc.cols.sharding), SENT)
        a_rows_h = np.asarray(a_loc.rows)
        a_cols_h = np.asarray(a_cols)

        # host B triples in the merged contraction space, sorted by row:
        # shard supports are disjoint and ranges ordered, and the union
        # rank maps are monotone, so ravel order IS sorted order
        a2a_bounds = None
        if b_resident:
            rws = np.asarray(b_loc.rows).ravel()
            keep = rws != int(SENT)
            rh = rws[keep]
            b_rows_h = b_map[rh] if len(b_map) else rh
            b_cols_h = np.asarray(b_loc.cols).ravel()[keep]
            b_vals_h = np.asarray(b_loc.vals).ravel()[keep]
            rb = np.asarray(other.row_bounds, np.int64)
            if len(b_map):
                a2a_bounds = np.where(
                    rb < len(b_map),
                    b_map.astype(np.int64)[np.clip(rb, 0, len(b_map) - 1)],
                    len(ks))
            else:
                a2a_bounds = np.zeros_like(rb)
        else:
            b_repl = b_t.reranked(ks, b_col_space, b_map,
                                  np.arange(len(b_col_space), dtype=np.int32))
            rws = np.asarray(b_repl.rows)
            keep = rws != int(SENT)
            b_rows_h = rws[keep]
            b_cols_h = np.asarray(b_repl.cols)[keep]
            b_vals_h = np.asarray(b_repl.vals)[keep]

        # exact per-entry product counts (host): two searchsorteds over
        # B's contraction ranks — the cost model's only data dependence
        lo = np.searchsorted(b_rows_h, a_cols_h.ravel(), side="left")
        hi = np.searchsorted(b_rows_h, a_cols_h.ravel(), side="right")
        counts = np.where(a_rows_h.ravel() != int(SENT),
                          hi - lo, 0).reshape(a_rows_h.shape)
        return _MatmulSetup(a_loc=a_loc, a_cols=a_cols, a_rows_h=a_rows_h,
                            a_cols_h=a_cols_h, counts=counts, ks=ks,
                            b_col_space=b_col_space, b_resident=b_resident,
                            b_repl=b_repl,
                            b_other=other if b_resident else None,
                            b_map=b_map, b_rows_h=b_rows_h,
                            b_cols_h=b_cols_h, b_vals_h=b_vals_h,
                            a2a_bounds=a2a_bounds)

    def _b_replicated(self, st: "_MatmulSetup") -> AssocTensor:
        """Replicated reranked B for the replicate strategy (built lazily:
        the sharded strategies never pay for it)."""
        if st.b_repl is None:
            st.b_repl = AssocTensor(
                jnp.asarray(st.b_rows_h, jnp.int32),
                jnp.asarray(st.b_cols_h, jnp.int32),
                jnp.asarray(st.b_vals_h, jnp.float32),
                jnp.int32(len(st.b_rows_h)), st.ks, st.b_col_space, None)
        return st.b_repl

    def _put_sharded(self, tree):
        return jax.tree.map(
            lambda x: jax.device_put(
                jnp.asarray(x),
                NamedSharding(self.mesh,
                              P(*(("data",) + (None,) * (x.ndim - 1))))),
            tree)

    def _a2a_b_operand(self, st: "_MatmulSetup", sr):
        """The sharded-B operand + row rank map for the all_to_all programs.

        A mesh-resident B is reused IN PLACE (its row partition is already
        a contraction partition; the program reranks through ``bm``); any
        other B stages once, split by equal contraction ranges — the same
        bounds the cost model's product table used.
        """
        n_shards = self.mesh.shape["data"]
        if st.b_resident:
            loc = st.b_other.local
            b_dict = {"rows": loc.rows, "cols": loc.cols,
                      "vals": loc.vals.astype(jnp.float32)}
            bm = (jnp.asarray(st.b_map) if len(st.b_map)
                  else jnp.zeros(1, jnp.int32))
            return b_dict, bm
        k = len(st.ks)
        bnds = np.linspace(0, k, n_shards + 1).astype(np.int64)
        idx = np.searchsorted(st.b_rows_h, bnds)
        cap = int(max(8, _round_up(int(np.diff(idx).max(initial=0)) or 1, 8)))
        rows = np.full((n_shards, cap), int(SENT), np.int32)
        cols = np.full((n_shards, cap), int(SENT), np.int32)
        vals = np.full((n_shards, cap), sr.zero, np.float32)
        for s in range(n_shards):
            seg = slice(int(idx[s]), int(idx[s + 1]))
            length = seg.stop - seg.start
            rows[s, :length] = st.b_rows_h[seg]
            cols[s, :length] = st.b_cols_h[seg]
            vals[s, :length] = st.b_vals_h[seg]
        b_dict = self._put_sharded({"rows": rows, "cols": cols,
                                    "vals": vals})
        bm = jnp.arange(max(k, 1), dtype=jnp.int32)  # already merged-space
        return b_dict, bm

    def _stage_b_blocks(self, st: "_MatmulSetup", sr, pr: int, pc: int,
                        block_cap: int):
        """Stage B's contraction blocks for the 2D grid: block ``p`` lands
        on every shard ``(g, p)`` (``pr``-fold staging replication — the
        cost model's ``pr·nnz(B)`` term), SENT/zero-padded to the uniform
        ``block_cap`` so whole blocks ring-shift as one packed array."""
        k = len(st.ks)
        n_shards = pr * pc
        bnds = np.linspace(0, k, pc + 1).astype(np.int64)
        idx = np.searchsorted(st.b_rows_h, bnds)
        rows = np.full((n_shards, block_cap), int(SENT), np.int32)
        cols = np.full((n_shards, block_cap), int(SENT), np.int32)
        vals = np.full((n_shards, block_cap), sr.zero, np.float32)
        for s in range(n_shards):
            blk = s % pc
            seg = slice(int(idx[blk]), int(idx[blk + 1]))
            length = seg.stop - seg.start
            rows[s, :length] = st.b_rows_h[seg]
            cols[s, :length] = st.b_cols_h[seg]
            vals[s, :length] = st.b_vals_h[seg]
        return self._put_sharded({"rows": rows, "cols": cols, "vals": vals})

    def _estimated_out_cap(self, st: "_MatmulSetup", plan) -> int:
        """Per-shard output capacity from shard-local structure.

        The replicate expand size (total products of the worst shard) is a
        correct but hub-pessimal bound; past a threshold it is worth a host
        pass of :func:`repro.core.spgemm.estimate_out_nnz` over each
        shard's own blocks — the sketch can in principle under-estimate,
        so the saturation ``RuntimeWarning`` downstream stays the safety
        net.
        """
        from .spgemm import estimate_out_nnz, plan_matmul
        expand = plan.expands["replicate"]
        if expand <= (1 << 12):
            return expand
        m = len(self.local.row_space)
        k, n = len(st.ks), len(st.b_col_space)
        best = 0
        for s in range(st.a_rows_h.shape[0]):
            mask = st.a_rows_h[s] != int(SENT)
            if not mask.any():
                continue
            p = plan_matmul(st.a_rows_h[s][mask], st.a_cols_h[s][mask],
                            st.b_rows_h, st.b_cols_h, m, k, n, impl="bsr")
            best = max(best, estimate_out_nnz(p))
        return int(min(expand, max(8, _round_up(best or 1, 8))))

    def _matmul_finish(self, out, st: "_MatmulSetup", out_cap: int
                       ) -> "DistAssoc":
        """Shared epilogue: overflow surfacing + result assembly (row
        partition unchanged — every strategy emits row-sharded output)."""
        true_nnz = np.asarray(out.pop("true_nnz"))
        overflowed = bool((true_nnz > out_cap).any())
        if overflowed:
            import warnings
            worst = int(true_nnz.max())
            warnings.warn(
                f"DistAssoc.matmul: a shard produced {worst} entries but "
                f"out_capacity_per_shard is {out_cap}; excess entries were "
                f"dropped — pass a larger out_capacity_per_shard",
                RuntimeWarning, stacklevel=3)
        new_local = AssocTensor(out["rows"], out["cols"], out["vals"],
                                out["nnz"], self.local.row_space,
                                st.b_col_space, None)
        result = DistAssoc(new_local, self.mesh, row_bounds=self.row_bounds)
        result.overflow = overflowed
        return result

    @contract(collectives=0,
              note="replicate strategy: shard-local expand-join, zero "
                   "collectives; sharded-B strategies carry their own "
                   "contracts (dist.matmul_all_to_all / dist.matmul_2d)")
    def matmul(self, other, semiring=PLUS_TIMES, *, impl: str = "auto_dist",
               kernel_impl: str = "auto",
               grid: Optional[Tuple[int, int]] = None,
               out_capacity_per_shard: Optional[int] = None) -> "DistAssoc":
        """Array multiplication ``A ⊗.⊕ B``, communication-strategy-tuned.

        ``other`` may be an ``AssocTensor``, host ``Assoc``, or another
        ``DistAssoc`` (mesh-resident B is reused in place on the sharded
        paths).  ``impl`` picks the communication strategy:

        ``"auto_dist"`` (default)
            host cost model (:func:`repro.core.spgemm.plan_dist_matmul`)
            chooses per multiply from exact product counts; the choice
            lands in ``PLAN_STATS["dist_replicate"/"dist_all_to_all"/
            "dist_2d"]``.
        ``"replicate"``
            broadcast-B, shard-local product, zero collectives (the
            Graphulo tablet-server pattern).
        ``"all_to_all"``
            B sharded by contraction range; one packed ``all_to_all`` of
            partial products.
        ``"2d"``
            SUMMA-style ``(pr, pc)`` grid (``grid=`` forces it), ``pc−1``
            ring ``ppermute`` shifts of B blocks; A never moves.
        ``"auto"`` / ``"coo"`` / ``"bsr"`` (legacy spelling)
            replicate strategy with that shard-local compute: ``coo`` the
            expand-join program, ``bsr`` the tiled pair-list program
            (``kernel_impl`` forwards to the kernel dispatch), ``auto``
            the ``_BSR_AUTO_EXPAND`` crossover.
        """
        if impl not in ("auto_dist", "replicate", "all_to_all", "2d",
                        "auto", "coo", "bsr"):
            raise ValueError(
                f"unknown DistAssoc matmul impl {impl!r}; expected "
                f"auto_dist/replicate/all_to_all/2d or legacy auto/coo/bsr")
        sr = get_semiring(semiring)
        st = self._matmul_setup(other)
        n_shards = self.mesh.shape["data"]
        plan = plan_dist_matmul(st.a_rows_h, st.a_cols_h, st.counts,
                                st.b_rows_h, len(st.ks), n_shards,
                                b_resident=st.b_resident, grid=grid,
                                a2a_bounds=st.a2a_bounds)
        if impl == "auto_dist":
            strategy, local = plan.strategy, "auto"
        elif impl in ("replicate", "all_to_all", "2d"):
            strategy, local = impl, "auto"
        else:  # legacy spellings pin the replicate strategy's local compute
            strategy, local = "replicate", impl
        from .plan import _bump  # lazy: plan.py imports this module
        _bump(f"dist_{strategy}")
        out_cap = out_capacity_per_shard or self._estimated_out_cap(st, plan)

        if strategy == "all_to_all":
            b_dict, bm = self._a2a_b_operand(st, sr)
            go = _matmul_a2a_prog(self.mesh, sr, plan.expands["all_to_all"],
                                  plan.bucket_cap, out_cap, n_shards)
            out = go(st.a_rows_h, st.a_cols_h, np.asarray(st.a_loc.vals),
                     b_dict, bm, jnp.asarray(self.row_bounds, jnp.int32))
            return self._matmul_finish(out, st, out_cap)
        if strategy == "2d":
            pr, pc = plan.grid
            b_dict = self._stage_b_blocks(st, sr, pr, pc, plan.block_cap)
            a_dict = {"rows": st.a_loc.rows, "cols": st.a_cols,
                      "vals": st.a_loc.vals}
            go = _matmul_ring_prog(self.mesh, sr, pr, pc,
                                   plan.expands["2d"], out_cap)
            out = go(a_dict, b_dict)
            return self._matmul_finish(out, st, out_cap)

        # replicate strategy: coo program vs tiled pair-list program
        expand = plan.expands["replicate"]
        if local == "bsr" or (local == "auto" and expand >= _BSR_AUTO_EXPAND):
            return self._matmul_bsr(st, sr, kernel_impl=kernel_impl,
                                    out_cap=out_cap)
        b = self._b_replicated(st)
        a_dict = {"rows": st.a_loc.rows, "cols": st.a_cols,
                  "vals": st.a_loc.vals}
        go = _matmul_prog(self.mesh, sr, expand, out_cap)
        out = go(a_dict, b.rows, b.cols, b.vals)
        return self._matmul_finish(out, st, out_cap)

    def _matmul_bsr(self, st: "_MatmulSetup", sr, *,
                    kernel_impl: str = "auto", out_cap: int) -> "DistAssoc":
        """Replicate-strategy tiled product as ONE cached shard_map program.

        The per-shard host planning survives (tile-pair lists are cheap
        numpy over rank triples), but execution is a single dispatch of
        :func:`_matmul_bsr_prog` for the whole mesh instead of the old
        eager per-shard planner+kernel loop.  Per-shard plans pad to
        uniform static sizes: invalid A entries scatter out of bounds
        (dropped), dummy pairs accumulate into an extra C slot (discarded),
        padded C blocks land past ``(m, n)`` (filtered).  B's entry→tile
        lists depend only on B's triples, so its packed tiles build once
        and broadcast.
        """
        from .spgemm import pack_tiles, plan_matmul
        n_shards = self.mesh.shape["data"]
        m = len(self.local.row_space)
        k, n = len(st.ks), len(st.b_col_space)
        plans = []
        for s in range(n_shards):
            mask = st.a_rows_h[s] != int(SENT)
            plans.append(plan_matmul(st.a_rows_h[s][mask],
                                     st.a_cols_h[s][mask],
                                     st.b_rows_h, st.b_cols_h,
                                     m, k, n, impl="bsr"))
        n_a = max(max(len(p.a_blocks) for p in plans), 1)
        n_c = max(max(len(p.c_blocks) for p in plans), 1)
        n_pairs = max(max(len(p.pair_a) for p in plans), 1)
        cap_a = st.a_rows_h.shape[1]

        tof = np.full((n_shards, cap_a), n_a, np.int32)   # OOB → dropped
        lr = np.zeros((n_shards, cap_a), np.int32)
        lc = np.zeros((n_shards, cap_a), np.int32)
        pa = np.zeros((n_shards, n_pairs), np.int32)
        pb = np.zeros((n_shards, n_pairs), np.int32)
        pcc = np.full((n_shards, n_pairs), n_c, np.int32)  # dummy C slot
        cblk = np.full((n_shards, n_c, 2), 1 << 20, np.int32)
        for s, p in enumerate(plans):
            ne, np_, nc_ = len(p.a_tile_of), len(p.pair_a), len(p.c_blocks)
            tof[s, :ne] = p.a_tile_of
            lr[s, :ne] = p.a_lr
            lc[s, :ne] = p.a_lc
            pa[s, :np_] = p.pair_a
            pb[s, :np_] = p.pair_b
            pcc[s, :np_] = p.pair_c
            cblk[s, :nc_] = p.c_blocks
        b_tiles = pack_tiles(jnp.asarray(st.b_vals_h, jnp.float32),
                             plans[0].b_tile_of, plans[0].b_lr,
                             plans[0].b_lc, len(plans[0].b_blocks),
                             TILE, TILE, sr.zero)
        sharded = self._put_sharded({"av": np.asarray(st.a_loc.vals),
                                     "tof": tof, "lr": lr, "lc": lc,
                                     "pa": pa, "pb": pb, "pcc": pcc,
                                     "cblk": cblk})
        go = _matmul_bsr_prog(self.mesh, sr, n_a, n_c, m, n, out_cap,
                              kernel_impl)
        out = go(sharded["av"], sharded["tof"], sharded["lr"],
                 sharded["lc"], b_tiles, sharded["pa"], sharded["pb"],
                 sharded["pcc"], sharded["cblk"])
        return self._matmul_finish(out, st, out_cap)

    def __matmul__(self, other):
        # thin wrapper over the one-node graph (see __add__)
        if isinstance(other, (DistAssoc, AssocTensor)) or hasattr(other, "adj"):
            return MatMul(Source(self), Source(other)).collect()
        return NotImplemented

    @contract(collectives=1, note="fused epilogue: exactly one psum-family op")
    def matmul_reduce(self, other, axis: int = 1, semiring=PLUS_TIMES, *,
                      impl: str = "auto_dist") -> jnp.ndarray:
        """Fused ``⊕-reduce(A ⊗.⊕ B, axis)`` — one collective, no C.

        Shards ⊕-fold products straight into a dense vector (no merge, no
        sort — ⊕ over every product per row/col IS the answer) and the
        partials combine with exactly one psum-family collective.
        ``axis=1`` → vector over the row keyspace; ``axis=0`` → vector
        over B's col keyspace.

        ``impl`` follows :meth:`matmul`: ``"replicate"`` broadcasts B and
        each shard folds its own rows' products; ``"all_to_all"`` keeps B
        sharded by contraction range — each shard folds the products of
        ITS block, and the same single collective that merges the partials
        replaces the partial-product exchange, so the sharded variant is
        no chattier.  ``"auto_dist"`` compares the two staging costs (the
        2D path has nothing to add here — there is no C to ring-shift
        for).
        """
        assert axis in (0, 1), axis
        if impl not in ("auto_dist", "replicate", "all_to_all"):
            raise ValueError(
                f"unknown matmul_reduce impl {impl!r}; expected "
                f"auto_dist/replicate/all_to_all")
        sr = get_semiring(semiring)
        st = self._matmul_setup(other)
        n_shards = self.mesh.shape["data"]
        plan = plan_dist_matmul(st.a_rows_h, st.a_cols_h, st.counts,
                                st.b_rows_h, len(st.ks), n_shards,
                                b_resident=st.b_resident,
                                a2a_bounds=st.a2a_bounds)
        if impl == "auto_dist":
            strategy = ("all_to_all"
                        if n_shards > 1 and (plan.costs["all_to_all"]
                                             < plan.costs["replicate"])
                        else "replicate")
        else:
            strategy = impl
        from .plan import _bump  # lazy: plan.py imports this module
        _bump(f"dist_{strategy}")
        n_out = (len(self.local.row_space) if axis == 1
                 else len(st.b_col_space))

        if strategy == "all_to_all":
            b_dict, bm = self._a2a_b_operand(st, sr)
            go = _matmul_reduce_a2a_prog(self.mesh, sr,
                                         plan.expands["all_to_all"],
                                         n_out, axis)
            return go(st.a_rows_h, st.a_cols_h, np.asarray(st.a_loc.vals),
                      b_dict, bm)
        b = self._b_replicated(st)
        a_dict = {"rows": st.a_loc.rows, "cols": st.a_cols,
                  "vals": st.a_loc.vals}
        go = _matmul_reduce_prog(self.mesh, sr, plan.expands["replicate"],
                                 n_out, axis)
        return go(a_dict, b.rows, b.cols, b.vals)

    @contract(collectives=1, note="fused reduce= epilogue (AA^T)")
    def sqout(self, semiring=PLUS_TIMES, reduce: Optional[int] = None):
        """AAᵀ — the row-key graph, sharded; ``reduce=0/1`` runs the fused
        epilogue instead (dense vector over the row keyspace, one
        collective)."""
        t = self.gather_replicated().transpose()
        if reduce is None:
            return self.matmul(t, semiring)
        return self.matmul_reduce(t, reduce, semiring)

    @contract(collectives=1, note="fused reduce= epilogue (A^T A)")
    def sqin(self, semiring=PLUS_TIMES, reduce: Optional[int] = None):
        """AᵀA — the correlation idiom.  The transpose breaks the row
        partition, so this runs as gathered-Aᵀ × broadcast-A from the
        transposed side: exact, but re-sharding the result is the caller's
        choice; ``reduce=0/1`` for the fused vector."""
        me = self.gather_replicated()
        t = me.transpose()
        if reduce is None:
            return t.matmul(me, semiring)
        return t.matmul_reduce(me, reduce, semiring)
