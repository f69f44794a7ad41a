"""Contract probes: lower each decorated API's compiled programs.

A probe is a zero-argument callable registered under a contract's name.
It yields, per compiled program behind that entry point, a
``(label, hlo_text)`` pair — the *pre-optimization* HLO of the program,
obtained by ``.lower(...)`` over ``jax.ShapeDtypeStruct`` arguments and
(for the sharded layer) an 8-way ``AbstractMesh`` — plus optional
:class:`~repro.analysis.contracts.RetraceAudit` items asserting the
entry's trace cache doesn't grow on structurally identical repeat
calls.  Nothing here needs devices or a TPU: no program executes except
the (tiny, CPU) retrace-audit calls.

Probe shapes are chosen so the densification detector has teeth: COO
capacities are small (64–512 triples) while keyspaces are large (4096
ranks per axis), so a program that builds anything ``O(nr·nc)`` jumps
~100× above the ``8 × max_input`` budget.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Iterable, List

from .contracts import RetraceAudit
from .hlo_contracts import lower_hlo

#: contract name -> probe
PROBES: Dict[str, Callable[[], Iterable]] = {}

# probe geometry: nnz capacity per (shard|tensor) and keyspace extent.
_CAP = 64
_NKEYS = 4096
_NSHARDS = 8


def probe_for(name: str):
    def deco(fn):
        PROBES[name] = fn
        return fn
    return deco


# --------------------------------------------------------------------------
# Shared fixtures (built lazily, cached: probes import core on first use)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _abstract_mesh():
    from jax.sharding import AbstractMesh
    return AbstractMesh((_NSHARDS,), ("data",))


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _coo_dict_sds(cap: int = _CAP):
    """ShapeDtypeStruct tree of one sharded COO local dict."""
    import jax.numpy as jnp
    return {"rows": _sds((_NSHARDS, cap), jnp.int32),
            "cols": _sds((_NSHARDS, cap), jnp.int32),
            "vals": _sds((_NSHARDS, cap), jnp.float32),
            "nnz": _sds((_NSHARDS,), jnp.int32)}


def _b_triples_sds(nnz: int = _CAP):
    import jax.numpy as jnp
    return (_sds((nnz,), jnp.int32), _sds((nnz,), jnp.int32),
            _sds((nnz,), jnp.float32))


def _sel_args_sds(row_gather: bool, col_gather: bool, k_boxes: int = 1):
    """(bounds, rmask, cmask) abstract args matching _compiled_selection."""
    import jax.numpy as jnp
    bounds = _sds((k_boxes, 4), jnp.int32)
    rmask = _sds((_NKEYS if row_gather else 1,), jnp.bool_)
    cmask = _sds((_NKEYS if col_gather else 1,), jnp.bool_)
    return bounds, rmask, cmask


@functools.lru_cache(maxsize=1)
def _device_tensor():
    """A concrete small-capacity AssocTensor over large keyspaces.

    Eager-layer probes need a real pytree (its keyspaces are static aux
    consumed at trace time); 64 stored triples over 4096×4096 key ranks
    keep the build trivial while making densification unmissable.
    """
    import numpy as np
    from repro.core.assoc_tensor import AssocTensor
    from repro.core.keyspace import KeySpace

    all_keys = np.array([f"k{i:04d}" for i in range(_NKEYS)])
    space = KeySpace(all_keys)
    idx = np.arange(_CAP) * (_NKEYS // _CAP)
    return AssocTensor.from_triples(
        all_keys[idx], all_keys[(idx * 7) % _NKEYS],
        np.arange(_CAP, dtype=np.float32) + 1.0,
        capacity=_CAP, row_space=space, col_space=space)


def _selector_kinds():
    """One selector pair per device dispatch kind (range/multirange/
    hybrid/gather), matching ``select.plan_boxes``'s four paths."""
    from repro.core.select import All, Keys, Range

    t = _device_tensor()
    keys = t.row_space.keys
    scattered = list(keys[::5][:40])       # >4 interval runs -> gather
    tworuns = list(keys[10:20]) + list(keys[100:110])   # 2 runs -> boxes
    return [
        ("range", (Range(keys[4], keys[2000]), All())),
        ("multirange", (Keys(tworuns), All())),
        ("hybrid", (Range(keys[4], keys[2000]), Keys(scattered))),
        ("gather", (Keys(scattered), Keys(scattered))),
    ]


# --------------------------------------------------------------------------
# AssocTensor (single device)
# --------------------------------------------------------------------------

@probe_for("AssocTensor.__getitem__")
def _probe_tensor_getitem():
    import jax

    t = _device_tensor()
    for label, sel in _selector_kinds():
        yield label, lower_hlo(jax.jit(lambda x, s=sel: x._select_eager(s)), t)


@probe_for("AssocTensor.__setitem__")
def _probe_tensor_setitem():
    import jax
    import jax.numpy as jnp

    t = _device_tensor()

    def assign(x, val, s):
        # the functional core of __setitem__ (which mutates the wrapper)
        keep = x._selection_keep(s)
        return jnp.where(keep, val, x.vals)

    for label, sel in _selector_kinds():
        yield label, lower_hlo(jax.jit(lambda x, v, s=sel: assign(x, v, s)),
                               t, jnp.float32(0))


# --------------------------------------------------------------------------
# spgemm kernel programs (single device; the host-driven planner around
# them is eager by design, so the compiled contract lives in the kernels)
# --------------------------------------------------------------------------

def _pairlist_args_sds(n_pairs: int = 16, n_a: int = 8, n_b: int = 8):
    import jax.numpy as jnp
    return (_sds((n_a, 128, 128), jnp.float32),
            _sds((n_b, 128, 128), jnp.float32),
            _sds((n_pairs,), jnp.int32), _sds((n_pairs,), jnp.int32),
            _sds((n_pairs,), jnp.int32))


@probe_for("spgemm.matmul")
def _probe_spgemm_matmul():
    from repro.kernels.bsr_spgemm import ops

    a, b, pa, pb, pc = _pairlist_args_sds()
    yield "bsr_pairlist", lower_hlo(
        ops.bsr_pairlist, a, b, pa, pb, pc, n_c=4,
        semiring="plus_times", impl="ref")

    def first():
        _pairlist_call(ops)

    def again():
        _pairlist_call(ops)

    yield RetraceAudit(label="bsr_pairlist-jit", first=first, again=again,
                       size=lambda: ops.bsr_pairlist._cache_size())


def _pairlist_call(ops):
    import jax.numpy as jnp
    a = jnp.zeros((2, 128, 128), jnp.float32)
    b = jnp.zeros((2, 128, 128), jnp.float32)
    p = jnp.zeros((2,), jnp.int32)
    ops.bsr_pairlist(a, b, p, p, p, n_c=1, semiring="plus_times",
                     impl="ref").block_until_ready()


@probe_for("spgemm.matmul_reduce")
def _probe_spgemm_matmul_reduce():
    from repro.kernels.bsr_spgemm import ops

    a, b, pa, pb, po = _pairlist_args_sds()
    for axis in (1, 0):
        yield f"bsr_pairlist_reduce-axis{axis}", lower_hlo(
            ops.bsr_pairlist_reduce, a, b, pa, pb, po, n_o=4,
            axis=axis, semiring="plus_times", impl="ref")


# --------------------------------------------------------------------------
# DistAssoc (8-way AbstractMesh: shard_map programs lower with no devices)
# --------------------------------------------------------------------------

def _plus_times():
    from repro.core.semiring import PLUS_TIMES, get_semiring
    return get_semiring(PLUS_TIMES)


@probe_for("DistAssoc.__getitem__")
def _probe_dist_getitem():
    from repro.core.dist_assoc import _select_prog

    mesh = _abstract_mesh()
    a = _coo_dict_sds()
    for label, (rg, cg, k) in [("range", (False, False, 1)),
                               ("multirange", (False, False, 3)),
                               ("hybrid", (False, True, 1)),
                               ("gather", (True, True, 1))]:
        prog = _select_prog(mesh, rg, cg)
        yield label, lower_hlo(prog, a, *_sel_args_sds(rg, cg, k))

    def run():
        _select_prog(mesh, False, False)

    yield RetraceAudit(label="select-prog-cache", first=run, again=run,
                       size=lambda: _select_prog.cache_info().currsize)


@probe_for("DistAssoc.__setitem__")
def _probe_dist_setitem():
    import jax.numpy as jnp
    from repro.core.dist_assoc import _setvals_prog

    mesh = _abstract_mesh()
    a = _coo_dict_sds()
    for label, (rg, cg) in [("range", (False, False)),
                            ("gather", (True, True))]:
        prog = _setvals_prog(mesh, rg, cg)
        yield label, lower_hlo(prog, a, *_sel_args_sds(rg, cg),
                               _sds((), jnp.float32))

    def run():
        _setvals_prog(mesh, False, False)

    yield RetraceAudit(label="setvals-prog-cache", first=run, again=run,
                       size=lambda: _setvals_prog.cache_info().currsize)


@probe_for("DistAssoc.add")
def _probe_dist_add():
    from repro.core.dist_assoc import _ewise_prog

    mesh = _abstract_mesh()
    a = _coo_dict_sds()
    yield "ewise-add", lower_hlo(_ewise_prog(mesh, _plus_times(), "add"),
                                 a, a)


@probe_for("DistAssoc.mul")
def _probe_dist_mul():
    from repro.core.dist_assoc import _ewise_prog

    mesh = _abstract_mesh()
    a = _coo_dict_sds()
    yield "ewise-mul", lower_hlo(_ewise_prog(mesh, _plus_times(), "mul"),
                                 a, a)


@probe_for("DistAssoc.matmul")
def _probe_dist_matmul():
    from repro.core.dist_assoc import _matmul_prog

    mesh = _abstract_mesh()
    a = {k: v for k, v in _coo_dict_sds().items() if k != "nnz"}
    prog = _matmul_prog(mesh, _plus_times(), 256, 256)
    yield "coo-expand-join", lower_hlo(prog, a, *_b_triples_sds())

    def run():
        _matmul_prog(mesh, _plus_times(), 256, 256)

    yield RetraceAudit(label="matmul-prog-cache", first=run, again=run,
                       size=lambda: _matmul_prog.cache_info().currsize)


@probe_for("DistAssoc.matmul_reduce")
def _probe_dist_matmul_reduce():
    from repro.core.dist_assoc import _matmul_reduce_prog

    mesh = _abstract_mesh()
    a = {k: v for k, v in _coo_dict_sds().items() if k != "nnz"}
    for axis in (1, 0):
        prog = _matmul_reduce_prog(mesh, _plus_times(), 256, _NKEYS, axis)
        yield f"axis{axis}", lower_hlo(prog, a, *_b_triples_sds())


def _probe_reduce_epilogue():
    # sqin/sqout's collective claim IS the fused matmul_reduce program
    # (reduce=None delegates to matmul, checked under its own contract)
    from repro.core.dist_assoc import _matmul_reduce_prog

    mesh = _abstract_mesh()
    a = {k: v for k, v in _coo_dict_sds().items() if k != "nnz"}
    prog = _matmul_reduce_prog(mesh, _plus_times(), 256, _NKEYS, 1)
    yield "reduce-epilogue", lower_hlo(prog, a, *_b_triples_sds())


PROBES["DistAssoc.sqin"] = _probe_reduce_epilogue
PROBES["DistAssoc.sqout"] = _probe_reduce_epilogue


@probe_for("DistAssoc.col_reduce")
def _probe_dist_col_reduce():
    import jax.numpy as jnp
    from repro.core.dist_assoc import _col_reduce_prog

    mesh = _abstract_mesh()
    prog = _col_reduce_prog(mesh, _plus_times(), _NKEYS, jnp.float32)
    yield "col-reduce", lower_hlo(prog, _sds((_NSHARDS, _CAP), jnp.int32),
                                  _sds((_NSHARDS, _CAP), jnp.float32),
                                  _sds((_NSHARDS, _CAP), jnp.int32))


@probe_for("DistAssoc.row_reduce")
def _probe_dist_row_reduce():
    # same compiled program as col_reduce, keyed by the row ranks
    yield from _probe_dist_col_reduce()


@probe_for("DistAssoc.col_degree")
def _probe_dist_col_degree():
    import jax.numpy as jnp
    from repro.core.dist_assoc import _col_degree_prog

    mesh = _abstract_mesh()
    prog = _col_degree_prog(mesh, _NKEYS)
    yield "col-degree", lower_hlo(prog, _sds((_NSHARDS, _CAP), jnp.int32),
                                  _sds((_NSHARDS, _CAP), jnp.int32))


# --------------------------------------------------------------------------
# Serve path: the server's execution entry point dispatches the same
# compiled programs as the eager layers, so its contract is checked over
# the shard-local programs a query mix reaches — selection (range +
# gather dispatch kinds), ewise ⊕, and the replicated-B matmul of a hot
# `A[sel, :] @ B` query.  (Fused matmul-*reduce* carries its one
# legitimate all-reduce and is budgeted under DistAssoc.matmul_reduce;
# the serve contract asserts the serve layer itself ADDS no collective.)
# --------------------------------------------------------------------------

@probe_for("serve.execute")
def _probe_serve_execute():
    from repro.core.dist_assoc import (_ewise_prog, _matmul_prog,
                                       _select_prog)

    mesh = _abstract_mesh()
    a = _coo_dict_sds()
    for label, (rg, cg, k) in [("select-range", (False, False, 1)),
                               ("select-gather", (True, True, 1))]:
        prog = _select_prog(mesh, rg, cg)
        yield label, lower_hlo(prog, a, *_sel_args_sds(rg, cg, k))
    yield "ewise-add", lower_hlo(_ewise_prog(mesh, _plus_times(), "add"),
                                 a, a)
    a_mm = {k: v for k, v in a.items() if k != "nnz"}
    prog = _matmul_prog(mesh, _plus_times(), 256, 256)
    yield "matmul", lower_hlo(prog, a_mm, *_b_triples_sds())

    def run():
        _select_prog(mesh, False, False)

    # repeated identical serve queries must not retrace the dispatch
    yield RetraceAudit(label="serve-repeat-query", first=run, again=run,
                       size=lambda: _select_prog.cache_info().currsize)


# --------------------------------------------------------------------------
# Sharded-B distribution strategies (exact collective budgets: the cost
# model may only ever choose between programs that are provably no
# chattier than declared — replicate 0, all_to_all 1, 2D pc−1)
# --------------------------------------------------------------------------

def _a2a_args_sds():
    import jax.numpy as jnp
    ar = _sds((_NSHARDS, _CAP), jnp.int32)
    av = _sds((_NSHARDS, _CAP), jnp.float32)
    b = {k: v for k, v in _coo_dict_sds().items() if k != "nnz"}
    bm = _sds((_NKEYS,), jnp.int32)
    return ar, av, b, bm


@probe_for("dist.matmul_all_to_all")
def _probe_dist_matmul_a2a():
    import jax.numpy as jnp
    from repro.core.dist_assoc import _matmul_a2a_prog

    mesh = _abstract_mesh()
    ar, av, b, bm = _a2a_args_sds()
    prog = _matmul_a2a_prog(mesh, _plus_times(), 256, 64, 256, _NSHARDS)
    yield "a2a-exchange", lower_hlo(prog, ar, ar, av, b, bm,
                                    _sds((_NSHARDS + 1,), jnp.int32))

    def run():
        _matmul_a2a_prog(mesh, _plus_times(), 256, 64, 256, _NSHARDS)

    yield RetraceAudit(label="a2a-prog-cache", first=run, again=run,
                       size=lambda: _matmul_a2a_prog.cache_info().currsize)


@probe_for("dist.matmul_2d")
def _probe_dist_matmul_2d():
    from repro.core.dist_assoc import _matmul_ring_prog

    mesh = _abstract_mesh()
    a = {k: v for k, v in _coo_dict_sds().items() if k != "nnz"}
    # 2×4 grid over the 8-shard mesh: exactly pc−1 = 3 ring ppermutes
    prog = _matmul_ring_prog(mesh, _plus_times(), 2, 4, 256, 256)
    yield "ring-2x4", lower_hlo(prog, a, a)

    def run():
        _matmul_ring_prog(mesh, _plus_times(), 2, 4, 256, 256)

    yield RetraceAudit(label="ring-prog-cache", first=run, again=run,
                       size=lambda: _matmul_ring_prog.cache_info().currsize)


@probe_for("dist.matmul_reduce_all_to_all")
def _probe_dist_matmul_reduce_a2a():
    from repro.core.dist_assoc import _matmul_reduce_a2a_prog

    mesh = _abstract_mesh()
    ar, av, b, bm = _a2a_args_sds()
    for axis in (1, 0):
        prog = _matmul_reduce_a2a_prog(mesh, _plus_times(), 256, _NKEYS,
                                       axis)
        yield f"axis{axis}", lower_hlo(prog, ar, ar, av, b, bm)


@probe_for("dist.matmul_bsr")
def _probe_dist_matmul_bsr():
    import jax.numpy as jnp
    from repro.core.dist_assoc import _matmul_bsr_prog

    mesh = _abstract_mesh()
    n_a, n_c, n_pairs = 2, 2, 16
    prog = _matmul_bsr_prog(mesh, _plus_times(), n_a, n_c, _NKEYS, _NKEYS,
                            256, "ref")
    ints = _sds((_NSHARDS, _CAP), jnp.int32)
    pint = _sds((_NSHARDS, n_pairs), jnp.int32)
    yield "bsr-one-program", lower_hlo(
        prog, _sds((_NSHARDS, _CAP), jnp.float32), ints, ints, ints,
        _sds((n_a, 128, 128), jnp.float32), pint, pint, pint,
        _sds((_NSHARDS, n_c, 2), jnp.int32))

    def run():
        _matmul_bsr_prog(mesh, _plus_times(), n_a, n_c, _NKEYS, _NKEYS,
                         256, "ref")

    yield RetraceAudit(label="bsr-prog-cache", first=run, again=run,
                       size=lambda: _matmul_bsr_prog.cache_info().currsize)


# --------------------------------------------------------------------------
# Dynamic ingest (repro.ingest): the LSM write/read path.  The batch fold
# and both merge programs must be zero-collective (delta batches are
# pre-routed to their owning row shard on host) and never densify (the
# merged output is O(capb + capd), never O(nr·nc)); small COO capacities
# over 4096-rank keyspaces keep the detector sharp.
# --------------------------------------------------------------------------

def _delta_sds(cap: int = _CAP):
    import jax.numpy as jnp
    return (*_b_triples_sds(cap), _sds((cap,), jnp.int32),
            _sds((cap,), jnp.bool_))


@probe_for("ingest.append")
def _probe_ingest_append():
    import jax.numpy as jnp
    from repro.ingest.merge import REMAP_SLOTS, _append_prog

    br, bc, _ = _b_triples_sds(4 * _CAP)
    xr, xc, xv = _b_triples_sds(_CAP // 4)
    q = _sds((_CAP // 4,), jnp.int32)
    for remap, n in [("shift", REMAP_SLOTS), ("map", _NKEYS)]:
        aux = _sds((n,), jnp.int32)
        yield f"append-{remap}", lower_hlo(
            _append_prog("sum", remap), br, bc, *_delta_sds(), xr, xc, xv,
            q, q, aux, aux)

    def run():
        _append_prog("sum", "shift")

    yield RetraceAudit(label="append-prog-cache", first=run, again=run,
                       size=lambda: _append_prog.cache_info().currsize)


@probe_for("ingest.merge_read")
def _probe_ingest_merge_read():
    import jax.numpy as jnp
    from repro.ingest.merge import REMAP_SLOTS, _merge_read_prog

    aux = _sds((REMAP_SLOTS,), jnp.int32)
    prog = _merge_read_prog("sum", "shift")
    yield "overlay-merge", lower_hlo(prog, *_b_triples_sds(4 * _CAP),
                                     *_delta_sds(), aux, aux,
                                     out_cap=4 * _CAP)

    def run():
        _merge_read_prog("sum", "shift")

    yield RetraceAudit(label="merge-prog-cache", first=run, again=run,
                       size=lambda: _merge_read_prog.cache_info().currsize)


@probe_for("ingest.dist_merge_read")
def _probe_ingest_dist_merge():
    import jax.numpy as jnp
    from repro.ingest.merge import _dist_merge_prog

    mesh = _abstract_mesh()
    a = _coo_dict_sds()
    d = _sds((_NSHARDS, _CAP), jnp.int32)
    dv = _sds((_NSHARDS, _CAP), jnp.float32)
    kmap = _sds((_NKEYS,), jnp.int32)
    for label, rerank in [("shard-local", False), ("reranked", True)]:
        prog = _dist_merge_prog(mesh, "sum", rerank)
        yield label, lower_hlo(prog, a, d, d, dv, kmap, kmap)

    def run():
        _dist_merge_prog(mesh, "sum", True)

    yield RetraceAudit(label="dist-merge-prog-cache", first=run, again=run,
                       size=lambda: _dist_merge_prog.cache_info().currsize)


@probe_for("DistAssoc.matmul_dense_vec")
def _probe_dist_matvec():
    import jax.numpy as jnp
    from repro.core.dist_assoc import _matvec_prog

    mesh = _abstract_mesh()
    prog = _matvec_prog(mesh, _plus_times(), _NKEYS, jnp.float32)
    yield "matvec", lower_hlo(prog, _sds((_NSHARDS, _CAP), jnp.int32),
                              _sds((_NSHARDS, _CAP), jnp.int32),
                              _sds((_NSHARDS, _CAP), jnp.float32),
                              _sds((_NKEYS,), jnp.float32))
