"""TPU-native associative arrays: fixed-capacity, jit-safe, semiring-generic.

``AssocTensor`` is the device counterpart of the host ``Assoc``.  Where the
paper's Python implementation leans on ``scipy.sparse`` with dynamic shapes,
the TPU demands static shapes and bulk vector ops, so:

* keys are **int32 ranks** into host-side :class:`~repro.core.keyspace.KeySpace`
  dictionaries (see that module for why rank order ⇔ key order);
* the nonempty entries live in a **sorted, sentinel-padded COO triple**
  ``(rows, cols, vals)`` of static ``capacity`` plus an ``nnz`` scalar —
  growth is an explicit host-side ``grow()``, mirroring how Accumulo-backed
  D4M splits tablets rather than reallocating per insert;
* element-wise algebra is *concat → lexsort → segment-reduce* — one fused,
  shape-static pipeline that subsumes the paper's constructor aggregation,
  sorted-union addition and sorted-intersection multiplication;
* array multiplication densifies ``adj`` onto MXU-aligned tiles and calls the
  Pallas semiring matmul (``repro.kernels.semiring_matmul``), or its
  block-sparse variant for large sparse operands.

All methods are pure functions of array state (registered pytree) and safe
under ``jax.jit`` / ``pjit``; keyspaces ride in the static aux.  The one
exception is the eager-only in-place ``__setitem__`` (see its docstring).
"""
from __future__ import annotations

import dataclasses
import threading
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .assoc import Assoc
from repro.analysis.contracts import contract
from repro.trace import span

from .coo import SENT, dedup_sorted_coo
from .expr import EwiseAdd, EwiseMul, MatMul, Select, Source
from .keyspace import KeySpace
from .semiring import PLUS_TIMES, Semiring, get_semiring
from .sorted_ops import INT_SENTINEL

# ``dedup_sorted_coo`` — the canonical COO merge shared with the host Assoc —
# lives in repro.core.coo; re-exported here for backward compatibility.
__all__ = ["AssocTensor", "dedup_sorted_coo"]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# -- selection primitives on raw COO rank arrays ------------------------------
#
# Shared by AssocTensor's methods AND DistAssoc's shard_map bodies (which
# operate on raw per-shard arrays, not pytree objects): one implementation
# of the keep mask and the sentinel-blank + lexsort compaction, so the
# layers cannot drift apart.  Eager AssocTensor selections compact small
# results into a result-sized buffer instead (``_compact_sized``).

def coo_range_keep(rows: jnp.ndarray, cols: jnp.ndarray,
                   bounds: jnp.ndarray) -> jnp.ndarray:
    """Keep mask for a rank box — the Pallas range-mask kernel."""
    from repro.kernels.range_extract import range_mask
    return range_mask(rows, cols, bounds) != 0


def coo_mask_keep(rows: jnp.ndarray, cols: jnp.ndarray,
                  row_mask: jnp.ndarray, col_mask: jnp.ndarray) -> jnp.ndarray:
    """Keep mask for keyspace membership masks (one gather each)."""
    ok = rows != SENT
    return (ok & row_mask[jnp.clip(rows, 0, row_mask.shape[0] - 1)]
            & col_mask[jnp.clip(cols, 0, col_mask.shape[0] - 1)])


def coo_axis_mask_keep(idx: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Single-axis membership gather (the set half of a hybrid selection)."""
    ok = idx != SENT
    return ok & mask[jnp.clip(idx, 0, mask.shape[0] - 1)]


# Selection-path dispatch counters (eager queries only): which execution
# path compiled selections take — ``range`` (Pallas range kernel, both axes
# contiguous), ``multirange`` (a multi-interval selection decomposed into
# ≤4 range-kernel boxes, OR-composed), ``hybrid`` (one contiguous axis
# through the range kernel + one membership gather), ``gather`` (both axes
# scattered).  Mirrors select.CACHE_STATS; tests and benchmarks read these
# to pin the fast path.
DISPATCH_STATS = {"range": 0, "multirange": 0, "hybrid": 0, "gather": 0}

# Device-to-host result traffic: ``to_host_bytes``/``to_host_calls`` count
# what ``to_assoc`` copies (whole-capacity arrays), ``entries_returned``
# the entries the serve layer's ``format_result`` hands back — their ratio
# is the copy's cost per answered entry.
TRANSFER_STATS = {"to_host_bytes": 0, "to_host_calls": 0,
                  "entries_returned": 0}

# Selection compactions by path (``AssocTensor._compact``; a traced one
# counts once per trace): ``sized`` moved the kept entries into a
# result-sized buffer (``_compact_sized``), ``full`` re-sorted the whole
# capacity (``coo_compact``: traced keep masks, small tables, large
# results).
COMPACT_STATS = {"sized": 0, "full": 0}

# The sized result buffer is a power of two of at least ``_SIZED_MIN``
# slots (one compiled program serves every small result of a table), and
# at most capacity / ``_SIZED_MAX_SHARE``: past that, ``k`` binary
# searches stop paying and a full-size result gains nothing from a
# smaller buffer.
_SIZED_MIN = 256
_SIZED_MAX_SHARE = 8

# Dict += is a read-modify-write: serve workers bump these concurrently.
_STATS_LOCK = threading.Lock()


@lru_cache(maxsize=None)
def _canonicalize(combine):
    """The constructor's canonicalization as ONE compiled program per ⊕:
    run eagerly, ``dedup_sorted_coo`` is ~100 separately compiled ops,
    about a minute per n = 18 table on a TPU v5e."""
    return jax.jit(partial(dedup_sorted_coo, combine=combine))


def _bump_dispatch(key: str) -> None:
    with _STATS_LOCK:
        DISPATCH_STATS[key] += 1


def count_transfer(**counts: int) -> None:
    """Add ``counts`` to ``TRANSFER_STATS``."""
    with _STATS_LOCK:
        for k, n in counts.items():
            TRANSFER_STATS[k] += n


def coo_compact(rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
                keep: jnp.ndarray):
    """Keep-masked triples → canonical sorted/sentinel-padded form.

    Static-shape and order-free: the kept triples need not be in (row,
    col) order, and the result keeps the input's capacity — what traced
    selections, ``DistAssoc``'s shard bodies and the ingest merge need.
    """
    r = jnp.where(keep, rows, SENT)
    c = jnp.where(keep, cols, SENT)
    v = jnp.where(keep, vals, 0.0)
    order = jnp.lexsort((c, r))
    return r[order], c[order], v[order], keep.sum().astype(jnp.int32)


@partial(jax.jit, static_argnums=4)
def _compact_sized(rows, cols, vals, keep, k: int):
    """The kept triples moved, in stored order, into ``k`` SENT-padded
    slots: the first ``k`` slots of :func:`coo_compact`'s result when the
    input is canonical (a keep mask filters a sorted table pointwise, so
    the kept entries are already sorted) and at most ``k`` are kept.

    The j-th kept entry sits where the prefix count of ``keep`` first
    reaches j + 1: ``k`` binary searches into the count and three
    ``k``-element gathers, no sort.
    """
    cs = jnp.cumsum(keep, dtype=jnp.int32)
    pos = jnp.searchsorted(cs, jnp.arange(1, k + 1, dtype=jnp.int32))
    pos = jnp.minimum(pos, keep.shape[0] - 1)
    ok = jnp.arange(k) < cs[-1]
    return (jnp.where(ok, rows[pos], SENT), jnp.where(ok, cols[pos], SENT),
            jnp.where(ok, vals[pos], 0.0), cs[-1])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AssocTensor:
    """Device associative array (padded COO + host keyspaces)."""

    rows: jnp.ndarray  # int32[capacity], sorted by (row, col), SENT-padded
    cols: jnp.ndarray  # int32[capacity]
    vals: jnp.ndarray  # float32[capacity] (or int32 value-ranks if val_space)
    nnz: jnp.ndarray   # int32 scalar
    row_space: KeySpace = dataclasses.field(metadata={"static": True})
    col_space: KeySpace = dataclasses.field(metadata={"static": True})
    val_space: Optional[KeySpace] = None  # None ⇒ numeric values

    # eager-only metadata, NOT part of the pytree: capacity-producing ops
    # (matmul, from_dense_adj) set an instance attribute when the result was
    # truncated; after any tree_map/jit round trip it falls back to this
    # class default rather than raising
    overflow = False

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return ((self.rows, self.cols, self.vals, self.nnz),
                (self.row_space, self.col_space, self.val_space))

    @classmethod
    def tree_unflatten(cls, aux, children):
        rows, cols, vals, nnz = children
        return cls(rows, cols, vals, nnz, *aux)

    # -- construction ---------------------------------------------------------
    @staticmethod
    def from_triples(row_keys, col_keys, values, *, aggregate="min",
                     capacity: Optional[int] = None,
                     row_space: Optional[KeySpace] = None,
                     col_space: Optional[KeySpace] = None) -> "AssocTensor":
        """Host-side constructor (the D4M ``Assoc(row, col, val)`` analogue).

        Builds keyspaces (or ranks into provided ones), uploads rank triples,
        and canonicalizes on device with the ``aggregate`` collision op.
        """
        row_keys = np.asarray(row_keys)
        col_keys = np.asarray(col_keys)
        values = np.asarray(values)
        if values.ndim == 0:
            values = np.broadcast_to(values, row_keys.shape).copy()

        val_space = None
        if values.dtype.kind in ("U", "S", "O"):
            val_space = KeySpace(values)
            vals_num, _ = val_space.rank(values)
            vals_num = vals_num.astype(np.float32)
        else:
            vals_num = values.astype(np.float32)

        row_space = row_space or KeySpace(row_keys)
        col_space = col_space or KeySpace(col_keys)
        r, _ = row_space.rank(row_keys)
        c, _ = col_space.rank(col_keys)

        cap = capacity or _round_up(max(len(r), 8), 8)
        if cap < len(r):
            raise ValueError(f"capacity {cap} < {len(r)} triples")
        pad = cap - len(r)
        rj = jnp.asarray(np.concatenate([r, np.full(pad, INT_SENTINEL, np.int32)]))
        cj = jnp.asarray(np.concatenate([c, np.full(pad, INT_SENTINEL, np.int32)]))
        vj = jnp.asarray(np.concatenate([vals_num, np.zeros(pad, np.float32)]))

        agg = {
            "min": jnp.minimum, "max": jnp.maximum, "sum": jnp.add,
            min: jnp.minimum, max: jnp.maximum, sum: jnp.add,
        }.get(aggregate, aggregate)
        # string values: aggregation acts on ranks; offset by +1 so that the
        # zero-drop below only removes true sentinels, not rank 0.
        if val_space is not None:
            vj = jnp.where(rj != SENT, vj + 1.0, 0.0)
        rows, cols, vals, nnz = _canonicalize(agg)(rj, cj, vj)
        return AssocTensor(rows, cols, vals, nnz, row_space, col_space, val_space)

    @staticmethod
    def from_assoc(a: Assoc, capacity: Optional[int] = None, *,
                   row_space: Optional[KeySpace] = None,
                   col_space: Optional[KeySpace] = None) -> "AssocTensor":
        """Upload a host Assoc; inverse of :meth:`to_assoc` (lossless for
        string values and f32-representable numeric values; explicit 0.0
        entries are dropped — the device stores 0 as empty)."""
        r, c, v = a.triples()
        return AssocTensor.from_triples(r, c, v, capacity=capacity,
                                        row_space=row_space,
                                        col_space=col_space)

    def to_assoc(self) -> Assoc:
        """Download to the host paper-faithful representation."""
        with span("d4m.device_wait"):
            n = int(self.nnz)
            jax.block_until_ready((self.rows, self.cols, self.vals))
        with span("d4m.to_host"):
            r, c, v = (np.asarray(a) for a in (self.rows, self.cols,
                                                self.vals))
        count_transfer(to_host_bytes=r.nbytes + c.nbytes + v.nbytes,
                       to_host_calls=1)
        r, c, v = r[:n], c[:n], v[:n]
        row_keys = self.row_space.keys[r]
        col_keys = self.col_space.keys[c]
        if self.val_space is not None:
            vals = self.val_space.keys[(v - 1.0).astype(np.int64)]
        else:
            vals = v.astype(np.float64)
        return Assoc(row_keys, col_keys, vals)

    # -- basic properties -----------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    @property
    def numeric(self) -> bool:
        return self.val_space is None

    def valid_mask(self) -> jnp.ndarray:
        return self.rows != SENT

    # -- re-ranking onto merged keyspaces --------------------------------------
    def reranked(self, row_space: KeySpace, col_space: KeySpace,
                 row_map: np.ndarray, col_map: np.ndarray) -> "AssocTensor":
        """Translate ranks onto merged keyspaces (one gather each)."""
        rm = jnp.asarray(row_map)
        cm = jnp.asarray(col_map)
        ok = self.valid_mask()
        rows = jnp.where(ok, rm[jnp.clip(self.rows, 0, len(rm) - 1)], SENT)
        cols = jnp.where(ok, cm[jnp.clip(self.cols, 0, len(cm) - 1)], SENT)
        return AssocTensor(rows, cols, self.vals, self.nnz,
                           row_space, col_space, self.val_space)

    def _aligned(self, other: "AssocTensor"):
        """Bring two arrays onto common keyspaces (host merge, amortized)."""
        rs, rm_a, rm_b = self.row_space.union(other.row_space)
        cs, cm_a, cm_b = self.col_space.union(other.col_space)
        a = self if (rs == self.row_space and cs == self.col_space) else \
            self.reranked(rs, cs, rm_a, cm_a)
        b = other if (rs == other.row_space and cs == other.col_space) else \
            other.reranked(rs, cs, rm_b, cm_b)
        return a, b

    # -- lazy expressions (the deferred pipeline API, repro.core.expr) ---------
    def lazy(self) -> Source:
        """Wrap as a lazy expression Source (see ``Assoc.lazy``)."""
        return Source(self)

    # -- element-wise algebra ---------------------------------------------------
    def add(self, other: "AssocTensor", semiring=PLUS_TIMES) -> "AssocTensor":
        """Element-wise ⊕ over the union of key sets (paper §II.C.1)."""
        sr = get_semiring(semiring)
        a, b = self._aligned(other)
        rows = jnp.concatenate([a.rows, b.rows])
        cols = jnp.concatenate([a.cols, b.cols])
        vals = jnp.concatenate([a.vals, b.vals])
        r, c, v, nnz = dedup_sorted_coo(rows, cols, vals, sr.add, zero=sr.zero)
        return AssocTensor(r, c, v, nnz, a.row_space, a.col_space, a.val_space)

    def __add__(self, other):
        # thin wrapper over the one-node graph (lazy/eager share one path);
        # expression operands defer to the Node's reflected operator
        if not isinstance(other, AssocTensor):
            return NotImplemented
        return EwiseAdd(Source(self), Source(other)).collect()

    def mul(self, other: "AssocTensor", semiring=PLUS_TIMES) -> "AssocTensor":
        """Element-wise ⊗ over the intersection of key sets (paper §II.C.2)."""
        sr = get_semiring(semiring)
        a, b = self._aligned(other)
        rows = jnp.concatenate([a.rows, b.rows])
        cols = jnp.concatenate([a.cols, b.cols])
        vals = jnp.concatenate([a.vals, b.vals])
        src = jnp.concatenate([
            jnp.zeros(a.capacity, jnp.int32), jnp.ones(b.capacity, jnp.int32)])
        r, c, v, nnz = dedup_sorted_coo(
            rows, cols, vals, sr.add, zero=sr.zero,
            require_pair=True, pair_op=sr.mul, src=src)
        cap = min(a.capacity, b.capacity)
        return AssocTensor(r[:cap], c[:cap], v[:cap], jnp.minimum(nnz, cap),
                           a.row_space, a.col_space, a.val_space)

    def __mul__(self, other):
        if not isinstance(other, AssocTensor):
            return NotImplemented
        return EwiseMul(Source(self), Source(other)).collect()

    def logical(self) -> "AssocTensor":
        """Replace nonempty entries with 1 (paper's ``.logical()``)."""
        ok = self.valid_mask()
        return AssocTensor(self.rows, self.cols,
                           jnp.where(ok, 1.0, 0.0).astype(self.vals.dtype),
                           self.nnz, self.row_space, self.col_space, None)

    # -- densification + array multiplication -----------------------------------
    def to_dense_adj(self, *, pad_to: int = 128,
                     zero: float = 0.0) -> jnp.ndarray:
        """Scatter onto a dense (|rowspace|, |colspace|) MXU-aligned array."""
        nr = _round_up(max(len(self.row_space), 1), pad_to)
        nc = _round_up(max(len(self.col_space), 1), pad_to)
        ok = self.valid_mask()
        # route padding entries out of bounds so mode="drop" discards them
        r = jnp.where(ok, self.rows, nr)
        c = jnp.where(ok, self.cols, nc)
        v = jnp.where(ok, self.vals, zero)
        dense = jnp.full((nr, nc), zero, dtype=self.vals.dtype)
        # duplicate-free by invariant: plain scatter
        return dense.at[r, c].set(v, mode="drop", unique_indices=False)

    @staticmethod
    def from_dense_adj(dense, row_space: KeySpace, col_space: KeySpace,
                       capacity: int, *, zero: float = 0.0,
                       warn_overflow: bool = True) -> "AssocTensor":
        """Top-|capacity| nonzeros of a dense adj back to padded COO.

        When the true nonzero count exceeds ``capacity`` the excess entries
        (latest in (row, col) order) are dropped; the result records that
        as an eager ``overflow`` attribute (bool device scalar) and, on
        host-driven (untraced) paths, emits a ``RuntimeWarning`` — a silent
        truncation here corrupts every downstream ⊕ without a trace.
        """
        nr, nc = dense.shape
        flat = dense.reshape(-1)
        ok = flat != zero
        # order: valid entries first, in row-major (row, col) order
        idx = jnp.arange(flat.shape[0], dtype=jnp.int32)
        order = jnp.argsort(jnp.where(ok, idx, jnp.int32(2**31 - 1)),
                            stable=True)[:capacity]
        taken_ok = ok[order]
        rows = jnp.where(taken_ok, order // nc, SENT).astype(jnp.int32)
        cols = jnp.where(taken_ok, order % nc, SENT).astype(jnp.int32)
        vals = jnp.where(taken_ok, flat[order], zero)
        true_nnz = ok.sum()
        nnz = jnp.minimum(true_nnz, capacity).astype(jnp.int32)
        out = AssocTensor(rows, cols, vals, nnz, row_space, col_space, None)
        overflow = true_nnz > capacity
        out.overflow = overflow
        if warn_overflow and not isinstance(dense, jax.core.Tracer) \
                and bool(overflow):
            import warnings
            warnings.warn(
                f"from_dense_adj: {int(true_nnz)} nonzeros exceed capacity "
                f"{capacity}; {int(true_nnz) - capacity} entries dropped",
                RuntimeWarning, stacklevel=2)
        return out

    def transpose(self) -> "AssocTensor":
        """Swap rows/cols and restore canonical (row, col) order."""
        ok = self.valid_mask()
        r = jnp.where(ok, self.cols, SENT)
        c = jnp.where(ok, self.rows, SENT)
        order = jnp.lexsort((c, r))
        return AssocTensor(r[order], c[order], self.vals[order], self.nnz,
                           self.col_space, self.row_space, self.val_space)

    @property
    def T(self) -> "AssocTensor":
        return self.transpose()

    def matmul(self, other: "AssocTensor", semiring=PLUS_TIMES,
               out_capacity: Optional[int] = None,
               use_kernel: bool = True, impl: str = "auto",
               kernel_impl: str = "auto") -> "AssocTensor":
        """Array multiplication ``⊗.⊕`` contracting over col/row keys.

        Strings are first reduced via ``logical()`` (paper rule).  Planned
        and executed by :mod:`repro.core.spgemm` — the dense strategy
        contracts MXU-aligned adj tiles through the Pallas semiring matmul;
        the BSR strategy packs only the present 128×128 tiles and streams
        them through the scalar-prefetch pair-list kernel, never
        materializing the dense product; ``impl`` overrides the auto
        heuristic (``"dense"`` / ``"bsr"`` / ``"coo"``) and ``kernel_impl``
        the pair-list kernel dispatch (``"pallas"`` / ``"interpret"`` /
        ``"ref"`` / ``"chunked"``).
        """
        from .spgemm import matmul as _planned_matmul
        return _planned_matmul(self, other, semiring, impl=impl,
                               out_capacity=out_capacity,
                               use_kernel=use_kernel,
                               kernel_impl=kernel_impl)

    def matmul_reduce(self, other: "AssocTensor", axis: int,
                      semiring=PLUS_TIMES, *, impl: str = "auto",
                      kernel_impl: str = "auto") -> jnp.ndarray:
        """Fused ``⊕-reduce(self ⊗.⊕ other, axis)`` — skips materializing
        the product entirely (Graphulo pushdown; see
        :func:`repro.core.spgemm.matmul_reduce`).  Returns a dense vector
        over ``self.row_space`` (``axis=1``) or ``other.col_space``
        (``axis=0``)."""
        from .spgemm import matmul_reduce as _planned_reduce
        return _planned_reduce(self, other, axis, semiring, impl=impl,
                               kernel_impl=kernel_impl)

    def sqin(self, semiring=PLUS_TIMES, reduce: Optional[int] = None):
        """AᵀA — the correlation idiom.  ``reduce=0/1`` returns the fused
        ⊕-reduction of the square instead (vector over the col keyspace)."""
        t = self.transpose()
        if reduce is None:
            return t.matmul(self, semiring)
        return t.matmul_reduce(self, reduce, semiring)

    def sqout(self, semiring=PLUS_TIMES, reduce: Optional[int] = None):
        """AAᵀ — row-key graph; ``reduce=0/1`` for the fused reduction."""
        t = self.transpose()
        if reduce is None:
            return self.matmul(t, semiring)
        return self.matmul_reduce(t, reduce, semiring)

    def __matmul__(self, other):
        if not isinstance(other, AssocTensor):
            return NotImplemented
        return MatMul(Source(self), Source(other)).collect()

    # -- extraction -------------------------------------------------------------
    #
    # All __getitem__ selection routes through the selector algebra
    # (repro.core.select): the selector compiles once on host against the
    # keyspaces, then executes on device against the padded COO triples —
    # a contiguous rank box goes through the Pallas range-mask kernel, a
    # general index set through one membership gather.  Selection never
    # densifies.

    def _compact(self, keep: jnp.ndarray) -> "AssocTensor":
        """Keep-masked triples → canonical sorted/sentinel-padded form.

        An eager ``keep`` has its count read to the host; a result that
        fits a buffer of at most capacity / ``_SIZED_MAX_SHARE`` slots is
        moved into one (:func:`_compact_sized`), in stored order.  A
        traced ``keep`` (no count can be read) and a larger result take
        the whole-capacity :func:`coo_compact`.
        """
        k = None
        if (not isinstance(keep, jax.core.Tracer)
                and self.capacity // _SIZED_MAX_SHARE >= _SIZED_MIN):
            with span("d4m.device_wait"):
                n = int(keep.sum())
            k = max(_SIZED_MIN, 1 << (n - 1).bit_length())
            if k > self.capacity // _SIZED_MAX_SHARE:
                k = None
        if k is None:
            r, c, v, nnz = coo_compact(self.rows, self.cols, self.vals, keep)
        else:
            r, c, v, nnz = _compact_sized(self.rows, self.cols, self.vals,
                                          keep, k)
        with _STATS_LOCK:
            COMPACT_STATS["full" if k is None else "sized"] += 1
        return AssocTensor(r, c, v, nnz,
                           self.row_space, self.col_space, self.val_space)

    @staticmethod
    def _bounds(row_range: Tuple[int, int],
                col_range: Tuple[int, int]) -> jnp.ndarray:
        """A rank box as the range kernel's device bounds."""
        return jnp.asarray([row_range[0], row_range[1],
                            col_range[0], col_range[1]], dtype=jnp.int32)

    def _range_keep(self, row_range: Tuple[int, int],
                    col_range: Tuple[int, int]) -> jnp.ndarray:
        """Keep mask for a rank box, via the shared Pallas range kernel."""
        return coo_range_keep(self.rows, self.cols,
                              self._bounds(row_range, col_range))

    def _mask_keep(self, row_mask: jnp.ndarray,
                   col_mask: jnp.ndarray) -> jnp.ndarray:
        """Keep mask for keyspace membership masks (one gather each)."""
        return coo_mask_keep(self.rows, self.cols, row_mask, col_mask)

    def extract_ranges(self, row_range: Tuple[int, int],
                       col_range: Tuple[int, int]) -> "AssocTensor":
        """Sub-array by rank ranges (host resolves key slices → ranks)."""
        return self._compact(self._range_keep(row_range, col_range))

    def extract_mask(self, row_mask: jnp.ndarray,
                     col_mask: jnp.ndarray) -> "AssocTensor":
        """Sub-array by keyspace membership masks (gather path, jit-safe).

        ``row_mask``/``col_mask`` are bool arrays over the row/col
        keyspaces — the compiled form of a non-contiguous selector.
        """
        return self._compact(self._mask_keep(row_mask, col_mask))

    def _compiled_pair(self, ij):
        from .select import compile_selector
        return (compile_selector(ij[0], self.row_space),
                compile_selector(ij[1], self.col_space))

    def _device_masks(self, rc, cc) -> Tuple[jnp.ndarray, jnp.ndarray]:
        rm = (np.ascontiguousarray(rc.mask()) if len(self.row_space)
              else np.zeros(1, bool))
        cm = (np.ascontiguousarray(cc.mask()) if len(self.col_space)
              else np.zeros(1, bool))
        return jnp.asarray(rm), jnp.asarray(cm)

    def _selection_keep(self, ij) -> jnp.ndarray:
        """Compile (row_sel, col_sel) and evaluate the device keep mask.

        The single dispatch point between four execution paths — both
        ``__getitem__`` and ``__setitem__`` go through here, planned by
        :func:`repro.core.select.plan_boxes`:

        * both axes contiguous → ONE Pallas range-mask kernel call;
        * a multi-interval ``Match``/``Where``/``Keys`` whose hits form ≤4
          rank boxes → one range-kernel call per box, OR-composed (the
          boxes are disjoint interval runs, so the OR is exact, and like
          every keep mask here it filters the stored (row, col) order
          pointwise — the compaction keeps that order, with no merge of
          extracted lists and no sort);
        * one axis boxable, the other scattered → the box calls AND one
          membership gather for the scattered axis;
        * both axes scattered → two membership gathers (no kernel).

        Spans: ``d4m.selector`` covers the host work (selector compile,
        box planning, bounds and mask uploads), ``d4m.keep`` the device
        launches.
        """
        from .select import plan_boxes

        with span("d4m.selector"):
            rc, cc = self._compiled_pair(ij)
            nr = max(len(self.row_space), 1)
            nc = max(len(self.col_space), 1)
            boxes, row_gather, col_gather = plan_boxes(rc, cc, nr, nc)
            if row_gather and col_gather:
                _bump_dispatch("gather")
                row_mask, col_mask = self._device_masks(rc, cc)
            else:
                if len(boxes) > 1:
                    _bump_dispatch("multirange")
                elif row_gather or col_gather:
                    _bump_dispatch("hybrid")
                else:
                    _bump_dispatch("range")
                bounds = [self._bounds((int(b[0]), int(b[1])),
                                       (int(b[2]), int(b[3])))
                          for b in boxes]
                # membership mask built (and uploaded) ONLY for a
                # scattered axis — boxed axes are already handled by the
                # kernel bounds
                row_mask = (jnp.asarray(np.ascontiguousarray(rc.mask()))
                            if row_gather else None)
                col_mask = (jnp.asarray(np.ascontiguousarray(cc.mask()))
                            if col_gather else None)
        with span("d4m.keep"):
            if row_gather and col_gather:
                return self._mask_keep(row_mask, col_mask)
            keep = coo_range_keep(self.rows, self.cols, bounds[0])
            for b in bounds[1:]:
                keep = keep | coo_range_keep(self.rows, self.cols, b)
            if row_mask is not None:
                keep = keep & coo_axis_mask_keep(self.rows, row_mask)
            if col_mask is not None:
                keep = keep & coo_axis_mask_keep(self.cols, col_mask)
            return keep

    @contract(collectives=0,
              note="device selection: range kernel / masks, never dense")
    def __getitem__(self, ij) -> "AssocTensor":
        # thin wrapper over the one-node graph (see __add__)
        i, j = ij
        return Select(Source(self), i, j).collect()

    def _select_eager(self, ij) -> "AssocTensor":
        """Physical selection (the executor's device backend)."""
        with span("d4m.select"):
            keep = self._selection_keep(ij)
            with span("d4m.compact"):
                return self._compact(keep)

    @contract(collectives=0,
              note="in-place value overwrite over stored entries")
    def __setitem__(self, ij, value) -> None:
        """Selector-targeted value update (in place, numeric scalar).

        Overwrites the values of *stored* entries inside the selection;
        the support is unchanged (inserting new entries is a host-side
        ``from_triples`` — the device layout is fixed-capacity).

        Eager/host-driven only: this mutates the Python object, which is
        the one exception to the module's pure-pytree contract — inside a
        ``jax.jit`` trace use ``extract_*``/functional updates instead.
        """
        if (not isinstance(value, (int, float, np.integer, np.floating))
                or isinstance(value, (bool, np.bool_))):
            raise TypeError("device __setitem__ takes a numeric scalar")
        if not self.numeric:
            raise TypeError("device __setitem__ requires numeric values")
        keep = self._selection_keep(ij)
        self.vals = jnp.where(keep, jnp.float32(value), self.vals)

    # -- reductions ---------------------------------------------------------------
    #
    # Both axis reductions route through the shared reduce path in
    # repro.core.plan (one scatter_combine implementation for the Reduce
    # node, eager calls, and the fused epilogue partials alike).

    def reduce_rows(self, semiring=PLUS_TIMES) -> jnp.ndarray:
        """⊕-reduce over columns → dense vector over the row keyspace."""
        from .plan import device_axis_reduce
        return device_axis_reduce(self, 1, semiring)

    def reduce_cols(self, semiring=PLUS_TIMES) -> jnp.ndarray:
        """⊕-reduce over rows → dense vector over the col keyspace."""
        from .plan import device_axis_reduce
        return device_axis_reduce(self, 0, semiring)

    def nnz_host(self) -> int:
        return int(self.nnz)
