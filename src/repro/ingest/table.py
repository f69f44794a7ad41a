"""LSM-style mutable overlay over a resident associative array.

:class:`IngestTable` wraps a base array from any of the three layers
(host ``Assoc``, device ``AssocTensor``, sharded ``DistAssoc``) with the
Accumulo tablet-server write path:

* ``insert(rows, cols, vals)`` takes one raw triple batch.  Host and dist
  tables append it to a host-side buffer (the dist layer routes each
  triple to its owning row shard: zero collectives).  A device table
  ranks the batch once, canonicalizes it on the host, uploads it and
  folds it into a device-resident canonical **delta** of one fixed
  capacity (:func:`repro.ingest.merge.append`).  New row or column keys
  grow the delta's keyspaces; the delta's ranks follow on the device.
* reads: a device table answers a selection (:meth:`select`) from the
  base and from the delta and ⊕-combines the two small results on the
  host, so no read pays a whole-table merge.  :meth:`snapshot` is the
  merged view for any other query: base ⊕ delta through the whole-table
  merge, memoized per (version, delta depth).
* ``compact()`` folds the delta into a new base, bumps ``version``, and
  invalidates the planner/compile cache entries keyed on the retired
  arrays.  It builds the new base without holding the lock readers take:
  they read the last consistent view until the new base is swapped in.
  Inserts wait for it.  :class:`Compactor` runs it in the background.

A device base lives in a power-of-two capacity with room for two deltas
beyond its entries, so compactions keep their output capacity until the
table outgrows it; the delta's capacity is the power of two at or above
``compact_threshold``.  Every program a stream of inserts, reads and
compactions runs is then one of a bounded set.

Aggregation: ⊕ combines base-first (the host ``combine`` order); device
and dist layers restrict ⊕ to the commutative monoids (``sum``/``min``/
``max``); host tables accept any ``Assoc`` aggregator (including
order-sensitive ``"concat"``).  Each layer keeps its own zero rule: the
host constructor drops explicit-zero *raw* values, the device layers
unstore zero *results*.  A device table unstores them where they arise —
at each fold of a batch into the delta and at each merge with the base —
which for ``sum`` is the one-shot constructor's answer; under ``min``/
``max`` an entry a zero unstored can be stored again by a later batch.
"""
from __future__ import annotations

import functools
import logging
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.trace import span

__all__ = ["IngestTable", "Compactor"]

_log = logging.getLogger(__name__)

# A writer is idle once it has been quiet for _IDLE_TIMES its longest gap
# between batches among its last _GAPS (kept across compactions): an
# open-loop (Poisson) writer's next gap does that once in ~3.7e5 batches,
# a writer that stopped does it within three of its longest gaps.
_IDLE_TIMES = 3
_GAPS = 128

_UFUNCS = {"sum": np.add, "min": np.minimum, "max": np.maximum}


def _next_pow2(n: int) -> int:
    p = 8
    while p < n:
        p *= 2
    return p


def _boundary_keys(space, bounds) -> np.ndarray:
    """First-key of shards 1..S-1 — the key-interval routing table."""
    keys = space.keys
    if len(keys) == 0:
        return keys[:0]
    idx = np.minimum(np.asarray(bounds[1:-1], dtype=np.int64),
                     len(keys) - 1)
    return keys[idx]


@contextmanager
def _held(lock):
    """Hold ``lock``; the time spent waiting for it is ``d4m.table_wait``."""
    with span("d4m.table_wait"):
        lock.acquire()
    try:
        yield
    finally:
        lock.release()


def _ranked(space, lack, keys):
    """One axis of a batch against the delta's keyspace ``space``:
    ``(grown, new, ranks, codes)``.  ``new`` are the keys ``space``
    lacks, sorted, and ``grown`` is ``space`` with them (``space`` itself
    when nothing is new); ``ranks`` are the keys' ranks in ``grown`` and
    ``codes`` code them against the base's keyspace for a lexicographic
    search of its ranks: ``2r + 1`` for the key of rank ``r``, ``2p`` for
    a key the base lacks that sorts before rank ``p``.  ``lack`` holds
    the keys ``space`` has and the base lacks (arrays of them).  Only
    one search runs over ``space``; the others are over the few keys
    the base lacks."""
    from repro.core import KeySpace
    keys = np.asarray(keys)
    if space.is_string:
        keys = keys.astype(str)
    pos = np.searchsorted(space.keys, keys)
    found = np.zeros(len(keys), bool)
    inside = pos < len(space.keys)
    found[inside] = space.keys[pos[inside]] == keys[inside]
    new = np.unique(keys[~found])
    grown = space
    if len(new):
        old = space.keys
        at = np.searchsorted(old, new)
        if np.result_type(old, new) != old.dtype:  # widen, never truncate
            old = old.astype(np.result_type(old, new))
        grown = KeySpace.from_sorted_unique(_inserted(old, at, new))
        pos = pos + np.searchsorted(new, keys)
    # the ranks in ``grown`` of the keys the base lacks, and how many of
    # them sort before each key
    lacked = np.searchsorted(grown.keys, np.sort(np.concatenate(
        [*lack, new])))
    below = np.searchsorted(lacked, pos)
    miss = np.zeros(len(keys), bool)
    ok = below < len(lacked)
    miss[ok] = lacked[below[ok]] == pos[ok]
    codes = 2 * (pos - below) + ~miss
    return grown, new, pos.astype(np.int32), codes.astype(np.int32)


def _inserted(keys, at, new) -> np.ndarray:
    """``np.insert(keys, at, new)`` for sorted ``at``, copied as raw bytes:
    numpy copies a fixed-width string array item by item with the GIL
    held, a byte copy releases it, so growing a keyspace of 2^18 keys
    does not stall the threads serving reads."""
    isz = keys.dtype.itemsize
    src = np.ascontiguousarray(keys).view(np.uint8).reshape(len(keys), isz)
    add = np.ascontiguousarray(new.astype(keys.dtype)).view(
        np.uint8).reshape(len(new), isz)
    out = np.empty((len(keys) + len(new), isz), np.uint8)
    prev = 0
    for i, a in enumerate(at):
        out[prev + i:a + i] = src[prev:a]
        out[a + i] = add[i]
        prev = a
    out[prev + len(new):] = src[prev:]
    return out.view(keys.dtype).reshape(-1)


class _DeviceState:
    """One consistent view of a device table: base, delta and the
    delta's keyspaces.  Immutable; the table swaps whole views."""

    __slots__ = ("base", "base_nnz", "delta", "delta_nnz", "spaces",
                 "grown", "depth")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)

    def but(self, **kw) -> "_DeviceState":
        return _DeviceState(**{**{k: getattr(self, k)
                                  for k in self.__slots__}, **kw})

    def delta_tensor(self):
        from repro.core import AssocTensor
        r, c, v = self.delta[:3]
        return AssocTensor(r, c, v, self.delta_nnz, *self.spaces, None)


class IngestTable:
    """Mutable LSM overlay (delta + merge-on-read + compaction)."""

    def __init__(self, base, *, aggregate: str = "sum",
                 compact_threshold: int = 4096, name: str = ""):
        from repro.core import Assoc, AssocTensor, DistAssoc

        if isinstance(base, Assoc):
            self.layer = "host"
        elif isinstance(base, AssocTensor):
            self.layer = "device"
        elif isinstance(base, DistAssoc):
            self.layer = "dist"
        else:
            raise TypeError(
                f"IngestTable base must be Assoc/AssocTensor/DistAssoc, "
                f"got {type(base).__name__}")
        if self.layer == "device" and base.val_space is not None:
            raise TypeError("device ingest requires numeric values")
        if self.layer == "dist" and base.local.val_space is not None:
            raise TypeError("dist ingest requires numeric values")
        if self.layer in ("device", "dist"):
            from .merge import _agg_op
            _agg_op(aggregate)   # validate early, not at first read

        self.aggregate = aggregate
        self.compact_threshold = int(compact_threshold)
        self.name = name
        self.version = 0

        # readers bind a view under ``_lock`` (held only to read or swap
        # references); inserts and compactions serialize on ``_write_lock``
        self._lock = threading.RLock()
        self._write_lock = threading.RLock()
        # host/device: one flat batch list; dist: one list per shard
        self._batches: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self._shard_batches: List[List[Tuple]] = []
        self._depth = 0
        self._last_insert_t: Optional[float] = None
        self._gaps: deque = deque(maxlen=_GAPS)     # the writer's last gaps
        self._snap: Optional[Tuple[int, int, Any]] = None  # (ver, depth, arr)
        self._retired: List[Any] = []   # superseded arrays, pending invalidation
        self.stats: Dict[str, int] = {}
        self.reset_stats()
        if self.layer == "dist":
            self._nshards = base.mesh.shape["data"]
            self._shard_batches = [[] for _ in range(self._nshards)]
            self._bkeys = _boundary_keys(base.local.row_space,
                                         base.row_bounds)
        if self.layer == "device":
            self._start_device(base)
        else:
            self.base = base

    def reset_stats(self) -> None:
        """Zero the counters (``inserts``, ``insert_triples``, ``reads``,
        ``merges``, ``compactions``, ``compact_errors``, and the entries
        whole-table merges read, ``merge_entries_in`` — base plus delta —
        and wrote, ``merge_entries_out``)."""
        with self._lock:
            self.stats = {k: 0 for k in (
                "inserts", "insert_triples", "reads", "merges",
                "compactions", "compact_errors", "merge_entries_in",
                "merge_entries_out")}

    # -- the device layer's state --------------------------------------------
    def _start_device(self, base) -> None:
        """Bucket the base and compile the whole-table merge at load: the
        base is padded to its capacity bucket, then merged once with the
        empty delta (the same program, at the same shapes, as every
        later compaction)."""
        import jax.numpy as jnp
        from repro.core.coo import SENT
        from .merge import REMAP_SLOTS

        self._capd = _next_pow2(self.compact_threshold)
        capd = self._capd
        self._empty = (jnp.full(capd, SENT), jnp.full(capd, SENT),
                       jnp.zeros(capd, jnp.float32),
                       jnp.zeros(capd, jnp.int32), jnp.zeros(capd, bool))
        self._zero = jnp.int32(0)
        self._no_points = jnp.full(REMAP_SLOTS, SENT)
        nnz = int(base.nnz)
        cap = _next_pow2(nnz + 2 * capd)
        if base.capacity != cap:
            from repro.core import AssocTensor
            base = AssocTensor(*_pad_prog(cap)(base.rows, base.cols,
                                               base.vals),
                               base.nnz, base.row_space, base.col_space,
                               None)
        st = _DeviceState(base=base, base_nnz=nnz, delta=self._empty,
                          delta_nnz=self._zero,
                          spaces=(base.row_space, base.col_space),
                          grown=((), ()), depth=0)
        self._dev = st.but(base=self._merged(st, count=False)[0])
        self.base = self._dev.base

    def _base_remap(self, st):
        """The base's ranks onto the delta's keyspaces, from the keys the
        delta added since the last fold."""
        spaces = (st.base.row_space, st.base.col_space)
        return _remap_aux(
            [np.searchsorted(s.keys, np.sort(np.concatenate(g)))
             if g else np.zeros(0, np.int64)
             for s, g in zip(spaces, st.grown)],
            [len(s) for s in spaces], self._no_points)

    def _merged(self, st, count: bool = True):
        """Whole-table base ⊕ delta of view ``st`` → (array, nnz)."""
        from repro.core import AssocTensor
        from .merge import merge_read

        rmap, cmap, remap = self._base_remap(st)
        out_cap = max(st.base.capacity,
                      _next_pow2(st.base_nnz + 2 * self._capd))
        r, c, v, nnz = merge_read(st.base, st.delta, rmap, cmap,
                                  self.aggregate, remap, out_cap)
        merged = AssocTensor(r, c, v, nnz, *st.spaces, None)
        with span("d4m.device_wait"):
            n_out = int(nnz)
        if count:
            n_in = st.base_nnz + int(st.delta_nnz)
            with self._lock:
                self.stats["merge_entries_in"] += n_in
                self.stats["merge_entries_out"] += n_out
        return merged, n_out

    def _insert_device(self, rows, cols, vals) -> None:
        """Rank, canonicalize and upload one batch, and fold it into the
        delta (write lock held)."""
        import jax.numpy as jnp
        from repro.core.coo import SENT
        from repro.core.select import invalidate_compiled_for
        from .merge import append

        if self._depth + len(rows) > self._capd:
            self._compact()
        st = self._dev
        rs, rnew, rr, qr = _ranked(st.spaces[0], st.grown[0], rows)
        cs, cnew, cr, qc = _ranked(st.spaces[1], st.grown[1], cols)
        v = vals.astype(np.float32)
        order = np.lexsort((cr, rr))
        rr, cr, qr, qc, v = (x[order] for x in (rr, cr, qr, qc, v))
        head = np.flatnonzero(np.r_[True, (rr[1:] != rr[:-1])
                                    | (cr[1:] != cr[:-1])])
        v = _UFUNCS[self.aggregate].reduceat(v, head)
        keep = head[v != 0]
        v = v[v != 0]
        cap = _next_pow2(len(rows))
        pad = cap - len(keep)

        def up(x, fill):
            return jnp.asarray(np.concatenate(
                [x, np.full(pad, fill, x.dtype)]))

        sent = int(SENT)
        batch = (up(rr[keep].astype(np.int32), sent),
                 up(cr[keep].astype(np.int32), sent), up(v, 0.0),
                 up(qr[keep], sent), up(qc[keep], sent))
        rmap, cmap, remap = _remap_aux(
            [np.searchsorted(s.keys, new) for s, new in
             zip(st.spaces, (rnew, cnew))],
            [len(s) for s in st.spaces], self._no_points)
        delta, nnz = append(st.base, st.delta, batch, rmap, cmap,
                            self.aggregate, remap)
        grown = tuple(g + ((new,) if len(new) else ())
                      for g, new in zip(st.grown, (rnew, cnew)))
        with _held(self._lock):
            self._dev = st.but(delta=delta, delta_nnz=nnz, spaces=(rs, cs),
                               grown=grown, depth=st.depth + len(rows))
            self._depth += len(rows)
        live = {s.digest for s in (rs, cs, st.base.row_space,
                                   st.base.col_space)}
        invalidate_compiled_for({s.digest for s in st.spaces} - live)

    # -- write path ----------------------------------------------------------
    def insert(self, rows, cols, vals) -> Dict[str, int]:
        """Apply one raw triple batch.  Host and dist tables buffer it (the
        dist layer routes each triple to its owning row shard by key
        interval — the zero-collective ingest path); a device table folds
        it into its device delta, a batch larger than the delta in
        delta-sized parts."""
        with span("d4m.ingest"):
            rows = np.asarray(rows)
            cols = np.asarray(cols)
            vals = np.asarray(vals)
            if not (len(rows) == len(cols) == len(vals)):
                raise ValueError(
                    f"batch arrays must have equal length, got "
                    f"{len(rows)}/{len(cols)}/{len(vals)}")
            if len(rows) == 0:
                return {"accepted": 0, "delta_depth": self._depth}
            if self.layer in ("device", "dist") and \
                    vals.dtype.kind not in "fiub":
                raise TypeError(
                    f"{self.layer} ingest requires numeric values, got "
                    f"dtype {vals.dtype}")
            if vals.dtype.kind in "fiub":
                vals = vals.astype(np.float64)
            with _held(self._write_lock):
                if self.layer == "device":
                    for i in range(0, len(rows), self._capd):
                        part = slice(i, i + self._capd)
                        self._insert_device(rows[part], cols[part],
                                            vals[part])
                with _held(self._lock):
                    if self.layer == "dist":
                        if len(self._bkeys):
                            shard = np.searchsorted(self._bkeys, rows,
                                                    side="right")
                        else:
                            shard = np.zeros(len(rows), dtype=np.int64)
                        for s in range(self._nshards):
                            m = shard == s
                            if m.any():
                                self._shard_batches[s].append(
                                    (rows[m], cols[m], vals[m]))
                    elif self.layer == "host":
                        self._batches.append((rows, cols, vals))
                    if self.layer != "device":
                        self._depth += len(rows)
                    now = time.monotonic()
                    if self._last_insert_t is not None:
                        self._gaps.append(now - self._last_insert_t)
                    self._last_insert_t = now
                    self.stats["inserts"] += 1
                    self.stats["insert_triples"] += len(rows)
                    return {"accepted": len(rows),
                            "delta_depth": self._depth}

    @property
    def delta_depth(self) -> int:
        return self._depth

    # -- read path -------------------------------------------------------------
    def select(self, row_sel, col_sel):
        """``table[row_sel, col_sel]`` of the current view.  A device table
        selects from the base and from the delta (each through
        ``AssocTensor._select_eager``) and ⊕-combines the two results on
        the host, base first (``d4m.merge``); other layers select from
        :meth:`snapshot`."""
        if self.layer != "device":
            from repro.core.expr import Select, Source
            return Select(Source(self.snapshot()), row_sel,
                          col_sel).collect()
        with _held(self._lock):
            self.stats["reads"] += 1
            st = self._dev
        parts = [st.base] + ([st.delta_tensor()] if st.depth else [])
        found = [p._select_eager((row_sel, col_sel)) for p in parts]
        found = [f.to_assoc() for f in found]
        with span("d4m.merge"):
            out = found[0]
            for f in found[1:]:
                out = out.combine(f, self.aggregate)
        return out

    def snapshot(self):
        """The queryable view: base ⊕ buffered delta.

        Memoized per (version, delta-depth): repeated reads between
        mutations reuse one merged array — the merge-on-read *hit* the
        stats report.  With an empty delta the base itself is returned
        (no copy, stable ``id`` ⇒ stable plan-cache keys).  The merge runs
        outside the lock, on the view bound at entry."""
        with _held(self._lock):
            self.stats["reads"] += 1
            if self._depth == 0:
                return self.base
            key = (self.version, self._depth)
            if self._snap is not None and self._snap[:2] == key:
                return self._snap[2]
            view = self._view()
        with span("d4m.merge"):
            merged = self._merge(view)
        with _held(self._lock):
            self.stats["merges"] += 1
            if (self.version, self._depth) == key:
                if self._snap is not None:
                    self._retired.append(self._snap[2])
                self._snap = (key[0], key[1], merged)
        return merged

    def _view(self):
        """The current state (lock held): what a merge needs."""
        if self.layer == "device":
            return self._dev
        if self.layer == "dist":
            return (self.base, [list(b) for b in self._shard_batches])
        return (self.base, list(self._batches))

    def _merge(self, view):
        if self.layer == "device":
            return self._merged(view)[0]
        return getattr(self, f"_merge_{self.layer}")(*view)

    def _merge_host(self, base, batches):
        from repro.core import Assoc
        r, c, v = (np.concatenate([b[k] for b in batches])
                   for k in range(3))
        delta = Assoc(r, c, v, aggregate=self.aggregate)
        return base.combine(delta, self.aggregate)

    def _union_spaces(self, base, d_rows, d_cols):
        """Union keyspaces + base rank maps (memoized in the keyspace
        layer); keeps the base space OBJECT when content is unchanged so
        digests and compile-cache keys stay put."""
        from repro.core import KeySpace
        loc = base.local
        rs, rmap, _ = loc.row_space.union(KeySpace(d_rows))
        cs, cmap, _ = loc.col_space.union(KeySpace(d_cols))
        if rs == loc.row_space:
            rs = loc.row_space
        if cs == loc.col_space:
            cs = loc.col_space
        rerank = rs is not loc.row_space or cs is not loc.col_space
        return rs, cs, rmap, cmap, rerank

    @staticmethod
    def _pad_ranks(r, c, v, cap: int):
        import jax.numpy as jnp
        from repro.core.sorted_ops import INT_SENTINEL
        pad = cap - len(r)
        sent = np.full(pad, INT_SENTINEL, np.int32)
        rj = jnp.asarray(np.concatenate([r.astype(np.int32), sent]))
        cj = jnp.asarray(np.concatenate([c.astype(np.int32), sent]))
        vj = jnp.asarray(np.concatenate(
            [v.astype(np.float32), np.zeros(pad, np.float32)]))
        return rj, cj, vj

    def _merge_dist(self, base, shard_batches):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import AssocTensor, DistAssoc
        from .merge import dist_merge

        per_shard = [self._shard_triples(base, b) for b in shard_batches]
        d_rows = np.concatenate([t[0] for t in per_shard])
        d_cols = np.concatenate([t[1] for t in per_shard])
        rs, cs, rmap, cmap, rerank = self._union_spaces(base, d_rows, d_cols)
        loc = base.local
        # new shard bounds: ranks of the old boundary KEYS in the union
        # space — key-interval ownership is the invariant, so the insert
        # routing and the rank partition stay consistent
        nb = np.empty(self._nshards + 1, dtype=np.int64)
        nb[0], nb[-1] = 0, len(rs)
        if len(self._bkeys):
            nb[1:-1] = np.searchsorted(rs.keys, self._bkeys, side="left")
        else:
            nb[1:-1] = len(rs)

        capd = _next_pow2(max((len(t[0]) for t in per_shard), default=8))
        drs, dcs, dvs = [], [], []
        for (r_k, c_k, v) in per_shard:
            rr, _ = rs.rank(r_k)
            cr, _ = cs.rank(c_k)
            dr, dc, dv = self._pad_ranks(rr, cr, v, capd)
            drs.append(dr)
            dcs.append(dc)
            dvs.append(dv)
        shard1 = NamedSharding(base.mesh, P("data", None))
        dr = jax.device_put(jnp.stack(drs), shard1)
        dc = jax.device_put(jnp.stack(dcs), shard1)
        dv = jax.device_put(jnp.stack(dvs), shard1)
        rm = jnp.asarray(rmap if rerank else np.zeros(1, np.int32))
        cm = jnp.asarray(cmap if rerank else np.zeros(1, np.int32))
        a_dict = {"rows": loc.rows, "cols": loc.cols, "vals": loc.vals,
                  "nnz": loc.nnz}
        out = dist_merge(base.mesh, a_dict, dr, dc, dv, rm, cm,
                         self.aggregate, rerank)
        new_local = AssocTensor(out["rows"], out["cols"], out["vals"],
                                out["nnz"], rs, cs, None)
        return DistAssoc(new_local, base.mesh, row_bounds=nb)

    @staticmethod
    def _shard_triples(base, batches):
        if not batches:
            e = base.local.row_space.keys[:0]
            return e, e, np.empty(0, np.float64)
        return (np.concatenate([b[0] for b in batches]),
                np.concatenate([b[1] for b in batches]),
                np.concatenate([b[2] for b in batches]))

    # -- compaction ----------------------------------------------------------
    def compact(self) -> Dict[str, int]:
        """Fold delta into a new base (reusing the memoized merge when the
        delta is unchanged), bump ``version``, and drop planner/compile
        cache entries keyed on the retired arrays.  Readers keep the old
        view until the swap; inserts wait."""
        with _held(self._write_lock):
            return self._compact()

    def _compact(self) -> Dict[str, int]:
        import jax
        from repro.core.plan import invalidate_plan_for
        from repro.core.select import invalidate_compiled_for

        with _held(self._lock):
            if self._depth == 0:
                return {"compacted": 0, "version": self.version}
            folded = self._depth
            snap = self._snap
            view = self._view()
        with span("d4m.table_compact"):
            if snap is not None and snap[:2] == (self.version, folded):
                new_base = snap[2]
                snap = None
            else:
                new_base = self._merge(view)
            if self.layer == "device":
                jax.block_until_ready(new_base)
                with span("d4m.device_wait"):
                    n_base = int(new_base.nnz)
        with _held(self._lock):
            retired = self._retired + [self.base]
            if snap is not None:
                retired.append(snap[2])
            self._retired = []
            self._snap = None
            self.base = new_base
            self._batches = []
            if self.layer == "dist":
                self._shard_batches = [[] for _ in range(self._nshards)]
                self._bkeys = _boundary_keys(new_base.local.row_space,
                                             new_base.row_bounds)
            if self.layer == "device":
                self._dev = _DeviceState(
                    base=new_base, base_nnz=n_base, delta=self._empty,
                    delta_nnz=self._zero,
                    spaces=(new_base.row_space, new_base.col_space),
                    grown=((), ()), depth=0)
            self._depth = 0
            self.version += 1
            self.stats["compactions"] += 1
        # invalidation outside the lock: pure cache maintenance.  Retired
        # object refs are held until here, so their ids cannot be reused
        # by unrelated arrays before the caches drop them.
        n_plans = invalidate_plan_for([id(a) for a in retired])
        stale = self._stale_digests(retired, new_base)
        invalidate_compiled_for(stale)
        return {"compacted": folded, "version": self.version,
                "plans_invalidated": n_plans}

    @staticmethod
    def _stale_digests(retired, new_base) -> set:
        def spaces(a):
            loc = getattr(a, "local", a)
            rs = getattr(loc, "row_space", None)
            cs = getattr(loc, "col_space", None)
            return [s for s in (rs, cs) if s is not None]

        live = {s.digest for s in spaces(new_base)}
        return {s.digest for a in retired for s in spaces(a)} - live

    def maybe_compact(self, idle_s: float = 0.25) -> bool:
        """Compact if the delta reached ``compact_threshold`` triples or
        the writer went idle: quiet for ``idle_s`` and for ``_IDLE_TIMES``
        its longest gap between batches among its last ``_GAPS``."""
        with self._lock:
            depth = self._depth
            if depth == 0 or self._last_insert_t is None:
                return False
            quiet = time.monotonic() - self._last_insert_t
            gap = max(self._gaps, default=0.0)
        if depth >= self.compact_threshold or \
                quiet >= max(idle_s, _IDLE_TIMES * gap):
            self.compact()
            return True
        return False

    def note_compact_error(self) -> None:
        """Count a failed background compaction (reported by ``info()``)."""
        with self._lock:
            self.stats["compact_errors"] += 1

    # -- telemetry -----------------------------------------------------------
    def info(self) -> Dict[str, Any]:
        with self._lock:
            reads = self.stats["reads"]
            merges = self.stats["merges"]
            return {
                "ingest": True, "layer": self.layer,
                "aggregate": self.aggregate, "version": self.version,
                "delta_depth": self._depth,
                "compact_threshold": self.compact_threshold,
                **{k: v for k, v in self.stats.items()},
                "merge_hit_rate": (
                    (reads - merges) / reads if reads else 0.0),
            }


def _remap_aux(points, sizes, none):
    """Rerank operands for :func:`repro.ingest.merge._rerank`: the
    insertion points per axis as ``"shift"`` operands when every axis has
    at most ``REMAP_SLOTS`` of them, else old → new rank maps."""
    import jax.numpy as jnp
    from repro.core.coo import SENT
    from .merge import REMAP_SLOTS

    if all(len(p) <= REMAP_SLOTS for p in points):
        aux = [none if not len(p) else jnp.asarray(np.concatenate(
            [np.sort(p).astype(np.int32),
             np.full(REMAP_SLOTS - len(p), int(SENT), np.int32)]))
            for p in points]
        return aux[0], aux[1], "shift"
    maps = []
    for p, n in zip(points, sizes):
        old = np.arange(_next_pow2(n), dtype=np.int64)
        maps.append(jnp.asarray(
            (old + np.searchsorted(np.sort(p), old, side="right"))
            .astype(np.int32)))
    return maps[0], maps[1], "map"


@functools.lru_cache(maxsize=None)
def _pad_prog(cap: int):
    """COO columns padded with sentinels to ``cap`` slots (one program
    per capacity)."""
    import jax
    import jax.numpy as jnp
    from repro.core.coo import SENT

    @jax.jit
    def pad(r, c, v):
        n = cap - r.shape[0]
        return (jnp.concatenate([r, jnp.full(n, SENT, r.dtype)]),
                jnp.concatenate([c, jnp.full(n, SENT, c.dtype)]),
                jnp.concatenate([v, jnp.zeros(n, v.dtype)]))

    return pad


class Compactor:
    """Background compaction: polls a registry's ingest tables and folds
    delta into base on a depth threshold (the table's own
    ``compact_threshold``) or when the writer goes idle
    (:meth:`IngestTable.maybe_compact`)."""

    def __init__(self, registry, *, interval_s: float = 0.05,
                 idle_s: float = 0.25):
        self.registry = registry
        self.interval_s = float(interval_s)
        self.idle_s = float(idle_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Compactor":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="d4m-ingest-compactor",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _loop(self) -> None:
        from repro.serve.wire import WireError

        while not self._stop.wait(self.interval_s):
            for name in self.registry.ingest_names():
                try:
                    table = self.registry.ingest_table(name)
                except WireError as exc:
                    if exc.code == "unknown_table":   # dropped mid-iteration
                        continue
                    raise
                try:
                    table.maybe_compact(idle_s=self.idle_s)
                except Exception:   # the loop outlives one bad table
                    table.note_compact_error()
                    _log.exception("background compaction of table %r "
                                   "failed", name)
