"""LSM-style mutable overlay over a resident associative array.

:class:`IngestTable` wraps a base array from any of the three layers
(host ``Assoc``, device ``AssocTensor``, sharded ``DistAssoc``) with the
Accumulo tablet-server write path:

* ``insert(rows, cols, vals)`` appends a raw triple batch to a host-side
  **delta buffer** — pure list appends, no canonicalization, no device
  work, and for the sharded layer the batch is key-partitioned straight
  to the owning row shard (no global re-canonicalize, zero collectives);
* ``snapshot()`` is the **merge-on-read** view: base ⊕ delta through the
  compiled overlay-merge programs (:mod:`repro.ingest.merge`), memoized
  per (version, delta-depth) so repeated reads between mutations reuse
  one merge;
* ``compact()`` re-canonicalizes delta into a new base, bumps the table
  ``version``, and invalidates the planner/compile cache entries keyed on
  the retired arrays (:func:`repro.core.plan.invalidate_plan_for` /
  :func:`repro.core.select.invalidate_compiled_for`) so nothing pins dead
  state; :class:`Compactor` runs this in the background on a depth
  threshold or an idle timeout.

Aggregation semantics match a one-shot constructor over the concatenated
triples: ⊕ collisions combine base-first (the host ``combine`` order),
and device/dist layers restrict ⊕ to the commutative monoids
(``sum``/``min``/``max``) the unstable device sort supports; host tables
accept any ``Assoc`` aggregator (including order-sensitive ``"concat"``).
One seeded difference is inherited from the layers themselves: the host
constructor drops explicit-zero *raw* values before aggregation while the
device constructor drops zero *results* after it — ingest preserves each
layer's own semantics.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["IngestTable", "Compactor"]

_log = logging.getLogger(__name__)


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


class IngestTable:
    """Mutable LSM overlay (delta buffer + merge-on-read + compaction)."""

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

        self.base = base
        self.aggregate = aggregate
        self.compact_threshold = int(compact_threshold)
        self.name = name
        self.version = 0

        self._lock = threading.RLock()
        # host/device: one flat batch list; dist: one list per shard
        self._batches: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self._shard_batches: List[List[Tuple]] = []
        self._depth = 0
        self._last_insert_t = time.monotonic()
        self._snap: Optional[Tuple[int, int, Any]] = None  # (ver, depth, arr)
        self._retired: List[Any] = []   # superseded arrays, pending invalidation
        self.stats: Dict[str, int] = {
            "inserts": 0, "insert_triples": 0, "reads": 0, "merges": 0,
            "compactions": 0, "compact_errors": 0,
        }
        if self.layer == "dist":
            self._nshards = base.mesh.shape["data"]
            self._shard_batches = [[] for _ in range(self._nshards)]
            self._bkeys = _boundary_keys(base.local.row_space,
                                         base.row_bounds)

    # -- write path ----------------------------------------------------------
    def insert(self, rows, cols, vals) -> Dict[str, int]:
        """Append one raw triple batch (host work only: validates, and for
        the dist layer routes each triple to its owning row shard by key
        interval — the zero-collective ingest path)."""
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        vals = np.asarray(vals)
        if not (len(rows) == len(cols) == len(vals)):
            raise ValueError(
                f"batch arrays must have equal length, got "
                f"{len(rows)}/{len(cols)}/{len(vals)}")
        if len(rows) == 0:
            return {"accepted": 0, "delta_depth": self._depth}
        if self.layer in ("device", "dist") and vals.dtype.kind not in "fiub":
            raise TypeError(
                f"{self.layer} ingest requires numeric values, got dtype "
                f"{vals.dtype}")
        if vals.dtype.kind in "fiub":
            vals = vals.astype(np.float64)
        with self._lock:
            if self.layer == "dist":
                if len(self._bkeys):
                    shard = np.searchsorted(self._bkeys, rows, side="right")
                else:
                    shard = np.zeros(len(rows), dtype=np.int64)
                for s in range(self._nshards):
                    m = shard == s
                    if m.any():
                        self._shard_batches[s].append(
                            (rows[m], cols[m], vals[m]))
            else:
                self._batches.append((rows, cols, vals))
            self._depth += len(rows)
            self._last_insert_t = time.monotonic()
            self.stats["inserts"] += 1
            self.stats["insert_triples"] += len(rows)
            return {"accepted": len(rows), "delta_depth": self._depth}

    @property
    def delta_depth(self) -> int:
        return self._depth

    # -- read path (merge-on-read) -------------------------------------------
    def snapshot(self):
        """The queryable view: base ⊕ buffered delta.

        Memoized per (version, delta-depth): repeated reads between
        mutations reuse one merged array — the merge-on-read *hit* the
        stats report.  With an empty delta the base itself is returned
        (no copy, stable ``id`` ⇒ stable plan-cache keys)."""
        with self._lock:
            self.stats["reads"] += 1
            if self._depth == 0:
                return self.base
            if self._snap is not None and \
                    self._snap[:2] == (self.version, self._depth):
                return self._snap[2]
            self.stats["merges"] += 1
            merged = getattr(self, f"_merge_{self.layer}")()
            if self._snap is not None:
                self._retired.append(self._snap[2])
            self._snap = (self.version, self._depth, merged)
            return merged

    def _delta_triples(self):
        rows = np.concatenate([b[0] for b in self._batches])
        cols = np.concatenate([b[1] for b in self._batches])
        vals = np.concatenate([b[2] for b in self._batches])
        return rows, cols, vals

    def _merge_host(self):
        from repro.core import Assoc
        r, c, v = self._delta_triples()
        delta = Assoc(r, c, v, aggregate=self.aggregate)
        return self.base.combine(delta, self.aggregate)

    def _union_spaces(self, d_rows, d_cols):
        """Union keyspaces + base rank maps (memoized in the keyspace
        layer); keeps the base space OBJECT when content is unchanged so
        digests and compile-cache keys stay put."""
        from repro.core import KeySpace
        base = self.base if self.layer == "device" else self.base.local
        rs, rmap, _ = base.row_space.union(KeySpace(d_rows))
        cs, cmap, _ = base.col_space.union(KeySpace(d_cols))
        if rs == base.row_space:
            rs = base.row_space
        if cs == base.col_space:
            cs = base.col_space
        rerank = rs is not base.row_space or cs is not base.col_space
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

    def _merge_device(self):
        from repro.core import AssocTensor
        from .merge import merge_read

        d_rows, d_cols, d_vals = self._delta_triples()
        rs, cs, rmap, cmap, rerank = self._union_spaces(d_rows, d_cols)
        base = self.base if not rerank else \
            self.base.reranked(rs, cs, rmap, cmap)
        rr, _ = rs.rank(d_rows)
        cr, _ = cs.rank(d_cols)
        capd = _next_pow2(len(rr))
        dr, dc, dv = self._pad_ranks(rr, cr, d_vals, capd)
        r, c, v, nnz = merge_read(base, dr, dc, dv, self.aggregate,
                                  nrows=len(rs), ncols=len(cs))
        return AssocTensor(r, c, v, nnz, rs, cs, None)

    def _merge_dist(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import AssocTensor, DistAssoc
        from .merge import dist_merge

        per_shard = [self._shard_triples(s) for s in range(self._nshards)]
        d_rows = np.concatenate([t[0] for t in per_shard])
        d_cols = np.concatenate([t[1] for t in per_shard])
        rs, cs, rmap, cmap, rerank = self._union_spaces(d_rows, d_cols)
        base = self.base
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

    def _shard_triples(self, s: int):
        batches = self._shard_batches[s]
        if not batches:
            e = self.base.local.row_space.keys[:0]
            return e, e, np.empty(0, np.float64)
        return (np.concatenate([b[0] for b in batches]),
                np.concatenate([b[1] for b in batches]),
                np.concatenate([b[2] for b in batches]))

    # -- compaction ----------------------------------------------------------
    def compact(self) -> Dict[str, int]:
        """Fold delta into a new base (reusing the cached merge when the
        delta is unchanged), bump ``version``, and drop planner/compile
        cache entries keyed on the retired arrays."""
        from repro.core.plan import invalidate_plan_for
        from repro.core.select import invalidate_compiled_for

        with self._lock:
            if self._depth == 0:
                return {"compacted": 0, "version": self.version}
            folded = self._depth
            new_base = self.snapshot()
            retired = self._retired + [self.base]
            self._retired = []
            self._snap = None
            self.base = new_base
            self._batches = []
            if self.layer == "dist":
                self._shard_batches = [[] for _ in range(self._nshards)]
                self._bkeys = _boundary_keys(new_base.local.row_space,
                                             new_base.row_bounds)
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
        """Compact if the delta crossed the threshold or went idle."""
        with self._lock:
            depth = self._depth
            idle = time.monotonic() - self._last_insert_t
        if depth == 0:
            return False
        if depth >= self.compact_threshold or idle >= idle_s:
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


class Compactor:
    """Background compaction: polls a registry's ingest tables and folds
    delta into base on a depth threshold (the table's own
    ``compact_threshold``) or an idle timeout."""

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
