"""Query engine: worker pool, admission/batching queue, live metrics.

The execution model is the D4M 3.0 server loop grown onto the lazy
planner:

* **admission batching** — queued queries are *compatible* when they
  touch the same table set on the same layer(s).  A worker admitting work
  takes the oldest request plus up to ``max_batch - 1`` compatible queued
  requests and executes them back-to-back, so a burst of same-shape
  traffic runs against warm trace caches and a warm plan cache instead of
  interleaving with unrelated shapes (``DISPATCH``/jit caches are keyed
  by structure; interleaving thrashes them).  Batch sizes are recorded —
  ``/stats`` exposes the distribution.
* **cross-request plan caching** — every query executes through
  ``LazyExpr.collect()``, i.e. ``plan.optimize()`` memoized by the
  graph's structural key in ``_PLAN_CACHE``.  Resident tables make the
  ``Source`` identity stable, and the wire format preserves selector
  structure, so two clients sending the same query — or one client
  repeating it — plan once (``PLAN_STATS['plan_hits']`` counts this).
* **⊕-merged telemetry** — each worker logs into its own
  :class:`~repro.distributed.metrics.MetricsStore` (no cross-thread
  contention); a ``/stats`` read ⊕-merges the per-worker stores on
  demand — the D4M aggregation-on-collision semantics doing the
  cross-thread reduction that a conventional metrics library needs locks
  for.

Ingest batches (``POST /ingest``) flow through the same queue under
disjoint admission keys — ``("ingest", table)`` vs ``("query", ...)`` —
so a mutation never batches with reads on the table it mutates; queries
over ingest tables bind at execution time: a plain selection of one
ingest table reads its base and delta apart
(:meth:`~repro.ingest.IngestTable.select`), anything else its merge-on-read
snapshot.
When the registry holds ingest tables the engine also runs a background
:class:`~repro.ingest.Compactor`.

Each request carries a :class:`repro.trace.Record` of its ``d4m.*`` spans:
the HTTP thread adds wire decoding to it, the worker everything from
dequeue to result.  A response's ``timing`` holds ``queue_s``, ``exec_s``
and ``total_s`` and, from the spans, ``decode_s`` (wire decode),
``selector_s`` (host selector compile and uploads), ``wait_s`` (waiting
for the device's results), ``to_host_s`` (copying them to the host) and
``format_s`` (building the JSON payload, copies excluded) and, for
ingest tables, ``ingest_s`` (an insert's own work, lock waits excluded),
``merge_s`` (combining what a read found in the base and the delta) and
``table_wait_s`` (waiting for the ingest table's lock).

The execution entry point :func:`serve_execute` carries a ``@contract``:
shard-local serve queries inherit the zero-collective / never-densify
budgets of the ops they dispatch, and ``tools/d4mcheck`` sweeps the serve
path like any other entry point.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from repro.analysis.contracts import contract
from repro.distributed.metrics import MetricsStore
from repro.trace import Record, recording, span

from .registry import TableRegistry
from .wire import WireError, from_wire, ingest_from_wire, table_names

__all__ = ["Engine", "QueryError", "serve_execute", "format_result"]


class QueryError(Exception):
    """Execution-time failure of a structurally valid query (wraps the
    underlying exception with a structured code for the transport)."""

    def __init__(self, code: str, message: str):
        self.code = code
        super().__init__(message)

    def to_dict(self) -> dict:
        return {"code": self.code, "message": str(self)}


@contract(collectives=0, densify=False, name="serve.execute",
          note="shard-local serve queries: zero collectives, no "
               "densification — budgets inherited from the dispatched ops")
def serve_execute(expr):
    """THE server execution entry point: optimize (plan-cached) +
    execute one decoded expression graph."""
    return expr.collect()


# response ``timing`` field ← (span, whether only the span's own time)
_STAGES = (("decode_s", "d4m.decode", False),
           ("selector_s", "d4m.selector", False),
           ("wait_s", "d4m.device_wait", False),
           ("to_host_s", "d4m.to_host", False),
           ("format_s", "d4m.format", True),
           ("ingest_s", "d4m.ingest", True),
           ("merge_s", "d4m.merge", False),
           ("table_wait_s", "d4m.table_wait", False))


def format_result(res, limit: Optional[int] = None) -> Dict[str, Any]:
    """Layer-native result → JSON-safe payload.

    Arrays return COO triples (gathered to host — the result of a query
    is small by design; resident operands never move), reductions return
    dense vectors or scalars.
    """
    with span("d4m.format"):
        return _format(res, limit)


def _format(res, limit):
    import jax.numpy as jnp

    from repro.core import Assoc, AssocTensor, DistAssoc
    from repro.core.assoc_tensor import count_transfer

    if isinstance(res, (AssocTensor, DistAssoc)):
        res = res.to_assoc()
    if isinstance(res, Assoc) or res is None:
        if res is None:
            res = Assoc()
        r, c, v = res.triples()
        n = len(r)
        truncated = limit is not None and n > limit
        if truncated:
            r, c, v = r[:limit], c[:limit], v[:limit]
        count_transfer(entries_returned=len(r))
        return {"kind": "triples", "nnz": n,
                "rows": [x.item() if hasattr(x, "item") else x
                         for x in r.tolist()],
                "cols": [x.item() if hasattr(x, "item") else x
                         for x in c.tolist()],
                "vals": v.tolist(), "truncated": truncated}
    if isinstance(res, (jnp.ndarray, np.ndarray)):
        arr = np.asarray(res)
        if arr.ndim == 0:
            return {"kind": "scalar", "val": float(arr)}
        return {"kind": "vector", "n": int(arr.shape[0]),
                "vals": [float(x) for x in arr]}
    if isinstance(res, (float, int, np.floating, np.integer)):
        return {"kind": "scalar", "val": float(res)}
    raise QueryError("bad_result",
                     f"unformattable result type {type(res).__name__}")


class _Request:
    """One admitted request (query or ingest batch) + its future-ish
    result.  ``expr`` is ``None`` for ingest requests and for queries
    over ingest tables (those bind at execution time so the merge-on-read
    snapshot reflects every mutation admitted ahead of them)."""

    __slots__ = ("payload", "expr", "options", "batch_key", "t_enqueue",
                 "event", "result", "error", "timing", "batch_size",
                 "kind", "data", "spans")

    def __init__(self, payload, expr, options, batch_key, spans: Record, *,
                 kind: str = "query", data=None):
        self.payload = payload
        self.expr = expr
        self.options = options
        self.batch_key = batch_key
        self.spans = spans
        self.kind = kind
        self.data = data
        self.t_enqueue = time.perf_counter()
        self.event = threading.Event()
        self.result: Optional[dict] = None
        self.error: Optional[Exception] = None
        self.timing: Dict[str, float] = {}
        self.batch_size = 1

    def wait(self, timeout: Optional[float] = None) -> dict:
        if not self.event.wait(timeout):
            raise QueryError("timeout", "query did not complete in time")
        if self.error is not None:
            raise self.error
        assert self.result is not None
        return self.result


class Engine:
    """Worker pool + admission queue over a :class:`TableRegistry`."""

    def __init__(self, registry: TableRegistry, *, workers: int = 4,
                 max_batch: int = 8, batch_window_s: float = 0.0,
                 default_limit: Optional[int] = 100_000,
                 compact_interval_s: float = 0.05,
                 compact_idle_s: float = 0.25):
        self.registry = registry
        self.workers = max(1, int(workers))
        self.max_batch = max(1, int(max_batch))
        self.batch_window_s = float(batch_window_s)
        self.default_limit = default_limit
        self.compact_interval_s = float(compact_interval_s)
        self.compact_idle_s = float(compact_idle_s)
        self._compactor = None
        self._queue: deque = deque()
        self._cv = threading.Condition()
        self._threads: List[threading.Thread] = []
        self._stop = False
        self._started = False
        # per-worker stores: single-writer each, ⊕-merged on /stats reads
        self._stores = [MetricsStore("sum") for _ in range(self.workers)]
        self._latencies: deque = deque(maxlen=2048)   # recent, for p50/p99
        self._lat_lock = threading.Lock()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "Engine":
        if self._started:
            return self
        self._started = True
        self._stop = False
        for i in range(self.workers):
            t = threading.Thread(target=self._worker_loop, args=(i,),
                                 name=f"d4m-serve-worker-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        if self.registry.ingest_names() and self.compact_interval_s > 0:
            from repro.ingest import Compactor
            self._compactor = Compactor(
                self.registry, interval_s=self.compact_interval_s,
                idle_s=self.compact_idle_s).start()
        return self

    def stop(self) -> None:
        if self._compactor is not None:
            self._compactor.stop()
            self._compactor = None
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads.clear()
        self._started = False

    def __enter__(self) -> "Engine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- admission ----------------------------------------------------------
    def _admission_key(self, payload) -> tuple:
        """Compatibility key: ``("query", table names, their layers)``.
        Same key ⇒ same resident operands and same execution layer ⇒
        batchable.  The ``"query"`` tag keeps the key space disjoint from
        ingest admission keys (``("ingest", table)``), so a mutation never
        batches with reads on the table it mutates."""
        tables = table_names(payload)
        if not tables:
            raise WireError("bad_payload",
                            "query references no tables")
        layers = tuple(self.registry.layer_of(n) for n in tables)
        return ("query", tables, layers)

    def submit(self, payload, options: Optional[dict] = None) -> _Request:
        """Validate + enqueue one wire payload; returns the request handle
        (``.wait()`` for the result).  Malformed payloads raise
        :class:`WireError` synchronously — they never enter the queue.

        Queries over read-only tables bind their ``Source`` arrays here
        (plan-cache keys resolve once); queries touching an ingest table
        only *validate* here and bind at execution time, so the snapshot
        they read reflects mutations admitted ahead of them."""
        if not self._started:
            raise RuntimeError("engine not started")
        rec = Record()
        with recording(rec), span("d4m.decode"):
            from_wire(payload, resolve=None)    # structural validation first
            key = self._admission_key(payload)  # then table-name checks
            tables = key[1]
            if any(self.registry.is_ingest(n) for n in tables):
                expr = None                     # bind at execution time
            else:
                expr = from_wire(payload, resolve=self.registry.resolve)
        req = _Request(payload, expr, dict(options or {}), key, rec)
        with self._cv:
            self._queue.append(req)
            self._cv.notify()
        return req

    def submit_ingest(self, payload,
                      options: Optional[dict] = None) -> _Request:
        """Validate + enqueue one ingest batch (the POST /ingest body).
        Decoding and table checks are synchronous — ``WireError`` codes
        ``bad_batch`` / ``not_ingestable`` / ``unknown_table`` never enter
        the queue.  The admission key is ``("ingest", table)``: disjoint
        from every query key, so a mutation batch is only ever admitted
        with other mutations of the same table (applied in queue order).

        Ordering: within one synchronous client connection ingest→query
        is read-your-writes (the client holds the ingest response before
        it sends the read).  Across connections the only guarantee is
        queue order of *admission*; concurrent workers may overlap an
        ingest with an independent query."""
        if not self._started:
            raise RuntimeError("engine not started")
        rec = Record()
        with recording(rec), span("d4m.decode"):
            name, rows, cols, vals = ingest_from_wire(payload)
            self.registry.ingest_table(name)    # raises if not ingestable
        req = _Request(payload, None, dict(options or {}),
                       ("ingest", name), rec, kind="ingest",
                       data=(name, rows, cols, vals))
        with self._cv:
            self._queue.append(req)
            self._cv.notify()
        return req

    def query(self, payload, options: Optional[dict] = None,
              timeout: Optional[float] = 120.0) -> dict:
        """Synchronous submit + wait (the in-process client path)."""
        return self.submit(payload, options).wait(timeout)

    def ingest(self, payload, options: Optional[dict] = None,
               timeout: Optional[float] = 120.0) -> dict:
        """Synchronous ingest submit + wait."""
        return self.submit_ingest(payload, options).wait(timeout)

    # -- the worker ---------------------------------------------------------
    def _take_batch(self) -> List[_Request]:
        """Admit the oldest request + up to ``max_batch - 1`` compatible
        queued requests (same admission key), preserving queue order for
        the rest."""
        with self._cv:
            while not self._queue and not self._stop:
                self._cv.wait(timeout=0.1)
            if self._stop and not self._queue:
                return []
            head = self._queue.popleft()
            batch = [head]
            if self.max_batch > 1:
                keep = deque()
                while self._queue and len(batch) < self.max_batch:
                    r = self._queue.popleft()
                    if r.batch_key == head.batch_key:
                        batch.append(r)
                    else:
                        keep.append(r)
                self._queue.extendleft(reversed(keep))
        if (len(batch) < self.max_batch and self.batch_window_s > 0):
            # optional accumulation window: let same-shape stragglers join
            time.sleep(self.batch_window_s)
            with self._cv:
                keep = deque()
                while self._queue and len(batch) < self.max_batch:
                    r = self._queue.popleft()
                    if r.batch_key == head.batch_key:
                        batch.append(r)
                    else:
                        keep.append(r)
                self._queue.extendleft(reversed(keep))
        return batch

    def _worker_loop(self, idx: int) -> None:
        while True:
            batch = self._take_batch()
            if not batch:
                if self._stop:
                    return
                continue
            # re-read per iteration: reset_stats() swaps the store list
            store = self._stores[idx]
            store.log(0, {"batches": 1.0, "batch_n": float(len(batch))})
            for req in batch:
                req.batch_size = len(batch)
                with recording(req.spans), span("d4m.execute"):
                    self._execute(req, store)

    def _execute(self, req: _Request, store: MetricsStore) -> None:
        """Run one admitted request and hand its result to the waiter."""
        t0 = time.perf_counter()
        try:
            if req.kind == "ingest":
                name, rows, cols, vals = req.data
                table = self.registry.ingest_table(name)
                out = table.insert(rows, cols, vals)
                body = {"kind": "ingest", "table": name,
                        "version": table.version, **out}
                store.log(0, {"ingests": 1.0,
                              "ingest_triples": float(out["accepted"])})
            else:
                if req.expr is None:    # ingest-table query: bind now
                    res = self._execute_ingest_query(req)
                else:
                    res = serve_execute(req.expr)
                limit = req.options.get("limit", self.default_limit)
                body = format_result(res, limit=limit)
        except (WireError, QueryError) as exc:
            req.error = exc
        except Exception as exc:   # execution-time type errors etc.
            req.error = QueryError("execution_error",
                                   f"{type(exc).__name__}: {exc}")
        t1 = time.perf_counter()
        if req.error is None:
            spans = req.spans
            req.timing = {
                "queue_s": round(t0 - req.t_enqueue, 6),
                "exec_s": round(t1 - t0, 6),
                "total_s": round(t1 - req.t_enqueue, 6),
                **{field: round((spans.own if own else spans.total)
                                .get(name, 0.0), 6)
                   for field, name, own in _STAGES},
            }
            req.result = {"result": body, "timing": req.timing,
                          "batch": req.batch_size}
        store.log(0, {"requests": 1.0,
                      "errors": 1.0 if req.error else 0.0})
        with self._lat_lock:
            self._latencies.append(t1 - req.t_enqueue)
        req.event.set()

    def _execute_ingest_query(self, req: _Request):
        """A query over ingest tables, bound at execution time.  A plain
        selection of one ingest table is the table's own
        :meth:`~repro.ingest.IngestTable.select` (base and delta apart,
        results ⊕-combined); anything else runs on the merge-on-read
        snapshots."""
        from repro.core.expr import Select
        from repro.ingest import IngestTable

        from .wire import TableRef

        with span("d4m.decode"):
            expr = from_wire(req.payload, resolve=None)
        if isinstance(expr, Select) and isinstance(expr.child, TableRef):
            table = self.registry.get(expr.child.name)
            if isinstance(table, IngestTable):
                return table.select(expr.row_sel, expr.col_sel)
        with span("d4m.decode"):
            req.expr = from_wire(req.payload, resolve=self.registry.resolve)
        return serve_execute(req.expr)

    # -- telemetry ----------------------------------------------------------
    def metrics(self) -> MetricsStore:
        """⊕-merge of every worker's store (one ``combine`` per worker)."""
        merged = MetricsStore("sum")
        for s in self._stores:
            merged = merged.merge(s)
        return merged

    def stats(self) -> Dict[str, Any]:
        """The /stats body: server counters + core telemetry dicts."""
        from repro.core import (CACHE_STATS, COMPACT_STATS, DISPATCH_STATS,
                                PLAN_STATS, TRANSFER_STATS, UNION_STATS)
        from repro.kernels import KERNEL_STATS

        merged = self.metrics()
        server: Dict[str, float] = {}
        if merged.table.nnz():
            _, names, vals = merged.table.triples()
            for n, v in zip(names.tolist(), vals.tolist()):
                server[str(n)] = server.get(str(n), 0.0) + float(v)
        with self._lat_lock:
            lats = sorted(self._latencies)
        if lats:
            server["p50_s"] = float(np.percentile(lats, 50))
            server["p99_s"] = float(np.percentile(lats, 99))
        if server.get("batches"):
            server["batch_mean"] = server["batch_n"] / server["batches"]
        out = {
            "server": server,
            "plan": dict(PLAN_STATS),
            "cache": dict(CACHE_STATS),
            "union": dict(UNION_STATS),
            "dispatch": dict(DISPATCH_STATS),
            "transfer": dict(TRANSFER_STATS),
            "compact": dict(COMPACT_STATS),
            "kernels": dict(KERNEL_STATS),
            "queue_depth": len(self._queue),
            "workers": self.workers,
        }
        ingest_names = self.registry.ingest_names()
        if ingest_names:
            out["ingest"] = {n: self.registry.ingest_table(n).info()
                             for n in ingest_names}
        return out

    def reset_stats(self) -> None:
        """Zero core + server telemetry (a fresh measurement window —
        the bench harness calls this between hot/cold mixes)."""
        from repro.core import reset_all_stats
        reset_all_stats()
        for name in self.registry.ingest_names():
            self.registry.ingest_table(name).reset_stats()
        self._stores = [MetricsStore("sum") for _ in range(self.workers)]
        with self._lat_lock:
            self._latencies.clear()
