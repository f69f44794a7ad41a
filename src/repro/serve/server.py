"""HTTP transport for the query engine (stdlib ``ThreadingHTTPServer``).

Endpoints (all JSON):

* ``POST /query``  — body ``{"expr": <wire payload>, "options": {...}}``;
  200 → ``{"result": ..., "timing": {...}, "batch": k}``; malformed
  payloads → 400 with ``{"error": {"code", "message"}}`` (never a bare
  500 for wire errors).
* ``POST /ingest`` — body ``{"ingest": {"table", "rows", "cols",
  "vals"}}`` (see :func:`~repro.serve.wire.ingest_to_wire`); 200 →
  ``{"result": {"kind": "ingest", "accepted", "delta_depth",
  "version"}}``; malformed batches → 400 ``bad_batch``, read-only
  tables → 400 ``not_ingestable``.
* ``GET /tables``  — registry listing (name/layer/shape/nnz per table).
* ``GET /stats``   — server request/latency/batch metrics ⊕-merged across
  workers + the core telemetry dicts (``plan``/``cache``/``union``/
  ``dispatch``/``transfer``) — ``plan.plan_hits`` is the cross-request
  plan-cache signal.
* ``POST /stats/reset`` — zero the measurement window (bench harness).
* ``GET /health``  — liveness + table count.

Every ``POST`` runs inside a ``d4m.request`` span and writes its answer
inside ``d4m.encode`` (:mod:`repro.trace`), so a ``jax.profiler`` trace of
the server shows each request beside the device work it launched.

CLI::

    python -m repro.serve.server --tables tables.json --port 8642 \
        --workers 4 --max-batch 8

where ``tables.json`` is a list of registry spec dicts (see
:mod:`~repro.serve.registry`), or inline JSON starting with ``[``/``{``.
"""
from __future__ import annotations

import argparse
import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from typing import Optional

from repro.trace import span

from .engine import Engine, QueryError
from .registry import TableRegistry
from .wire import WireError

__all__ = ["D4MServer", "start_server", "main", "use_compile_cache"]

_MAX_BODY = 64 * 1024 * 1024
_CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it —
    nothing is set in code.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` (git-ignored), so every process started
    from this checkout finds what an earlier one compiled.  Call it at
    start-up, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class _Handler(BaseHTTPRequestHandler):
    server_version = "d4m-serve/1"
    protocol_version = "HTTP/1.1"

    # silence per-request stderr logging (the server is long-lived)
    def log_message(self, fmt, *args):  # noqa: D102
        pass

    @property
    def engine(self) -> Engine:
        return self.server.engine          # type: ignore[attr-defined]

    def _send(self, status: int, body: dict) -> None:
        with span("d4m.encode"):
            data = json.dumps(body).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    def _error(self, status: int, code: str, message: str) -> None:
        self._send(status, {"error": {"code": code, "message": message}})

    def do_GET(self) -> None:  # noqa: N802
        try:
            if self.path == "/health":
                self._send(200, {"status": "ok",
                                 "tables": len(self.engine.registry)})
            elif self.path == "/tables":
                self._send(200,
                           {"tables": self.engine.registry.list_info()})
            elif self.path == "/stats":
                self._send(200, self.engine.stats())
            else:
                self._error(404, "not_found", f"no endpoint {self.path!r}")
        except Exception as exc:   # pragma: no cover - defensive
            self._error(500, "internal", f"{type(exc).__name__}: {exc}")

    def do_POST(self) -> None:  # noqa: N802
        with span("d4m.request"):
            self._post()

    def _post(self) -> None:
        try:
            if self.path == "/stats/reset":
                self.engine.reset_stats()
                self._send(200, {"status": "reset"})
                return
            if self.path not in ("/query", "/ingest"):
                self._error(404, "not_found", f"no endpoint {self.path!r}")
                return
            length = int(self.headers.get("Content-Length", 0))
            if length <= 0 or length > _MAX_BODY:
                self._error(400, "bad_payload",
                            f"Content-Length {length} out of range")
                return
            try:
                body = json.loads(self.rfile.read(length))
            except (ValueError, UnicodeDecodeError) as exc:
                self._error(400, "bad_payload", f"invalid JSON: {exc}")
                return
            if not isinstance(body, dict):
                self._error(400, "bad_payload", "body must be a JSON dict")
                return
            options = body.get("options") or {}
            if not isinstance(options, dict):
                self._error(400, "bad_payload", "'options' must be a dict")
                return
            try:
                if self.path == "/ingest":
                    # accept either a bare wire payload or {"ingest": ...}
                    # nested like /query's {"expr": ...}
                    payload = body if "ingest" in body else body.get("expr")
                    req = self.engine.submit_ingest(payload, options)
                else:
                    if "expr" not in body:
                        self._error(400, "bad_payload",
                                    "body must be {'expr': <wire payload>, "
                                    "'options': {...}?}")
                        return
                    req = self.engine.submit(body["expr"], options)
                out = req.wait(timeout=float(options.get("timeout_s", 120)))
            except WireError as exc:
                self._error(400, exc.code, str(exc))
                return
            except QueryError as exc:
                status = 504 if exc.code == "timeout" else 422
                self._error(status, exc.code, str(exc))
                return
            self._send(200, out)
        except Exception as exc:   # pragma: no cover - defensive
            self._error(500, "internal", f"{type(exc).__name__}: {exc}")


class D4MServer(ThreadingHTTPServer):
    """HTTP server owning an :class:`Engine` (and through it the resident
    table registry)."""

    daemon_threads = True

    def __init__(self, registry: TableRegistry, host: str = "127.0.0.1",
                 port: int = 0, *, workers: int = 4, max_batch: int = 8,
                 batch_window_s: float = 0.0):
        self.engine = Engine(registry, workers=workers, max_batch=max_batch,
                             batch_window_s=batch_window_s)
        super().__init__((host, port), _Handler)
        self._serve_thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self.server_address[1]

    @property
    def url(self) -> str:
        return f"http://{self.server_address[0]}:{self.port}"

    def start_background(self) -> "D4MServer":
        self.engine.start()
        self._serve_thread = threading.Thread(
            target=self.serve_forever, name="d4m-serve-http", daemon=True)
        self._serve_thread.start()
        return self

    def close(self) -> None:
        self.shutdown()
        self.engine.stop()
        self.server_close()
        if self._serve_thread is not None:
            self._serve_thread.join(timeout=5.0)
            self._serve_thread = None


def start_server(registry: TableRegistry, *, host: str = "127.0.0.1",
                 port: int = 0, workers: int = 4, max_batch: int = 8,
                 batch_window_s: float = 0.0) -> D4MServer:
    """Boot a server on a background thread; ``port=0`` picks a free
    port.  Caller owns ``server.close()``."""
    return D4MServer(registry, host, port, workers=workers,
                     max_batch=max_batch,
                     batch_window_s=batch_window_s).start_background()


def _load_specs(arg: str):
    if arg.lstrip().startswith(("[", "{")):
        specs = json.loads(arg)
    else:
        with open(arg) as f:
            specs = json.load(f)
    if isinstance(specs, dict):
        specs = [specs]
    return specs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="D4M query server over resident associative arrays")
    ap.add_argument("--tables", required=True,
                    help="path to a JSON list of table specs, or inline "
                         "JSON ('[{\"name\": ...}]')")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8642)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--batch-window-ms", type=float, default=0.0)
    args = ap.parse_args(argv)

    use_compile_cache()
    registry = TableRegistry.from_specs(_load_specs(args.tables))
    server = D4MServer(registry, args.host, args.port,
                       workers=args.workers, max_batch=args.max_batch,
                       batch_window_s=args.batch_window_ms / 1e3)
    server.engine.start()
    print(f"[d4m-serve] {len(registry)} table(s) resident "
          f"({', '.join(registry.names())}); serving on {server.url} "
          f"with {args.workers} worker(s), max_batch={args.max_batch}")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
