"""One run of one cell: tables, server, warm-up, window, reference, result.

Everything that belongs to a configuration, a traffic mix, a cell or a
metric is a file found by its name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` names its generator,
  ``bench/data/<generator>.py`` (``generate(cfg, seed)``);
* ``bench/traffic/<mix>.json`` is read by ``bench/traffic.py``;
* ``bench/workloads/<cell>.json`` holds the cell's rate, warm-up,
  comparison sample, control and limits;
* ``bench/metrics/<metric>.py`` (or ``<prefix>.py`` for a name
  ``<prefix>.<suffix>``) is the metric's reader, ``read(run)``.

The program supplies only the system under test: the tables it builds,
the server it runs, its ``/stats`` counters and its device trace.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402
from reference import Table, answer, compare_answer, ingest_candidates  # noqa: E402

DRAIN_S = 60.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class SpecError(ValueError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path):
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as exc:
        raise SpecError(f"missing {path}") from exc


def load_cell(checkout: Path, workload: str) -> dict:
    """Every file of one cell, found by name."""
    bench = _json(checkout / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    root = checkout / "bench"
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return {"checkout": checkout, "root": root, "workload": w,
            "config": _json(checkout / configs[w["config"]]["file"]),
            "cell": _json(root / "workloads" / f"{workload}.json"),
            "mix": _json(root / "traffic" / f"{w['traffic']}.json"),
            "e2e": e2e, "per_layer": per_layer}


def reader(root: Path, name: str):
    """The ``read(run)`` function of a metric, by its name."""
    for cand in (name, name.split(".")[0]):
        path = root / "metrics" / f"{cand}.py"
        if path.exists():
            return load_module(path, "bench_metric_" +
                               cand.replace(".", "_")).read
    raise SpecError(f"no reader bench/metrics/{name}.py")


def peak_for(root: Path, kind: str) -> dict:
    peaks = _json(root / "peaks.json")["devices"]
    if kind not in peaks:
        raise SpecError(f"device kind {kind!r} has no entry in "
                        f"bench/peaks.json ({sorted(peaks)})")
    return peaks[kind]


def tables_used(mix: dict) -> list:
    return sorted({c["table"] for st in mix["streams"]
                   for c in st["classes"]})


# -- request descriptions -> the program's wire format ------------------------

def to_expr(q: dict):
    from repro.core import Keys, Range, StartsWith
    from repro.serve import TableRef

    def sel(s):
        if s is None:
            return slice(None)
        if s["kind"] == "keys":
            return Keys(list(s["keys"]))
        if s["kind"] == "prefix":
            return StartsWith(s["p"])
        if s["kind"] == "range":
            return Range(s["lo"], s["hi"])
        raise SpecError(f"unknown selector {s}")

    t = TableRef(q["table"])
    op = q["op"]
    if op == "select":
        return t[sel(q.get("rows")), sel(q.get("cols"))]
    if op == "select_sum":
        return t[sel(q["rows"]), :].sum(axis=q["axis"])
    if op == "twohop":
        from repro.core.semiring import get_semiring
        sr = get_semiring(q["semiring"])
        return t[sel(q["rows"]), :].matmul(t, sr).sum(axis=q["axis"],
                                                      semiring=sr)
    if op == "total":
        return t.sum(axis=None)
    raise SpecError(f"unknown op {op!r}")


def to_request(rid: int, q: dict, due=None) -> list:
    from repro.serve import to_wire
    if q["op"] == "ingest":
        return [rid, "/ingest", None, due]
    body = json.dumps({"expr": to_wire(to_expr(q)),
                       "options": {"limit": None}})
    return [rid, "/query", body, due]


# -- the run ------------------------------------------------------------------

class Run:
    """What a metric reader may read after the window."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def queries(self, done_by=None):
        """Window records of queries (not ingest batches)."""
        return [r for r in self.records if r["desc"]["op"] != "ingest"
                and (done_by is None or r.get("done", math.inf) <= done_by)]


def use_cache() -> str:
    """JAX's persistent compilation cache where the program places it
    (``JAX_COMPILATION_CACHE_DIR``, else a fixed directory of the
    checkout), with every program cached, so only a checkout's first run
    of a cell compiles."""
    import jax
    from repro.serve.server import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


_COMPILES = {"n": 0, "s": 0.0, "on": False}


def _count_compiles():
    if _COMPILES["on"]:
        return
    from jax import monitoring

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES["n"] += 1
            _COMPILES["s"] += duration

    monitoring.register_event_duration_secs_listener(listen)
    _COMPILES["on"] = True


def _pct(xs, q):
    """Nearest-rank percentile; a failed request is +inf."""
    xs = sorted(xs)
    if not xs:
        return None
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def number_streams(raw: list) -> list:
    """Give every request of ``traffic.build_streams`` its id: streams in
    order, a closed stream client by client.  Returns per stream its
    ``ids`` and ``descs`` in id order and, closed, the ids per client."""
    out, rid = [], 0
    for st in raw:
        if st["loop"] == "open":
            descs = st["requests"]
            out.append({"loop": "open", "senders": st["senders"],
                        "ids": list(range(rid, rid + len(descs))),
                        "descs": descs})
        else:
            descs, clients = [], []
            for per in st["requests"]:
                clients.append(list(range(rid + len(descs),
                                          rid + len(descs) + len(per))))
                descs += per
            out.append({"loop": "closed", "clients": clients,
                        "ids": [i for c in clients for i in c],
                        "descs": descs})
        rid += len(descs)
    return out


def sample_ids(streams: list, cell: dict, seed: int) -> set:
    """The ids whose answers a run keeps and compares: a sample of
    ``cell["compare"]["sample"]`` drawn from the seed, or all."""
    ids = [i for st in streams for i in st["ids"]]
    n = int(cell["compare"]["sample"])
    if n >= len(ids):
        return set(ids)
    rng = np.random.default_rng([int(seed) % (2 ** 63), 7])
    return set(int(x) for x in rng.choice(ids, size=n, replace=False))


def build_tables(spec: dict, data: dict, log):
    from repro.core import AssocTensor
    from repro.serve import TableRegistry
    reg = TableRegistry()
    ing = spec["cell"].get("ingest")
    for name in tables_used(spec["mix"]):
        t = data["tables"][name]
        tensor = AssocTensor.from_triples(t["rows"], t["cols"], t["vals"],
                                          aggregate=t["aggregate"])
        tensor.rows.block_until_ready()
        if ing and ing["table"] == name:
            from repro.ingest import IngestTable
            tensor = IngestTable(tensor, aggregate=t["aggregate"],
                                 compact_threshold=int(
                                     ing["compact_threshold"]))
        reg.register(name, tensor)
        log(f"table {name}: {len(t['rows'])} triples")
    return reg


class Cell:
    """A cell's tables resident on the device and served over HTTP; each
    :meth:`window` drives one measured window from a fresh load
    generator."""

    def __init__(self, checkout: Path, workload: str, seed: int, *,
                 require_tpu: bool = True, peak_kind: str | None = None,
                 log=None):
        self.log = log or (lambda msg: print(f"[bench] {msg}",
                                             file=sys.stderr, flush=True))
        self.spec = spec = load_cell(checkout, workload)
        self.checkout, self.seed = checkout, int(seed)
        import jax
        from repro.serve import start_server
        cache = use_cache()
        self.devices = jax.devices()
        self.dev = dev = self.devices[0]
        chips = int(spec["workload"]["chips"])
        if require_tpu:
            if dev.platform != "tpu":
                raise NoChip(f"needs a TPU; JAX found platform "
                             f"{dev.platform!r} ({len(self.devices)} "
                             f"device(s))")
            if len(self.devices) < chips:
                raise NoChip(f"the cell needs {chips} chips; JAX found "
                             f"{len(self.devices)}")
        self.peaks = peak_for(spec["root"], peak_kind or dev.device_kind)
        self.log(f"{workload} seed {seed}: {dev.device_kind} "
                 f"x{len(self.devices)}, compile cache {cache}")
        _count_compiles()
        cfg = spec["config"]
        gen = load_module(spec["root"] / "data" / f"{cfg['generator']}.py",
                          "bench_data_" + cfg["generator"])
        t0 = time.monotonic()
        self.data = gen.generate(cfg, seed)
        t1 = time.monotonic()
        self.reg = build_tables(spec, self.data, self.log)
        self.log(f"set-up: data {t1 - t0} s, tables "
                 f"{time.monotonic() - t1} s")
        self.server = start_server(self.reg)

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
            self.server = None
            self.reg = None
            gc.collect()

    def _plan(self, seconds: float, rate):
        from repro.serve.wire import ingest_to_wire
        spec, ctx, seed = self.spec, self.data["ctx"], self.seed
        streams = number_streams(traffic.build_streams(
            spec["mix"], ctx, seed, seconds, rate=rate))
        by_id = {i: q for st in streams for i, q in zip(st["ids"],
                                                         st["descs"])}
        plan_streams = []
        for st in streams:
            if st["loop"] == "open":
                plan_streams.append({
                    "loop": "open", "senders": st["senders"],
                    "requests": [to_request(i, by_id[i], by_id[i]["due"])
                                 for i in st["ids"]]})
            else:
                plan_streams.append({
                    "loop": "closed",
                    "requests": [[to_request(i, by_id[i]) for i in ids]
                                 for ids in st["clients"]]})
        warm = traffic.warmup_requests(spec["mix"], ctx, seed,
                                       spec["cell"]["warmup"])
        writer = None
        for q in warm + list(by_id.values()):
            if q["op"] == "ingest":
                writer = {"seed": seed, "batch": q["batch"],
                          "key_hi": q["key_hi"], "vals": q["vals"],
                          "template": ingest_to_wire(q["table"], ["0"],
                                                     ["0"], [1.0])}
                break
        keep = sample_ids(streams, spec["cell"], seed)
        plan = {"url": self.server.url, "seconds": seconds,
                "drain_s": DRAIN_S,
                "warmup": [to_request(10 ** 9 + i, q)
                           for i, q in enumerate(warm)],
                "streams": plan_streams, "keep": sorted(keep),
                "ingest": writer}
        return plan, by_id

    def window(self, seconds: float, trace: bool, *, t_start: float,
               rate=None) -> dict:
        """Warm up, then drive one window; returns what was recorded."""
        import jax
        rate = rate if rate is not None else \
            self.spec["cell"].get("rate_per_s")
        plan, by_id = self._plan(seconds, rate)
        proc = subprocess.Popen(
            [sys.executable, str(BENCH / "loadgen.py")],
            cwd=str(self.checkout), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        tdir = None
        try:
            proc.stdin.write(json.dumps(plan) + "\n")
            proc.stdin.flush()
            t_w = time.monotonic()
            ready = _expect(proc, "ready")
            self.log(f"warm-up: {ready}, {time.monotonic() - t_w} s")
            self.server.engine.reset_stats()
            n_comp0 = _COMPILES["n"]
            setup_s = time.monotonic() - t_start
            if trace:
                tdir = tempfile.mkdtemp(prefix="bench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tdir, profiler_options=opts)
                t_tr0 = time.monotonic()
            proc.stdin.write("go\n")
            proc.stdin.flush()
            _expect(proc, "closed")
            compiles = _COMPILES["n"] - n_comp0
            if trace:
                window_s = time.monotonic() - t_tr0
                jax.profiler.stop_trace()
            done = _expect(proc, "done")
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        stats = self.server.engine.stats()
        mem = self.dev.memory_stats() or {}
        trace_sum = None
        if trace:
            reduce_trace = load_module(BENCH / "trace.py",
                                       "bench_trace").reduce_trace
            try:
                trace_sum = reduce_trace(tdir, window_s)
            finally:
                import shutil
                shutil.rmtree(tdir, ignore_errors=True)
            if trace_sum is None:      # no device plane held an operation
                trace_sum = {"busy_s": 0.0, "window_s": window_s,
                             "device_ops": [], "idle_gaps": [], "chips": 0}
        for r in done["records"] + done["lost"]:
            r["desc"] = by_id[r["id"]]
        return {"records": done["records"], "lost": done["lost"],
                "t0": done["t0"], "t_close": done["t_close"],
                "seconds": seconds, "setup_s": setup_s, "stats": stats,
                "trace": trace_sum, "compiles": compiles,
                "warm_batches": ready.get("ingest_next", 0),
                "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}


def run_cell(checkout: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_tpu: bool = True,
             peak_kind: str | None = None, log=None) -> dict:
    """One run; returns the result object (``correct`` … ``checks``)."""
    cell = Cell(checkout, workload, seed, require_tpu=require_tpu,
                peak_kind=peak_kind, log=log)
    try:
        w = cell.window(seconds, trace, t_start=t_start)
    finally:
        cell.close()          # the program's state goes before the reference
    return evaluate(cell, w, trace)


def evaluate(cell: Cell, w: dict, trace: bool) -> dict:
    """Reference, checks and metrics of one window."""
    spec, data, seed = cell.spec, cell.data, cell.seed
    ref_tables = {}

    def ref_table(name):
        if name not in ref_tables:
            t = data["tables"][name]
            ref_tables[name] = Table(t["rows"], t["cols"], t["vals"],
                                     t["aggregate"])
        return ref_tables[name]

    recs, lost = w["records"], w["lost"]
    checks = _check(spec, recs, lost, w["warm_batches"], seed, ref_table)
    run = Run(records=recs, lost=lost, t0=w["t0"], t_close=w["t_close"],
              seconds=w["seconds"], setup_s=w["setup_s"], stats=w["stats"],
              trace=w["trace"], peaks=cell.peaks, data=data,
              ref_table=ref_table, spec=spec)
    metrics = {}
    for m in (spec["per_layer"] if trace else spec["e2e"]):
        v = reader(spec["root"], m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = cell.dev
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices),
              "memory_peak_bytes": w["memory_peak_bytes"]}
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": len(recs) + len(lost),
           "failed": checks["failed"]["value"],
           "metrics": metrics, "device": device}
    if trace:
        t = w["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    lags = [r["send"] - r["due"] for r in recs if r.get("due") is not None]
    out["window_compiles"] = w["compiles"]
    out["generator_lag_p95_s"] = _pct(lags, 95) if lags else 0.0
    out["plan"] = w["stats"].get("plan", {})
    out["kernels"] = w["stats"].get("kernels", {})
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def _expect(proc, tag: str, timeout: float | None = None) -> dict:
    line = proc.stdout.readline()
    if not line.startswith(tag + " "):
        raise RuntimeError(f"load generator: expected {tag!r}, got "
                           f"{line[:200]!r} (exit {proc.poll()})")
    return json.loads(line[len(tag) + 1:])


# -- correctness --------------------------------------------------------------

def _check(spec, recs, lost, warm_batches, seed, table):
    """Compare every kept answer with the reference (``table(name)`` gives
    a reference table); returns each number compared beside its limit."""
    limits = spec["cell"]["compare"]["limits"]
    ing = spec["cell"].get("ingest")

    writes = sorted((r for r in recs if r["desc"]["op"] == "ingest"),
                    key=lambda r: r["batch"])
    batches = []
    if writes:
        spec_w = writes[0]["desc"]
        top = max(r["batch"] for r in writes) + 1
        batches = [traffic.ingest_batch(seed, i, spec_w)
                   for i in range(top)]
    acked = sorted(r["done"] for r in writes if r.get("status") == 200)
    sent = sorted(r["send"] for r in writes)

    wrong, gap, compared = 0, 0.0, 0
    for r in recs:
        body = r.get("body")
        q = r["desc"]
        if body is None or q["op"] == "ingest":
            continue
        if ing and q["table"] == ing["table"]:
            lo = warm_batches + int(np.searchsorted(acked, r["send"],
                                                    side="right"))
            hi = warm_batches + int(np.searchsorted(sent, r["done"]))
            best = None
            for _, ref in ingest_candidates(table(q["table"]), batches, q,
                                            lo, hi):
                w, g = compare_answer(body, ref)
                if best is None or (w, g) < best:
                    best = (w, g)
            w, g = best
        else:
            w, g = compare_answer(body, answer({q["table"]:
                                                table(q["table"])}, q))
        wrong += w
        gap = max(gap, g)
        compared += 1
    failed = sum(r.get("status") != 200 for r in recs) + len(lost)
    checks = {
        "failed": {"value": failed, "limit": limits["failed"],
                   "ok": failed <= limits["failed"]},
        "wrong_entries": {"value": wrong, "limit": limits["wrong_entries"],
                          "ok": wrong <= limits["wrong_entries"]},
        "max_rel_gap": {"value": gap, "limit": limits["max_rel_gap"],
                        "ok": gap <= limits["max_rel_gap"]},
        "answers_compared": {"value": compared,
                             "limit": limits["answers_compared"],
                             "ok": compared >= limits["answers_compared"]},
    }
    return checks
