"""Shared fixtures: a tiny copy of the benchmark's checkout (the same
files, with the configurations cut to CPU sizes) and kernels in Pallas
interpret mode."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {"paper18": {"n": 8}, "graph500-s14": {"SCALE": 9}}


def with_candidates(bench: dict) -> dict:
    """BENCHMARK.json with the cells of ``bench/candidates.json`` added,
    so their files are tested too."""
    cand = json.loads((BENCH / "candidates.json").read_text())
    for key in ("configs", "workloads", "per_layer"):
        bench[key] = bench[key] + cand[key]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in cand["end_to_end"]:
        if m["name"] in e2e:
            e2e[m["name"]]["workloads"] += m["workloads"]
        else:
            bench["end_to_end"].append(m)
    return bench


# A test cell whose mix holds every selector kind and select operation the
# generator offers, so that a later cell built from them as data alone is
# already tested against the reference and the served path.
KINDS_MIX = {"shape_seed": 7, "streams": [{"loop": "open", "senders": 4,
                                           "classes": [
    {"name": "keys", "share": 0.3, "op": "select", "table": "A",
     "axis": "rows", "sel": {"kind": "keys", "count": [1, 8], "zipf": 0.99}},
    {"name": "prefix", "share": 0.2, "op": "select", "table": "A",
     "axis": "rows", "sel": {"kind": "prefix", "digits": 2, "pool": 16}},
    {"name": "range", "share": 0.2, "op": "select", "table": "A",
     "axis": "rows", "sel": {"kind": "range", "lo": [10, 25]}},
    {"name": "col_prefix", "share": 0.1, "op": "select", "table": "B",
     "axis": "cols", "sel": {"kind": "prefix", "digits": 2, "pool": 16}},
    {"name": "degree", "share": 0.1, "op": "select_sum", "table": "A",
     "reduce_axis": 1, "sel": {"kind": "prefix", "digits": 2, "pool": 16}},
    {"name": "total", "share": 0.1, "op": "total", "table": "B"}]}]}
KINDS_CELL = {"rate_per_s": 8, "warmup": {"per_class": 2, "counts": [1, 8]},
              "compare": {"sample": 100000, "control": {"prec": "bf16"},
                          "limits": {"failed": 0, "wrong_entries": 0,
                                     "max_rel_gap": 1e-4,
                                     "answers_compared": 1}}}


def make_checkout(dst: Path, small=SMALL) -> Path:
    """``dst`` with ``BENCHMARK.json`` (candidate cells and the test cell
    ``paper18.kinds`` included) and a copy of ``bench/`` whose
    configurations are cut to ``small``."""
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = with_candidates(json.loads((REPO / "BENCHMARK.json")
                                       .read_text()))
    bench["workloads"].append({"name": "paper18.kinds", "config": "paper18",
                               "traffic": "kinds", "chips": 1,
                               "why": "every selector kind"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "query_p50_s")["workloads"].append("paper18.kinds")
    (dst / "bench/traffic/kinds.json").write_text(json.dumps(KINDS_MIX))
    (dst / "bench/workloads/paper18.kinds.json").write_text(
        json.dumps(KINDS_CELL))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    for c in bench["configs"]:
        path = dst / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(small.get(c["name"], {}))
        path.write_text(json.dumps(cfg))
    return dst


@pytest.fixture(scope="session")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="session")
def checkout_maker():
    """``make_checkout`` itself, for tests that need their own copy."""
    return make_checkout


@pytest.fixture
def no_cache(monkeypatch):
    """Runs in the test process leave JAX's compilation cache alone."""
    import harness
    monkeypatch.setattr(harness, "use_cache", lambda: "off")


@pytest.fixture
def interpret(monkeypatch):
    """Every kernel dispatch the served path makes runs its Pallas body in
    interpret mode."""
    import jax
    from repro import kernels
    from repro.kernels.bsr_spgemm import ops as bsr
    from repro.kernels.range_extract import ops as rng
    from repro.kernels.semiring_matmul import ops as smm
    from repro.kernels.sorted_merge import ops as srt

    orig = kernels.resolve_impl

    def resolve(kernel, impl):
        return orig(kernel, "interpret" if impl == "auto" else impl)

    for mod in (bsr, rng, smm, srt):
        monkeypatch.setattr(mod, "resolve_impl", resolve)
    jax.clear_caches()
    yield
    jax.clear_caches()
