"""Records the ``data/tpu_spans_trace`` fixture on one chip: three served
one-row reads of a 1,024-triple table under the profiler, after one read
that compiles.  Keep only the ``.xplane.pb`` it writes.

    python3 bench/tests/record_spans_trace.py <directory>
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))


def main(out: str) -> None:
    import jax
    import numpy as np
    from repro.core import AssocTensor, Keys
    from repro.serve import D4MClient, TableRef, TableRegistry, start_server

    rng = np.random.default_rng(0)
    n = 1024
    reg = TableRegistry()
    reg.register("A", AssocTensor.from_triples(
        rng.integers(0, 128, n).astype(str),
        rng.integers(0, 128, n).astype(str),
        rng.integers(1, 100, n).astype(np.float32), aggregate="sum"))
    srv = start_server(reg)
    try:
        client = D4MClient(srv.url, timeout=300)
        client.query(TableRef("A")[Keys(["0"]), :])
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        for key in ("1", "2", "3"):
            client.query(TableRef("A")[Keys([key]), :])
        jax.profiler.stop_trace()
    finally:
        srv.close()


if __name__ == "__main__":
    main(sys.argv[1])
