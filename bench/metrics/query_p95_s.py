"""95th percentile (nearest rank) of the latencies ``query_p50_s``
reads."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_query_p50_s", Path(__file__).with_name("query_p50_s.py"))
_p50 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_p50)


def read(run):
    return _p50.pct(_p50.latencies(run), 95)
