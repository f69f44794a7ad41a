"""Plain numpy/float64 reference of the served D4M semantics.

It imports nothing of the program and takes nothing the program made: it
builds its tables from the same generated triples and answers the same
request descriptions (``bench/traffic.py``).  ``prec="bf16"`` (values
rounded to bfloat16) and ``key_bytes`` (keys matched by their first
bytes) give the controls that a comparison has to fail.
"""
from .semantics import Table, answer, ingest_candidates
from .compare import compare_answer

__all__ = ["Table", "answer", "ingest_candidates", "compare_answer"]
