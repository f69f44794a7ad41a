"""repro.core — the paper's contribution: D4M associative arrays in JAX.

* ``coo``          — the canonical COO/semiring triple-store core every
                     associative-array implementation builds on
                     (host ``canonicalize_np`` / device ``dedup_sorted_coo``).
* ``Assoc``        — paper-faithful host implementation (numpy/scipy).
* ``AssocTensor``  — TPU-native device implementation (padded COO, semirings).
* ``KeySpace``     — host key dictionaries backing device rank arrays.
* ``Semiring``     — the value algebras (⊕, ⊗, 0, 1).
* ``DistAssoc``    — mesh-sharded associative arrays (the Distributed D).
* ``expr``/``plan`` — lazy expression graphs + the planner/executor behind
                     them (``A.lazy()[sel] @ B.lazy()[sel] … .collect()``);
                     the eager operators are thin wrappers over one-node
                     graphs, so lazy and eager share a single code path.

Telemetry counters (and their reset helpers) are exported together so
benchmarks and tests can assert a fast path actually fired:
``CACHE_STATS`` (selector compilation), ``UNION_STATS`` (keyspace-union
memoization), ``DISPATCH_STATS`` (selection execution paths),
``TRANSFER_STATS`` (result copies to the host), ``COMPACT_STATS``
(selection compaction paths) and ``PLAN_STATS`` (expression
hash-consing + planner rewrites).
"""
from repro.kernels import reset_kernel_stats

from .assoc import Assoc
from .assoc_tensor import (AssocTensor, COMPACT_STATS, DISPATCH_STATS,
                           TRANSFER_STATS)
from .coo import (aggregate_runs, canonicalize_np, dedup_sorted_coo,
                  intersect_pairs_np, linearize_pairs_np, spgemm_np)
from .dist_assoc import DistAssoc
from .expr import (EwiseAdd, EwiseMul, LazyExpr, MatMul, Reduce, Select,
                   Source, Transpose, lazy)
from .keyspace import KeySpace, UNION_STATS, clear_union_cache
from .plan import PLAN_STATS, clear_plan_cache, reset_plan_stats
from .select import (All, CACHE_STATS, Keys, Mask, Match, Positions, Range,
                     Selector, StartsWith, Where, as_selector,
                     clear_compile_cache, compile_selector, reset_cache_stats)
from .semiring import (AND_OR, MAX_MIN, MAX_PLUS, MAX_TIMES, MIN_PLUS,
                       PLUS_TIMES, REGISTRY, STRING, Semiring, get_semiring,
                       mesh_combine, scatter_combine)
from .spgemm import matmul_reduce, plan_matmul
from .sorted_ops import (INT_SENTINEL, sorted_intersect,
                         sorted_intersect_padded, sorted_union,
                         sorted_union_padded)


def reset_all_stats():
    """Zero every telemetry counter in one call.

    Covers ``UNION_STATS`` (and drops the keyspace-union cache),
    ``CACHE_STATS`` (selector compilation — counters only; compiled
    selectors stay warm), ``DISPATCH_STATS`` (selection execution paths),
    ``TRANSFER_STATS`` (result copies to the host),
    ``COMPACT_STATS`` (selection compaction paths),
    ``PLAN_STATS`` (and drops the plan cache) and the kernels'
    ``KERNEL_STATS`` (impl resolutions per trace).  Tests get this
    between cases from the autouse fixture in ``tests/conftest.py``;
    benchmarks call it before a measured region.
    """
    clear_union_cache()
    reset_cache_stats()
    for stats in (DISPATCH_STATS, TRANSFER_STATS, COMPACT_STATS):
        for k in stats:
            stats[k] = 0
    reset_plan_stats()
    reset_kernel_stats()


__all__ = [
    "Assoc", "AssocTensor", "DistAssoc", "KeySpace", "Semiring",
    "get_semiring",
    "REGISTRY", "PLUS_TIMES", "MAX_PLUS", "MIN_PLUS", "MAX_MIN", "MAX_TIMES",
    "AND_OR", "STRING", "INT_SENTINEL", "sorted_union", "sorted_intersect",
    "sorted_union_padded", "sorted_intersect_padded",
    "aggregate_runs", "canonicalize_np", "dedup_sorted_coo",
    "intersect_pairs_np", "linearize_pairs_np", "spgemm_np",
    "matmul_reduce", "plan_matmul", "mesh_combine", "scatter_combine",
    "Selector", "Keys", "Range", "StartsWith", "Match", "Where", "Mask",
    "Positions", "All", "as_selector", "compile_selector",
    # lazy expressions + planner
    "LazyExpr", "Source", "Select", "EwiseAdd", "EwiseMul", "MatMul",
    "Reduce", "Transpose", "lazy",
    # telemetry counters + reset helpers
    "reset_all_stats",
    "PLAN_STATS", "reset_plan_stats", "clear_plan_cache",
    "CACHE_STATS", "clear_compile_cache", "reset_cache_stats",
    "UNION_STATS", "clear_union_cache",
    "DISPATCH_STATS", "TRANSFER_STATS", "COMPACT_STATS",
]
