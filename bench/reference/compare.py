"""Served answer against reference answer: two numbers per answer.

``wrong`` counts entries that are missing, extra, or on the wrong side of
the semiring's zero; ``gap`` is the widest relative gap
``|served − reference| / |reference|`` over entries both hold.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["compare_answer"]


def _rel(got: np.ndarray, want: np.ndarray) -> float:
    if len(want) == 0:
        return 0.0
    same = got == want        # also equal infinities
    d = np.abs(got - want)
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(same, 0.0, d / np.abs(want))
    r = np.where(np.isnan(r), np.inf, r)
    return float(r.max())


def compare_answer(served: dict, ref) -> tuple:
    """``(wrong, gap)`` of one served result body against one reference
    answer from :func:`~bench.reference.semantics.answer`."""
    kind = ref[0]
    if served.get("kind") != kind:
        n = len(ref[1]) if kind != "scalar" else 1
        return max(n, 1), math.inf
    if kind == "scalar":
        got, want = float(served["val"]), float(ref[1])
        return 0, _rel(np.array([got]), np.array([want]))
    if kind == "triples":
        got = dict(zip(zip(served["rows"], served["cols"]), served["vals"]))
        want = ref[1]
        wrong = len(got.keys() ^ want.keys())
        if served.get("truncated"):
            wrong += max(0, int(served["nnz"]) - len(got))
        both = list(got.keys() & want.keys())
        g = np.array([got[k] for k in both], np.float64)
        w = np.array([want[k] for k in both], np.float64)
        return wrong, _rel(g, w)
    want, zero = ref[1], ref[2]
    got = np.asarray(served["vals"], np.float64)
    if len(got) != len(want):
        return max(len(got), len(want)), math.inf
    support = (got == zero) != (want == zero)
    both = ~support & (want != zero)
    return int(support.sum()), _rel(got[both], want[both])
