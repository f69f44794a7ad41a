"""Associative-array semantics, straightforwardly, in numpy.

A table is the set of (row key, column key) → value entries that the
generated triples give once duplicates are combined by the table's ⊕
(``sum`` or ``min``) and zero results are dropped.  Its row (column)
keyspace is every distinct row (column) key of the triples, in sorted
string order: a reduction returns one value per key of that keyspace.

Selectors: ``keys`` (exact members), ``prefix`` (``str.startswith``),
``range`` (``lo <= key <= hi`` in string order, both ends included).

Two controls, each a step a later change might take, which a run's check
has to fail: ``prec="bf16"`` rounds every stored value, intermediate and
result to bfloat16; ``key_bytes=k`` matches ``keys`` selectors by the
first ``k`` bytes of each key, as a fixed-width packed key would.
"""
from __future__ import annotations

import numpy as np

__all__ = ["Table", "answer", "ingest_candidates", "rounder"]

_INF = np.inf


def rounder(prec: str):
    """Identity for float64; round-to-nearest bfloat16 for the control."""
    if prec == "f64":
        return lambda x: np.asarray(x, np.float64)
    if prec == "bf16":
        import ml_dtypes
        return lambda x: np.asarray(x, np.float64).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {prec!r}")


def _combine(codes: np.ndarray, vals: np.ndarray, agg: str):
    """Unique codes and their ⊕-combined values."""
    uniq, inv = np.unique(codes, return_inverse=True)
    if agg == "sum":
        out = np.bincount(inv, weights=vals, minlength=len(uniq))
    elif agg == "min":
        out = np.full(len(uniq), _INF)
        np.minimum.at(out, inv, vals)
    else:
        raise ValueError(f"unknown aggregate {agg!r}")
    return uniq, out


class Table:
    """Canonical entries of one table, with row-major offsets."""

    def __init__(self, rows, cols, vals, aggregate: str, prec: str = "f64",
                 key_bytes: int | None = None):
        self.q = rounder(prec)
        self.key_bytes = key_bytes
        self.aggregate = aggregate
        rows = np.asarray(rows).astype(str)
        cols = np.asarray(cols).astype(str)
        self.rkeys, rc = np.unique(rows, return_inverse=True)
        self.ckeys, cc = np.unique(cols, return_inverse=True)
        code = rc.astype(np.int64) * len(self.ckeys) + cc
        uniq, v = _combine(code, self.q(vals), aggregate)
        keep = v != 0.0
        uniq, v = uniq[keep], self.q(v[keep])
        self.r = (uniq // len(self.ckeys)).astype(np.int64)
        self.c = (uniq % len(self.ckeys)).astype(np.int64)
        self.v = v
        self.rowptr = np.searchsorted(self.r, np.arange(len(self.rkeys) + 1))

    # -- selectors ----------------------------------------------------------
    @staticmethod
    def key_mask(keys: np.ndarray, sel, key_bytes: int | None = None
                 ) -> np.ndarray:
        if sel is None:
            return np.ones(len(keys), bool)
        kind = sel["kind"]
        if kind == "keys":
            want = np.asarray(sel["keys"], dtype=str)
            if key_bytes:
                clip = f"<U{int(key_bytes)}"
                return np.isin(keys.astype(clip), want.astype(clip))
            return np.isin(keys, want)
        if kind == "prefix":
            return np.char.startswith(keys, sel["p"])
        if kind == "range":
            return (keys >= sel["lo"]) & (keys <= sel["hi"])
        raise ValueError(f"unknown selector kind {kind!r}")

    def entries(self, rows_sel=None, cols_sel=None) -> np.ndarray:
        """Indices of the entries a selection keeps."""
        rm = self.key_mask(self.rkeys, rows_sel, self.key_bytes)
        if rows_sel is None:
            idx = np.arange(len(self.v))
        else:
            sel_rows = np.flatnonzero(rm)
            lo, hi = self.rowptr[sel_rows], self.rowptr[sel_rows + 1]
            idx = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)]
                                 or [np.zeros(0, np.int64)])
        if cols_sel is not None:
            cm = self.key_mask(self.ckeys, cols_sel, self.key_bytes)
            idx = idx[cm[self.c[idx]]]
        return idx

    def triples(self, idx) -> dict:
        return dict(zip(zip(self.rkeys[self.r[idx]].tolist(),
                            self.ckeys[self.c[idx]].tolist()),
                        self.v[idx].tolist()))


# -- requests -----------------------------------------------------------------

def _twohop(t: Table, q: dict) -> np.ndarray:
    """⊕-reduce over ``axis`` of ``G[rows, :] ⊗.⊕ G`` without forming it:
    plus_times  axis 1: Σ_k A[i,k]·(Σ_j G[k,j]);  axis 0: Σ_k (Σ_i A[i,k])·G[k,j]
    min_plus    axis 1: min_k A[i,k]+(min_j G[k,j]); axis 0: min_k (min_i A[i,k])+G[k,j]
    The contraction matches A's column keys to G's row keys by string."""
    rnd = t.q
    idx = t.entries(q["rows"], None)
    ar, ac, av = t.r[idx], t.c[idx], t.v[idx]
    # A's column key -> G's row code (-1: no such row, contributes nothing)
    pos = np.searchsorted(t.rkeys, t.ckeys)
    pos = np.minimum(pos, len(t.rkeys) - 1)
    col_to_row = np.where(t.rkeys[pos] == t.ckeys, pos, -1)
    k = col_to_row[ac]
    ok = k >= 0
    ar, av, k = ar[ok], av[ok], k[ok]
    plus = q["semiring"] == "plus_times"
    zero = 0.0 if plus else _INF
    nr, nc = len(t.rkeys), len(t.ckeys)
    if q["axis"] == 1:
        if plus:
            red = rnd(np.bincount(t.r, weights=t.v, minlength=nr))
            out = np.bincount(ar, weights=rnd(av * red[k]), minlength=nr)
        else:
            red = np.full(nr, _INF)
            np.minimum.at(red, t.r, t.v)
            out = np.full(nr, _INF)
            np.minimum.at(out, ar, rnd(av + red[k]))
        return rnd(out)
    if plus:
        ak = rnd(np.bincount(k, weights=av, minlength=nr))
        out = np.bincount(t.c, weights=rnd(ak[t.r] * t.v), minlength=nc)
    else:
        ak = np.full(nr, _INF)
        np.minimum.at(ak, k, av)
        out = np.full(nc, _INF)
        np.minimum.at(out, t.c, rnd(ak[t.r] + t.v))
    out = rnd(out)
    out[np.isnan(out)] = zero
    return out


def answer(tables: dict, q: dict):
    """The reference answer to one request description:
    ``("triples", dict)``, ``("vector", array, zero)`` or
    ``("scalar", float)``."""
    t = tables[q["table"]]
    op = q["op"]
    if op == "select":
        return ("triples", t.triples(t.entries(q.get("rows"),
                                               q.get("cols"))))
    if op == "select_sum":
        if q["axis"] != 1:
            raise ValueError("select_sum reduces along axis 1 only")
        rm = Table.key_mask(t.rkeys, q["rows"], t.key_bytes)
        rowsum = t.q(np.bincount(t.r, weights=t.v, minlength=len(t.rkeys)))
        return ("vector", np.where(rm, rowsum, 0.0), 0.0)
    if op == "twohop":
        zero = 0.0 if q["semiring"] == "plus_times" else _INF
        return ("vector", _twohop(t, q), zero)
    if op == "total":
        return ("scalar", float(t.q(t.v.sum())))
    raise ValueError(f"no reference for op {op!r}")


def ingest_candidates(base: Table, batches: list, q: dict, lo: int, hi: int,
                      prec: str = "f64"):
    """Reference answers of a read against an ingest table after each
    admissible number ``j`` of applied batches, ``lo <= j <= hi``:
    ``base ⊕ batches[:j]`` (⊕ = sum).  Yields ``(j, answer)``."""
    rnd = rounder(prec)
    op = q["op"]
    if op == "total":
        sums = np.cumsum([0.0] + [float(b[2].sum()) for b in batches[:hi]])
        for j in range(lo, hi + 1):
            yield j, ("scalar", float(rnd(base.v.sum() + sums[j])))
        return
    if op != "select" or q.get("cols") is not None:
        raise ValueError(f"no ingest reference for {q}")
    sel = q["rows"]
    idx = base.entries(sel, None)
    acc: dict = {}
    for key, v in base.triples(idx).items():
        acc[key] = v
    hits = []
    for r, c, v in batches[:hi]:
        m = Table.key_mask(np.asarray(r), sel, base.key_bytes)
        hits.append((np.asarray(r)[m], np.asarray(c)[m], np.asarray(v)[m]))
    state = dict(acc)
    for j in range(0, hi + 1):
        if j > 0:
            for r, c, v in zip(*(x.tolist() for x in hits[j - 1])):
                state[(r, c)] = state.get((r, c), 0.0) + v
        if j >= lo:
            yield j, ("triples", {k: float(rnd(v)) for k, v in state.items()
                                  if v != 0.0})
