"""Useful work of a request, counted from the data, not from any
implementation: what any strategy has to compute and move at least."""
import numpy as np


def twohop_work(t, q) -> tuple:
    """Operations and bytes of ``(G[rows, :] ⊗.⊕ G).⊕(axis)`` on the
    reference table ``t``:

    * operations ``2·Σ_k nnz(A_sel[:, k])·nnz(G[k, :])`` (one ⊗ and one ⊕
      per product);
    * bytes: 12 per stored entry of ``A_sel`` and of the rows of ``G`` it
      touches (row, column, value), and 4 per element of the output
      vector."""
    idx = t.entries(q["rows"], None)
    ac = t.c[idx]
    pos = np.searchsorted(t.rkeys, t.ckeys)
    pos = np.minimum(pos, len(t.rkeys) - 1)
    col_to_row = np.where(t.rkeys[pos] == t.ckeys, pos, -1)
    k = col_to_row[ac]
    k = k[k >= 0]
    row_nnz = np.diff(t.rowptr)
    ops = 2 * int(row_nnz[k].sum())
    touched = np.unique(k)
    out_len = len(t.rkeys) if q["axis"] == 1 else len(t.ckeys)
    nbytes = 12 * (len(idx) + int(row_nnz[touched].sum())) + 4 * out_len
    return ops, nbytes
