"""The paper's own dataset (arXiv:2209.00602, HPEC'22, §III.A).

For size ``n``: ``8·2^n`` triples per table, row and column keys uniform
integers in ``[0, 2^n)`` written as strings, numeric values uniform
integers in ``[0, 100)``.  Table ``A`` is ``(rows, cols, vals)`` and
``B`` is ``(rows2, cols2, vals)``, drawn in the order of the paper's
generator.  Its random length-8 string values are not drawn: no cell
serves them.  The seed is the run's ``--seed``.
"""
import numpy as np


def generate(cfg: dict, seed: int) -> dict:
    n = int(cfg["n"])
    rng = np.random.default_rng(int(seed) % (2 ** 63))
    m = int(cfg["entries_per_row"]) * 2 ** n

    def ints():
        return rng.integers(0, 2 ** n, size=m)

    rows, rows2, cols, cols2 = ints(), ints(), ints(), ints()
    lo, hi = cfg["value_range"]
    vals = rng.integers(lo, hi, size=m).astype(np.float64)
    agg = cfg["aggregate"]
    return {
        "tables": {
            "A": {"rows": rows.astype(str), "cols": cols.astype(str),
                  "vals": vals, "aggregate": agg},
            "B": {"rows": rows2.astype(str), "cols": cols2.astype(str),
                  "vals": vals, "aggregate": agg},
        },
        "ctx": {"key_universe": 2 ** n,
                "row_keys": {"A": np.unique(rows), "B": np.unique(rows2)}},
    }
