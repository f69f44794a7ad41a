"""The paper's own workload (Figs 3–7): associative-array benchmarks.

Six synthetic datasets exactly as §III.A describes: for each n in [5, 18],
8·2^n uniformly random integer keys in [0, 2^n] (cast to strings), numeric
values in [0, 100], and random length-8 strings.  ``make_dataset(n)``
regenerates them deterministically; ``benchmarks/run.py`` consumes this.
"""
from __future__ import annotations

import numpy as np

N_RANGE = range(5, 19)          # paper: 5 ≤ n ≤ 18
ENTRIES_PER_ROW = 8             # ≈ 8 nonempty entries per row
SEED = 20220926                 # HPEC'22 publication date


def make_dataset(n: int, seed: int = SEED):
    """Returns dict with rows/rows2/cols/cols2/num_vals/str_vals for size n."""
    rng = np.random.default_rng(seed + n)
    m = ENTRIES_PER_ROW * (2 ** n)
    def ints():
        return rng.integers(0, 2 ** n, size=m)
    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    def strs():
        idx = rng.integers(0, 26, size=(m, 8))
        return np.ascontiguousarray(letters[idx]).view("<U8").ravel()
    return {
        "rows": ints().astype(str),
        "rows2": ints().astype(str),
        "cols": ints().astype(str),
        "cols2": ints().astype(str),
        "num_vals": rng.integers(0, 100, size=m).astype(np.float64),
        "str_vals": strs(),
    }
