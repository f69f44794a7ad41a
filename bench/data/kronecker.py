"""Graph500 Kronecker graph (Graph500 specification, generator section).

``2^SCALE`` vertices and ``edgefactor·2^SCALE`` edges drawn bit by bit
with initiator probabilities A, B, C (D = 1 − A − B − C); vertex labels
are permuted and the edge list shuffled, as the reference generator does.
The table ``G`` holds the graph symmetrised (each edge in both
directions) without self-loops, keys as vertex-id strings, and weights
uniform in (0, 1] (the SSSP kernel's weights; a stored zero is no entry
in D4M, so 0 is excluded); parallel edges combine by ``min``.
"""
import numpy as np


def generate(cfg: dict, seed: int) -> dict:
    scale = int(cfg["SCALE"])
    n = 2 ** scale
    m = int(cfg["edgefactor"]) * n
    a, b, c = float(cfg["A"]), float(cfg["B"]), float(cfg["C"])
    rng = np.random.default_rng(int(seed) % (2 ** 63))
    ij = np.zeros((2, m), np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > (c_norm * ii + a_norm * ~ii)
        ij += (1 << bit) * np.stack([ii, jj]).astype(np.int64)
    ij = rng.permutation(n)[ij]
    ij = ij[:, rng.permutation(m)]
    # float32 weights, so the device stores exactly what the reference reads
    w = (1.0 - rng.random(m, dtype=np.float32)).astype(np.float64)
    keep = ij[0] != ij[1]
    src, dst, w = ij[0][keep], ij[1][keep], w[keep]
    rows = np.concatenate([src, dst])
    cols = np.concatenate([dst, src])
    vals = np.concatenate([w, w])
    return {
        "tables": {
            "G": {"rows": rows.astype(str), "cols": cols.astype(str),
                  "vals": vals, "aggregate": cfg["aggregate"]},
        },
        "ctx": {"roots": {"G": np.unique(rows)}, "key_universe": n},
    }
