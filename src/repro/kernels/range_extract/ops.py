"""Jitted wrapper: COO keep-masks for compiled rank-range selections.

``range_mask`` is the device half of the selector algebra's range fast
path (:mod:`repro.core.select`): the host compiles a selector to
``[lo, hi)`` rank bounds, the device masks its padded COO triples — the
selection never densifies.  Dispatch mirrors ``sorted_merge.ops``:
Pallas on TPU, the jnp ref elsewhere, ``impl="interpret"`` in tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sorted_ops import INT_SENTINEL
from repro.kernels import resolve_impl
from .ref import range_mask_ref
from .range_extract import range_mask_pallas


@partial(jax.jit, static_argnames=("impl",))
def range_mask(rows: jnp.ndarray, cols: jnp.ndarray, bounds: jnp.ndarray,
               *, impl: str = "auto") -> jnp.ndarray:
    """keep[t] ∈ {0, 1}: triple t inside the (row, col) rank box.

    ``rows``/``cols``: int32[N] sentinel-padded; ``bounds``: int32 array
    of 4 entries (row_lo, row_hi, col_lo, col_hi), any shape.
    """
    impl = resolve_impl("range_mask", impl)
    bounds = bounds.reshape(1, 4).astype(jnp.int32)
    if impl == "ref":
        return range_mask_ref(rows, cols, bounds)
    n = rows.shape[0]
    pad = (-n) % 1024 if n > 1024 else (-n) % 8
    rp = jnp.pad(rows, (0, pad), constant_values=INT_SENTINEL)
    cp = jnp.pad(cols, (0, pad), constant_values=INT_SENTINEL)
    bn = min(1024, rp.shape[0])
    keep = range_mask_pallas(rp, cp, bounds, bn=bn,
                             interpret=(impl == "interpret"))
    return keep[:n]
