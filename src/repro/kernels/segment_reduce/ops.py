"""Jitted wrapper: constructor-style aggregation of sorted COO runs."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_impl
from .ref import segment_scan_ref
from .segment_reduce import segment_scan_pallas


@partial(jax.jit, static_argnames=("combine", "impl"))
def segment_scan(keys, vals, *, combine: str = "sum", impl: str = "auto"):
    """Inclusive segmented ⊕-scan; run-last positions hold run totals."""
    impl = resolve_impl("segment_scan", impl)
    if impl == "ref":
        return segment_scan_ref(keys, vals, combine=combine)
    n = keys.shape[0]
    pad = (-n) % 256
    kp = jnp.pad(keys, (0, pad), constant_values=jnp.int32(2**31 - 1))
    vp = jnp.pad(vals, (0, pad))
    out = segment_scan_pallas(kp, vp, combine=combine, bn=min(1024, kp.shape[0]),
                              interpret=(impl == "interpret"))
    return out[:n]


@partial(jax.jit, static_argnames=("combine", "impl"))
def aggregate_runs(keys, vals, *, combine: str = "sum", impl: str = "auto"):
    """(keys, aggregated value at each run head, head mask)."""
    scanned = segment_scan(keys, vals, combine=combine, impl=impl)
    n = keys.shape[0]
    run_last = jnp.concatenate(
        [keys[1:] != keys[:-1], jnp.array([True])])
    is_head = jnp.concatenate(
        [jnp.array([True]), keys[1:] != keys[:-1]])
    # value for each head = scanned value at its run's last position
    head_pos = jnp.flatnonzero(is_head, size=n, fill_value=n - 1)
    last_pos = jnp.flatnonzero(run_last, size=n, fill_value=n - 1)
    head_vals = jnp.zeros_like(scanned).at[head_pos].set(scanned[last_pos])
    return keys, jnp.where(is_head, head_vals, 0.0), is_head
