"""Sharding-constraint helpers usable from mesh-agnostic model code.

``constrain_batch(x)`` pins the leading (batch) dim of an activation to the
data-parallel mesh axes — the single most important hint for XLA's SPMD
partitioner here: without it, the residuals saved by the layer-scan for
backward may be re-sharded onto feature axes (batch-replicated!), inflating
per-device live memory by |data| ×.

The helpers no-op when no mesh is active (CPU unit tests) and adapt to
single-pod ("data") vs multi-pod ("pod", "data") meshes automatically.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


def _current_axis_names() -> Tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return ()
    return tuple(mesh.axis_names)


_BATCH_OVER_MODEL = False  # fsdp_only parallelism: model axis joins DP


def set_parallelism(mode: str):
    """Called by launch.steps before tracing; trace-time static."""
    global _BATCH_OVER_MODEL
    _BATCH_OVER_MODEL = (mode == "fsdp_only")


def batch_axes_in_mesh() -> Optional[Tuple[str, ...]]:
    names = _current_axis_names()
    pool = ("pod", "data", "model") if _BATCH_OVER_MODEL else ("pod", "data")
    axes = tuple(a for a in pool if a in names)
    return axes or None


def constrain(x, *spec_entries):
    """with_sharding_constraint if a mesh is active, else identity."""
    if not _current_axis_names():
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec_entries))


def constrain_batch(x, n_extra: Optional[int] = None):
    """Pin dim0 to the batch axes; remaining dims unconstrained."""
    axes = batch_axes_in_mesh()
    if axes is None:
        return x
    extra = x.ndim - 1 if n_extra is None else n_extra
    if x.shape[0] % _axes_size(axes):
        return x
    return jax.lax.with_sharding_constraint(x, P(axes, *([None] * extra)))


def _axes_size(axes: Tuple[str, ...]) -> int:
    mesh = jax.sharding.get_abstract_mesh()
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def constrain_seq(x):
    """Megatron-style sequence parallelism: the residual stream lives
    S-sharded over `model` between blocks; XLA inserts all-gather before
    the TP matmuls and reduce-scatter after — same bytes as the all-reduce
    but per-device activation residency drops by |model|."""
    names = _current_axis_names()
    if "model" not in names or x.ndim < 3:
        return x
    if x.shape[1] % jax.sharding.get_abstract_mesh().shape["model"]:
        return x
    b_axes = batch_axes_in_mesh()
    b = b_axes if (b_axes and x.shape[0] % _axes_size(b_axes) == 0) else None
    return jax.lax.with_sharding_constraint(
        x, P(b, "model", *([None] * (x.ndim - 2))))


def constrain_decode_qkv(q, k, v, n_kv_heads: int):
    """dh-shard decode q/k/v when kv heads can't shard over `model`."""
    names = _current_axis_names()
    if "model" not in names:
        return q, k, v
    if n_kv_heads % jax.sharding.get_abstract_mesh().shape["model"] == 0:
        return q, k, v  # kv-head sharding is consistent; leave it alone
    return (constrain_last_model(q), constrain_last_model(k),
            constrain_last_model(v))


def constrain_last_model(x):
    """Shard the LAST dim over `model` (if present & divisible), batch on 0.

    Used on decode-path q/k/v so the per-step attention einsums contract a
    model-sharded head_dim against the model-sharded KV cache — without
    this, SPMD repartitions the entire stacked cache (involuntary full
    rematerialization) when kv_heads don't divide the model axis.
    """
    names = _current_axis_names()
    if "model" not in names:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if x.shape[-1] % mesh.shape["model"]:
        return x
    b_axes = batch_axes_in_mesh()
    b = b_axes if (b_axes and x.shape[0] % _axes_size(b_axes) == 0) else None
    spec = [b] + [None] * (x.ndim - 2) + ["model"]
    return jax.lax.with_sharding_constraint(x, P(*spec))
