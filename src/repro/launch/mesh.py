"""Production mesh construction.

A FUNCTION (not module-level state) so importing this module never touches
jax device initialization — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, while tests and benches must keep seeing 1 device.

Mesh geometry (TPU v5e pods of 256 chips):
  * single-pod:  (16, 16)    axes ("data", "model")
  * multi-pod:   (2, 16, 16) axes ("pod", "data", "model")

``pod`` composes with ``data`` for batch/gradient parallelism (DP across
pods over DCI; FSDP parameter sharding stays intra-pod over ICI), so adding
pods never changes per-tensor shardings — the basis of elastic scaling.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # the model code places activations with with_sharding_constraint
    # hints, which only Auto (GSPMD) axes accept; jax.make_mesh defaults
    # to Explicit axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes that jointly shard the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
