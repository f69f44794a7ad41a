"""repro.kernels — Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships three files:
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling,
  ops.py    — jitted public wrapper (padding, impl dispatch),
  ref.py    — pure-jnp oracle used by the allclose test sweeps.

Kernels are validated in interpret mode on CPU; TPU is the deployment
target.  See DESIGN.md §2 for the CPU-scipy → TPU adaptation story.

Every ``ops.py`` dispatch resolves ``impl`` through :func:`resolve_impl`,
which counts each resolution in ``KERNEL_STATS`` (``"<kernel>:<impl>"``
→ traces) — the server's ``/stats`` reports it, so a chip run can show
that every kernel on its path traced as ``pallas``.
"""
import threading
from typing import Dict

import jax

KERNEL_STATS: Dict[str, int] = {}
_STATS_LOCK = threading.Lock()


def resolve_impl(kernel: str, impl: str) -> str:
    """Resolve a dispatch ``impl``: ``"auto"`` is the Pallas kernel on a
    TPU backend and the jnp reference elsewhere; ``"pallas"``, ``"ref"``
    and ``"interpret"`` pass through.  Runs at trace time, so
    ``KERNEL_STATS`` counts traces, not calls."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    key = f"{kernel}:{impl}"
    with _STATS_LOCK:
        KERNEL_STATS[key] = KERNEL_STATS.get(key, 0) + 1
    return impl


def reset_kernel_stats() -> None:
    with _STATS_LOCK:
        KERNEL_STATS.clear()
