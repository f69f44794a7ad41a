"""Jitted public wrapper: pads to tile multiples, dispatches kernel/ref.

On CPU (tests, dry-run) the kernel runs in interpret mode or falls back to
the jnp reference — Pallas-on-TPU is the deployment target; interpret=True
executes the same kernel body for correctness validation.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.semiring import get_semiring
from repro.kernels import resolve_impl
from .ref import semiring_matmul_ref
from .semiring_matmul import semiring_matmul_pallas


def _pad_to(x, mult_r, mult_c, fill):
    r = (-x.shape[0]) % mult_r
    c = (-x.shape[1]) % mult_c
    if r or c:
        x = jnp.pad(x, ((0, r), (0, c)), constant_values=fill)
    return x


@partial(jax.jit, static_argnames=("semiring", "impl", "bm", "bn", "bk"))
def semiring_matmul(a: jnp.ndarray, b: jnp.ndarray, *, semiring="plus_times",
                    impl: str = "auto", bm: int = 128, bn: int = 128,
                    bk: int = 128) -> jnp.ndarray:
    """Semiring contraction with shape-padding; returns [M, N] fp32.

    impl: "pallas" (TPU), "interpret" (kernel body on CPU), "ref" (jnp),
    "auto" (pallas on TPU backend, ref elsewhere).
    """
    sr = get_semiring(semiring)
    m, n = a.shape[0], b.shape[1]
    impl = resolve_impl("semiring_matmul", impl)
    if impl == "ref":
        return semiring_matmul_ref(a, b, semiring=sr)
    ap = _pad_to(a.astype(jnp.float32), bm, bk, sr.zero)
    bp = _pad_to(b.astype(jnp.float32), bk, bn, sr.zero)
    out = semiring_matmul_pallas(ap, bp, semiring=sr, bm=bm, bn=bn, bk=bk,
                                 interpret=(impl == "interpret"))
    return out[:m, :n]
