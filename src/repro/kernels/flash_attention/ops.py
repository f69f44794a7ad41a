"""Jitted wrapper matching the model-side calling convention.

``repro.models.attention.chunked_attention`` calls this when
``cfg.attn_impl == "pallas"`` with [B, S, H, D]-layout tensors and
position arrays; we transpose to the kernel layout, dispatch, and
transpose back.  Decode (1-token query over a ring cache) stays on the
reference path — the kernel targets the S² train/prefill hot spot.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_impl
from .flash_attention import flash_attention_pallas
from .ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "sm_scale", "impl",
                                   "q_off"))
def flash_attention(q, k, v, *, q_positions=None, k_positions=None,
                    causal=True, window=None, k_valid_len=None,
                    sm_scale=None, impl: str = "auto", q_off: int = 0):
    """Model-layout entry: q [B,Sq,H,D], k/v [B,Sk,KV,D] → [B,Sq,H,D].

    Train/prefill assume contiguous positions starting at ``q_off``
    (``q_positions``/``k_positions`` arrays are accepted for signature
    parity with the reference path).  Decode over ring caches
    (``k_valid_len``) routes to the reference implementation.
    """
    impl = resolve_impl("flash_attention", impl)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if impl == "ref" or k_valid_len is not None:
        out = flash_attention_ref(qt, kt, vt, causal=causal, window=window,
                                  sm_scale=sm_scale, q_off=q_off)
    else:
        out = flash_attention_pallas(
            qt, kt, vt, causal=causal, window=window, sm_scale=sm_scale,
            q_off=q_off, interpret=(impl == "interpret"))
    return out.transpose(0, 2, 1, 3)
