"""Jitted public wrapper for flash attention: plan integration."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import CachePolicyEngine
from repro.core.characterize import attention_op
from repro.kernels.flash_attention.flash_attention import flash_attention as _kernel


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    engine: CachePolicyEngine | None = None,
    bq: int | None = None,
    bkv: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if engine is not None and (bq is None or bkv is None):
        plan = engine.plan_op(
            attention_op(b, hq, hkv, sq, skv, d, causal=causal, dtype=str(q.dtype))
        )
        bq = bq or plan.block["bq"]
        bkv = bkv or plan.block["bkv"]
    return _kernel(
        q, k, v, causal=causal, scale=scale, q_offset=q_offset,
        bq=bq or 256, bkv=bkv or 256, interpret=interpret,
    )
