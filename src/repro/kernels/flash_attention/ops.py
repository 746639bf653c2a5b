"""Public wrapper for flash attention: default blocks."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention as _kernel


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    bq: int | None = None,
    bkv: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    return _kernel(
        q, k, v, causal=causal, scale=scale, q_offset=q_offset,
        bq=bq or 256, bkv=bkv or 256, interpret=interpret,
    )
