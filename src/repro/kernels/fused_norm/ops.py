"""Jitted public wrapper for fused norm."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.fused_norm.fused_norm import fused_norm as _kernel


def fused_norm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    residual: jnp.ndarray | None = None,
    *,
    eps: float = 1e-6,
    kind: str = "rms",
    interpret: bool | None = None,
) -> jnp.ndarray:
    return _kernel(
        x, weight, bias, residual, eps=eps, kind=kind, interpret=interpret
    )
