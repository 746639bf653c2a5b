"""Jitted public wrapper for the Mamba-2 SSD kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssd.ssd import ssd as _kernel
from repro.kernels.ssd.ssd import ssd_decode_step  # noqa: F401 (re-export)


def ssd(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    D: jnp.ndarray | None = None,
    *,
    chunk: int = 128,
    interpret: bool | None = None,
):
    return _kernel(x, dt, A, B, C, D, chunk=chunk, interpret=interpret)
