"""Jitted public wrapper for the MoE grouped GEMM: padding + block planning."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.common import pad_dim
from repro.kernels.moe_gmm.moe_gmm import grouped_matmul as _kernel


def grouped_matmul(
    x: jnp.ndarray,          # (e, c, k)
    w: jnp.ndarray,          # (e, k, n)
    counts: jnp.ndarray | None = None,
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int = 256,
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    e, c, k = x.shape
    n = w.shape[2]
    bm, bn, bk = min(bm, c), min(bn, n), min(bk, k)
    xp = pad_dim(pad_dim(x, 1, bm), 2, bk)
    wp = pad_dim(pad_dim(w, 1, bk), 2, bn)
    out = _kernel(
        xp, wp, counts, bm=bm, bn=bn, bk=bk,
        out_dtype=out_dtype or x.dtype, interpret=interpret,
    )
    return out[:, :c, :n]
