"""Public wrapper for the matmul kernel: padding and default blocks."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.common import pad_dim
from repro.kernels.matmul.matmul import matmul as _matmul_kernel


def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    split_k: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Blocked matmul: 256 blocks, grid order ``"mnk"`` and no split-K
    unless given; operands are padded to whole blocks."""
    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = min(bm or 256, m), min(bn or 256, n), min(bk or 256, k)
    ap = pad_dim(pad_dim(a, 0, bm), 1, bk)
    bp = pad_dim(pad_dim(b, 0, bk), 1, bn)
    out = _matmul_kernel(
        ap, bp, bm=bm, bn=bn, bk=bk, order="mnk", split_k=split_k or 1,
        out_dtype=out_dtype or a.dtype, interpret=interpret,
    )
    return out[:m, :n]
