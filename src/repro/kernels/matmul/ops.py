"""Jitted public wrapper for the matmul kernel: padding + plan integration."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import CachePolicyEngine, Policy
from repro.core.characterize import matmul_op
from repro.kernels.common import pad_dim
from repro.kernels.matmul.matmul import matmul as _matmul_kernel


def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    engine: CachePolicyEngine | None = None,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    split_k: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Policy-planned blocked matmul.

    With an engine, block shapes / grid order / output policy come from the
    paper's characterize->predict->allocate pipeline; explicit kwargs
    override for benchmarking ablations.
    """
    m, k = a.shape
    _, n = b.shape

    if engine is not None:
        plan = engine.plan_op(matmul_op(m, k, n, dtype=str(a.dtype)))
        bm = bm or plan.block["bm"]
        bn = bn or plan.block["bn"]
        bk = bk or plan.block["bk"]
        order = "mnk" if plan.grid_order[0] == "m" else "nmk"
        if split_k is None:
            split_k = 1 if plan.policy("out") is Policy.RESIDENT_ACCUM else max(
                2, k // max(bk, 1) // 4
            )
    else:
        bm, bn, bk = bm or 256, bn or 256, bk or 256
        order = "mnk"
        split_k = split_k or 1

    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    ap = pad_dim(pad_dim(a, 0, bm), 1, bk)
    bp = pad_dim(pad_dim(b, 0, bk), 1, bn)
    out = _matmul_kernel(
        ap, bp, bm=bm, bn=bn, bk=bk, order=order, split_k=split_k,
        out_dtype=out_dtype or a.dtype, interpret=interpret,
    )
    return out[:m, :n]
