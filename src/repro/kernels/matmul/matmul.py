"""Blocked matmul Pallas kernel with policy-selectable schedule.

Policies map to schedules (DESIGN.md §2):

* output ``RESIDENT_ACCUM`` (CacheRW analogue, default): grid iterates
  (m, n, k) with k innermost; the output tile accumulates in a VMEM fp32
  scratch and is written back exactly once — the write-coalescing policy.
  The rinse-planned order keeps the (m, n) sweep row-major so writebacks hit
  HBM in address order.
* output ``STREAM`` (write-through / split-K analogue): the K range is split
  across grid workers; each writes fp32 partials straight through to HBM and
  a cheap reduction combines them.  This is the "Uncached-writes" baseline
  the cost model charges for, and is also the right plan when M*N is tiny
  but K is huge (the reduction needs the parallelism).
* input residency (``RESIDENT`` A or B) is expressed through the grid order:
  the operand whose block index is innermost-invariant stays in VMEM across
  revisits (Pallas skips the re-copy when the block index repeats).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, compiler_params, interpret_mode


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int, out_dtype):
    """Grid (m, n, k) or (n, m, k): k innermost, accumulate in VMEM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def _mm_splitk_kernel(a_ref, b_ref, o_ref):
    """Grid (k, m, n): every k split writes its fp32 partial through to HBM."""
    o_ref[...] = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "order", "split_k", "out_dtype", "interpret"),
)
def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    order: str = "mnk",          # "mnk" (rinse row-major) or "nmk"
    split_k: int = 1,            # >1 -> STREAM-output write-through partials
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        "caller (ops.py) must pad to block multiples"
    )

    if split_k > 1:
        ks = cdiv(k, split_k * bk) * bk          # k elems per split, bk-aligned
        split_k = cdiv(k, ks)
        grid = (split_k, m // bm, n // bn, ks // bk)

        def kern(a_ref, b_ref, o_ref, acc_ref):
            kk = pl.program_id(3)

            @pl.when(kk == 0)
            def _():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            acc_ref[...] += jnp.dot(
                a_ref[...], b_ref[...], preferred_element_type=jnp.float32
            )

            @pl.when(kk == grid[3] - 1)
            def _():
                o_ref[0] = acc_ref[...]

        partials = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda s, i, j, kk: (i, s * (ks // bk) + kk)),
                pl.BlockSpec((bk, bn), lambda s, i, j, kk: (s * (ks // bk) + kk, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn), lambda s, i, j, kk: (s, i, j)),
            out_shape=jax.ShapeDtypeStruct((split_k, m, n), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=compiler_params(),
            interpret=interpret_mode(interpret),
        )(a, b)
        return jnp.sum(partials, axis=0).astype(out_dtype)

    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    if order == "mnk":
        a_map = lambda i, j, kk: (i, kk)
        b_map = lambda i, j, kk: (kk, j)
        o_map = lambda i, j, kk: (i, j)
    elif order == "nmk":  # column-major tile sweep (no-rinse baseline)
        grid = (n // bn, m // bm, k_steps)
        a_map = lambda j, i, kk: (i, kk)
        b_map = lambda j, i, kk: (kk, j)
        o_map = lambda j, i, kk: (i, j)
    else:
        raise ValueError(order)

    return pl.pallas_call(
        functools.partial(_mm_kernel, k_steps=k_steps, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(a, b)
