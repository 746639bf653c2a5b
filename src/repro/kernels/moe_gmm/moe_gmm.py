"""MoE grouped expert GEMM Pallas kernel (capacity-based dispatch layout).

Tokens are gathered into per-expert capacity buffers (GShard-style), turning
the ragged expert matmul into a regular batched GEMM the MXU can eat:
``y[e] = x[e] @ w[e]``.

Policy story: expert weights are the interesting operand.  With few tokens
per expert (decode, high expert count) the weight tile is touched ~once —
the paper's throughput-sensitive regime: STREAM the weights, don't burn
VMEM keeping them.  With large per-expert batches the weights become
reuse-dense and the planner keeps each expert's (K, N) panel RESIDENT
across the token blocks.  Both show up here purely as block shapes/grid
from the engine's allocator.

Experts whose token count is zero are skipped entirely (`pl.when` guard) —
compute and HBM writes for empty capacity slots are elided.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import compiler_params, interpret_mode


def _gmm_kernel(cnt_ref, x_ref, w_ref, o_ref, acc_ref, *, k_steps: int, bm: int):
    ie = pl.program_id(0)
    im = pl.program_id(1)
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Skip token blocks entirely beyond this expert's live count.
    live = cnt_ref[0] > im * bm

    @pl.when(live)
    def _():
        acc_ref[...] += jnp.dot(
            x_ref[0], w_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when(kk == k_steps - 1)
    def _flush():
        rows = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        valid = rows + im * bm < cnt_ref[0]
        o_ref[0] = jnp.where(valid, acc_ref[...], 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def grouped_matmul(
    x: jnp.ndarray,          # (e, c, k)
    w: jnp.ndarray,          # (e, k, n)
    counts: jnp.ndarray | None = None,  # (e,)
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int = 256,
    out_dtype=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    e, c, k = x.shape
    _, _, n = w.shape
    out_dtype = out_dtype or x.dtype
    if counts is None:
        counts = jnp.full((e,), c, jnp.int32)
    bm, bn, bk = min(bm, c), min(bn, n), min(bk, k)
    assert c % bm == 0 and n % bn == 0 and k % bk == 0, (
        "caller (ops.py) must pad to block multiples"
    )
    k_steps = k // bk
    grid = (e, c // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, k_steps=k_steps, bm=bm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda ie, im, jn, kk: (ie,)),
            pl.BlockSpec((1, bm, bk), lambda ie, im, jn, kk: (ie, im, kk)),
            pl.BlockSpec((1, bk, bn), lambda ie, im, jn, kk: (ie, kk, jn)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda ie, im, jn, kk: (ie, im, jn)),
        out_shape=jax.ShapeDtypeStruct((e, c, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(counts.astype(jnp.int32), x, w)
