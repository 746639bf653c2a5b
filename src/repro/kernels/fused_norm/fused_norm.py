"""Fused (RMS/Layer)Norm (+ residual add) Pallas kernel.

The paper's throughput-sensitive class, as a kernel: activations stream
through once (reuse = 1), so the only correct policy is STREAM with
full-bandwidth row-major sweeps — the fusion (residual add + normalize +
scale in one pass) removes the extra HBM round-trips an unfused stack would
pay, which is the TPU-native way to "win" on a no-reuse layer.  The tiny
(d,) weight/bias are RESIDENT via constant index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, compiler_params, interpret_mode


def _norm_kernel(x_ref, w_ref, b_ref, r_ref, o_ref, *, eps: float, kind: str,
                 has_bias: bool, has_residual: bool):
    h = x_ref[...].astype(jnp.float32)
    if has_residual:
        h = h + r_ref[...].astype(jnp.float32)
    if kind == "layer":
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        y = (h - mu) * jax.lax.rsqrt(var + eps)
    else:
        ms = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        y = h * jax.lax.rsqrt(ms + eps)
    y = y * w_ref[...].astype(jnp.float32)
    if has_bias:
        y = y + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("eps", "kind", "block_rows", "interpret")
)
def fused_norm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    residual: jnp.ndarray | None = None,
    *,
    eps: float = 1e-6,
    kind: str = "rms",
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    r2 = residual.reshape(rows, d) if residual is not None else None

    br = min(block_rows, rows)
    rows_pad = cdiv(rows, br) * br
    if rows_pad != rows:
        x2 = jnp.pad(x2, ((0, rows_pad - rows), (0, 0)))
        if r2 is not None:
            r2 = jnp.pad(r2, ((0, rows_pad - rows), (0, 0)))

    has_bias = bias is not None
    has_residual = r2 is not None
    b_arg = bias if has_bias else jnp.zeros((d,), x.dtype)
    r_arg = r2 if has_residual else jnp.zeros((1, d), x.dtype)

    out = pl.pallas_call(
        functools.partial(
            _norm_kernel, eps=eps, kind=kind,
            has_bias=has_bias, has_residual=has_residual,
        ),
        grid=(rows_pad // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),       # RESIDENT weight
            pl.BlockSpec((d,), lambda i: (0,)),       # RESIDENT bias
            pl.BlockSpec(
                (br, d) if has_residual else (1, d),
                (lambda i: (i, 0)) if has_residual else (lambda i: (0, 0)),
            ),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, d), x.dtype),
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(x2, weight, b_arg, r_arg)
    return out[:rows].reshape(orig_shape)
