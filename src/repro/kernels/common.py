"""Shared helpers for the Pallas TPU kernels.

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are validated
on CPU in interpret mode.  ``interpret_mode()`` picks the mode from the
backend so the same ops run on both; on a TPU a kernel is always compiled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro import hw


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument.

    None interprets exactly when the backend is not a TPU.  On a TPU the
    kernel is compiled, never interpreted: an explicit ``True`` there would
    hide the chip behind the Pallas interpreter, so it is an error."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas kernels are compiled on a TPU, not interpreted")
    return bool(interpret)


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic parameters shared by every kernel: the scoped-VMEM limit is
    the chip's budget (``hw.Chip.vmem_budget`` of the process's device),
    the one the kernels' block rules plan against, so the tiles they plan
    are tiles the compiler admits."""
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics or None,
        vmem_limit_bytes=hw.chip_for_device(jax.devices()[0]).vmem_budget,
    )


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    return cdiv(x, m) * m


def pad_dim(x: jnp.ndarray, axis: int, multiple: int, value=0.0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = round_up(size, multiple) - size
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


NEG_INF = -1e30
