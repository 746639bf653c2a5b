"""Public wrappers for decode attention: block and split planning.

Both kernels plan from what they can observe: the shapes they receive and
the chip this process runs on.  One split rule serves both
(:func:`split_count`): one split per padded grid block, at most the chip's
TensorCores.  The paged kernel's compute block comes from the page's bytes
and VMEM (:func:`paged_plan`); the dense kernel's is at most 512 positions.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.kernels.common import cdiv

# K bytes per compute block the paged kernel aims at (pages_per_block).
BLOCK_K_BYTES = 512 * 1024
# KV positions per compute block of the dense kernel.
DENSE_BKV = 512


def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray | None = None,
    *,
    scale: float | None = None,
    bkv: int | None = None,
    splits: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Dense split-KV decode attention over a (b, hkv, s, d) view.  The
    block is ``DENSE_BKV`` positions and the splits :func:`split_count`'s
    unless given."""
    from repro.kernels.decode_attention.decode_attention import (
        decode_attention as _kernel,
    )

    s = k.shape[2]
    bkv = min(bkv or DENSE_BKV, s)
    return _kernel(
        q, k, v, lengths, scale=scale, bkv=bkv,
        splits=splits or split_count(s, bkv), interpret=interpret,
    )


def _chip():
    import jax

    from repro import hw

    return hw.chip_for_device(jax.devices()[0])


def split_cores() -> int:
    """TensorCores of this process's chip: how many splits can run at once.
    A split beyond that only adds partial writes and a merge."""
    return _chip().tensorcores


def split_count(kv_positions: int, block: int) -> int:
    """Splits of a slot's KV stream, for either kernel: one per block of
    the padded grid (cdiv: 513 positions over 512-wide blocks are 2 blocks),
    at most the chip's TensorCores."""
    return max(1, min(split_cores(), cdiv(kv_positions, block)))


def pages_per_block(page_bytes: int, pages_per_slot: int) -> int:
    """Pages per compute block of the paged kernel, from what it can see.

    A block should carry about ``BLOCK_K_BYTES`` of K: enough to hide each
    work item's fixed cost behind its DMA, small enough that a slot's last,
    partial block wastes little compute.  The power of two nearest that,
    at most the slot's pages, and halved until the four buffers (K and V,
    double-buffered) take at most a quarter of the chip's VMEM budget.
    Query heads do not enter: the block-diagonal query streams each K row
    through the MXU once for up to 128 of them.
    """
    want = max(1.0, BLOCK_K_BYTES / max(1, page_bytes))
    ppb = min(1 << max(0, round(math.log2(want))), pages_per_slot)
    while ppb > 1 and 4 * ppb * page_bytes > _chip().vmem_budget // 4:
        ppb //= 2
    return max(1, ppb)


def paged_plan(page_bytes: int, pages_per_slot: int,
               splits: int | None = None,
               pages: int | None = None) -> tuple[int, int]:
    """(pages per block, splits) for the paged kernel, either given or
    planned; the dense kernel with ``bkv = pages * page_size`` and the same
    splits folds the same blocks (the identity reference)."""
    ppb = pages or pages_per_block(page_bytes, pages_per_slot)
    if splits:
        return ppb, max(1, min(splits, cdiv(pages_per_slot, ppb)))
    return ppb, split_count(pages_per_slot, ppb)


def paged_decode_attention(
    q: jnp.ndarray,          # (b, hq, d)
    k_pool: jnp.ndarray,     # ([L,] N, page_size, hkv * d)
    v_pool: jnp.ndarray,     # ([L,] N, page_size, hkv * d)
    pages: jnp.ndarray,      # (b, P) int32, -1 = unmapped
    lengths: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,   # which of L stacked pools
    *,
    scale: float | None = None,
    splits: int | None = None,
    pages_per_block: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Paged split-KV decode attention: only the resident pages of the
    pool, read in place.  Block size and splits are planned by
    :func:`paged_plan` unless given."""
    from repro.kernels.decode_attention.decode_attention import (
        paged_decode_attention as _kernel,
    )

    psz, hd = k_pool.shape[-2:]
    ppb, splits = paged_plan(psz * hd * k_pool.dtype.itemsize,
                             pages.shape[1], splits, pages_per_block)
    return _kernel(
        q, k_pool, v_pool, pages, lengths, layer, scale=scale,
        splits=splits, pages_per_block=ppb, interpret=interpret,
    )
