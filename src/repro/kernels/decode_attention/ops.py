"""Public wrappers for decode attention: block and split planning.

The paged kernel plans from what it can observe (:func:`paged_plan`): its
compute block from the page's bytes and VMEM, its splits from the chip's
cores.  For the dense kernel the split count is a policy decision: more splits means more parallelism on
the zero-reuse KV stream but more partial (acc, m, l) write-through traffic
— exactly the STREAM-output trade-off the cost model prices.  When a
``CachePolicyEngine`` is passed, its (PlanCache-memoized) plan for the
decode-shaped attention op supplies the target: one split per planned KV
block, so the grid parallelism tracks the same lattice argmin the serve
tier plans with (``ServeEngine.decode_plan`` flows through here).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.core import CachePolicyEngine
from repro.core.characterize import attention_op
from repro.kernels.common import cdiv

# K bytes per compute block the paged kernel aims at (pages_per_block).
BLOCK_K_BYTES = 512 * 1024


def plan_splits(
    s: int,
    bkv: int,
    target_parallelism: int = 8,
    *,
    plan=None,
) -> int:
    """Enough splits to feed the cores without drowning in partials.

    ``blocks`` counts the padded grid's KV blocks (cdiv — a 513-token
    stream over 512-wide blocks runs 2 grid steps, not 1), so the split
    count never exceeds the real parallelism available.  ``plan`` (a
    ``core.allocator.KernelPlan``) overrides the default target with the
    engine's own block decision: one split per engine-planned KV block.
    """
    blocks = max(1, cdiv(s, bkv))
    if plan is not None:
        planned_bkv = int(plan.block.get("bkv", bkv)) or bkv
        target_parallelism = max(1, cdiv(s, planned_bkv))
    return max(1, min(target_parallelism, blocks))


def _engine_plan(engine: CachePolicyEngine, b, hq, hkv, s, d):
    """The engine's plan for a decode-shaped attention op (sq == 1), via
    the engine's own PlanCache — repeat calls are hits, not re-sweeps."""
    return engine.plan_op(attention_op(
        b, hq, max(1, hkv), 1, s, d, causal=False, name="decode_attention",
    ))


def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray | None = None,
    *,
    scale: float | None = None,
    engine: CachePolicyEngine | None = None,
    bkv: int | None = None,
    splits: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    from repro.kernels.decode_attention.decode_attention import (
        decode_attention as _kernel,
    )

    s = k.shape[2]
    bkv = bkv or 512
    if splits is None:
        plan = None
        if engine is not None:
            plan = _engine_plan(
                engine, q.shape[0], q.shape[1], k.shape[1], s, q.shape[2]
            )
        splits = plan_splits(s, bkv, plan=plan)
    return _kernel(
        q, k, v, lengths, scale=scale, bkv=min(bkv, s), splits=splits,
        interpret=interpret,
    )


def _chip():
    import jax

    from repro import hw

    return hw.chip_for_device(jax.devices()[0])


def split_cores() -> int:
    """TensorCores of this process's chip: how many splits can run at once.
    A split beyond that only adds partial writes and a merge."""
    return _chip().tensorcores


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
    blocks = cdiv(pages_per_slot, ppb)
    return ppb, max(1, min(splits or split_cores(), blocks))


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
