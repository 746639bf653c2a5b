"""Jitted public wrapper for decode attention: split planning from the engine.

The split count is a policy decision: more splits means more parallelism on
the zero-reuse KV stream but more partial (acc, m, l) write-through traffic
— exactly the STREAM-output trade-off the cost model prices.  When a
``CachePolicyEngine`` is passed, its (PlanCache-memoized) plan for the
decode-shaped attention op supplies the target: one split per planned KV
block, so the grid parallelism tracks the same lattice argmin the serve
tier plans with (``ServeEngine.decode_plan`` flows through here).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import CachePolicyEngine
from repro.core.characterize import attention_op
from repro.kernels.common import cdiv


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


def paged_decode_attention(
    q: jnp.ndarray,          # (b, hq, d)
    k_pool: jnp.ndarray,     # (N, page_size, hkv * d)
    v_pool: jnp.ndarray,     # (N, page_size, hkv * d)
    pages: jnp.ndarray,      # (b, P) int32, -1 = unmapped
    lengths: jnp.ndarray | None = None,
    *,
    scale: float | None = None,
    engine: CachePolicyEngine | None = None,
    splits: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Paged split-KV decode attention: the page pool read in place.

    The KV block size is pinned to the page size (the page table is the
    block index map), so split planning runs over the dense-equivalent
    width ``P * page_size`` with ``bkv = page_size`` — with equal splits
    this is bit-identical to ``gather_pages`` + :func:`decode_attention`.
    """
    from repro.kernels.decode_attention.decode_attention import (
        paged_decode_attention as _kernel,
    )

    psz = k_pool.shape[1]
    P = pages.shape[1]
    if splits is None:
        plan = None
        if engine is not None:
            plan = _engine_plan(
                engine, q.shape[0], q.shape[1],
                k_pool.shape[2] // q.shape[2], P * psz, q.shape[2],
            )
        splits = plan_splits(P * psz, psz, plan=plan)
    return _kernel(
        q, k_pool, v_pool, pages, lengths, scale=scale, splits=splits,
        interpret=interpret,
    )
