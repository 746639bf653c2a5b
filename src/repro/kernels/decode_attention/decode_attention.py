"""GQA decode attention with split-KV (flash-decoding) Pallas kernels.

Decode is the paper's throughput-sensitive regime personified: the KV cache
is a huge, zero-reuse stream (each cache line is touched exactly once per
step), so the right policy is pure STREAM with maximal HBM bandwidth —
bypass, don't cache.  The only RESIDENT_ACCUM state is the online-softmax
accumulator, tiny and revisited every block.

``splits > 1`` partitions each slot's KV sequence across grid workers that
each write (acc, m, l) partials; a cheap log-sum-exp combine merges them.
A split only pays where the chip has several cores to run them on; it is
also the schedule the sequence-parallel decoder uses across chips (see
repro/distributed/sp_decode.py).

Two kernels share the block math (:func:`_fold_block`): the dense one over a
(b, hkv, s, d) view, and the paged one, which reads only the resident pages
of the (N, page_size, hkv * d) pool, in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    NEG_INF, cdiv, compiler_params, interpret_mode,
)

_F32 = jnp.float32


def _fold_block(q, k, v, start, length, m_ref, l_ref, acc_ref, *, scale):
    """Fold one KV block into one (slot, split)'s running softmax.

    A block holds every head of ``bk`` positions as (bk, hkv * d) rows —
    several pages of the paged pool, or ``bk`` rows of the dense view — and
    ``q`` is the slot's (hq, hkv * d) block-diagonal query: row j holds
    query head j in the lanes of its kv head and zeros elsewhere.  So one
    matmul gives every head's scores, and one more every head's weighted
    values (row j's own lanes; the other lanes are dropped when the
    partials are combined).  Operands enter the MXU in the cache's dtype —
    bf16 K/V as stored, the float32 probabilities as a high and a low part
    in it, so they keep twice the bits of XLA's default one-pass product —
    and accumulate in float32; scores, softmax statistics and the
    accumulator are float32.  A float32 cache is multiplied at full float32
    precision.

    Positions at or past ``length`` are masked exactly: p == 0.0 there and,
    for a block with no valid position, alpha == 1.0, so the fold is a
    no-op and skipping such a block changes no bit."""
    bk = k.shape[0]
    prec = jax.lax.Precision.HIGHEST if k.dtype == _F32 else None
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = pos < length                                      # (1, bk)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=prec,
        preferred_element_type=_F32,
    ) * scale
    s = jnp.where(mask, s, NEG_INF)                          # (hq, bk)
    m_prev = m_ref[...]                                      # (hq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    p_hi = p.astype(v.dtype)
    p_lo = (p - p_hi.astype(_F32)).astype(v.dtype)
    acc_ref[...] = acc_ref[...] * alpha + sum(
        jnp.dot(x, v, precision=prec, preferred_element_type=_F32)
        for x in (p_hi, p_lo))
    m_ref[...] = m_cur


def _block_diagonal(q, hkv, dtype):
    """(b, hq, d) -> (b, hq, hkv * d): query head j in the d lanes of its
    kv head j // group, zeros elsewhere (exact in any dtype)."""
    b, hq, d = q.shape
    own = (jnp.arange(hq)[:, None] // (hq // hkv)) == jnp.arange(hkv)[None]
    qbd = q.astype(dtype)[:, :, None, :] * own[None, :, :, None].astype(dtype)
    return qbd.reshape(b, hq, hkv * d)


def _partials(acc, m, l, hkv):
    """Kernel outputs (b, splits, hq, hkv * d | 1) -> combine_partials'
    (b, hq, splits, d | -) layout, keeping each row's own kv-head lanes."""
    b, splits, hq, hd = acc.shape
    d = hd // hkv
    own = (jnp.arange(hq) // (hq // hkv))[None, None, :, None, None]
    acc = jnp.take_along_axis(acc.reshape(b, splits, hq, hkv, d), own,
                              axis=3)[:, :, :, 0]
    return (jnp.swapaxes(acc, 1, 2), jnp.swapaxes(m[..., 0], 1, 2),
            jnp.swapaxes(l[..., 0], 1, 2))


def _init_state(m_ref, l_ref, acc_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _dense_kernel(len_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                  bkv: int, kv_steps: int, scale: float):
    """Grid (b, splits, kv_steps): one KV block per step, folded into the
    (slot, split)'s output block, which stays in VMEM across the sweep.
    Blocks past the slot's length are neither fetched (the index map
    clamps to the last valid block) nor computed."""
    ib, sp, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    start = (sp * kv_steps + ik) * bkv
    length = len_ref[ib]

    @pl.when(start < length)
    def _fold():
        _fold_block(q_ref[...], k_ref[...], v_ref[...], start, length,
                    m_ref, l_ref, acc_ref, scale=scale)


@functools.partial(
    jax.jit, static_argnames=("scale", "bkv", "splits", "interpret")
)
def decode_attention(
    q: jnp.ndarray,          # (b, hq, d)
    k: jnp.ndarray,          # (b, hkv, s, d)
    v: jnp.ndarray,          # (b, hkv, s, d)
    lengths: jnp.ndarray | None = None,   # (b,) valid lengths
    *,
    scale: float | None = None,
    bkv: int = 512,
    splits: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    assert hq % hkv == 0
    hd = hkv * d
    scale = float(scale if scale is not None else d ** -0.5)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)

    bkv = min(bkv, s)
    # Pad s so it divides evenly into splits * kv_steps * bkv.
    per_split = cdiv(cdiv(s, splits), bkv) * bkv
    s_pad = per_split * splits
    # Rows of all heads, (b, s_pad, hkv * d): the kernel's block layout.
    k, v = (jnp.pad(jnp.swapaxes(x, 1, 2), ((0, 0), (0, s_pad - s),
                                            (0, 0), (0, 0)))
            .reshape(b, s_pad, hd) for x in (k, v))
    kv_steps = per_split // bkv

    def kv_map(ib, sp, ik, ln):
        last = jnp.maximum(cdiv(ln[ib], bkv) - 1, 0)
        return ib, jnp.minimum(sp * kv_steps + ik, last), 0

    def out_map(ib, sp, ik, ln):
        return ib, sp, 0, 0

    acc, m, l = pl.pallas_call(
        functools.partial(_dense_kernel, bkv=bkv, kv_steps=kv_steps,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, splits, kv_steps),
            in_specs=[
                pl.BlockSpec((None, hq, hd), lambda ib, sp, ik, ln: (ib, 0, 0)),
                pl.BlockSpec((None, bkv, hd), kv_map),
                pl.BlockSpec((None, bkv, hd), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((None, None, hq, hd), out_map),
                pl.BlockSpec((None, None, hq, 1), out_map),
                pl.BlockSpec((None, None, hq, 1), out_map),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, splits, hq, hd), _F32),
            jax.ShapeDtypeStruct((b, splits, hq, 1), _F32),
            jax.ShapeDtypeStruct((b, splits, hq, 1), _F32),
        ],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret_mode(interpret),
    )(lengths.astype(jnp.int32), _block_diagonal(q, hkv, k.dtype), k, v)
    return combine_partials(*_partials(acc, m, l, hkv)).astype(q.dtype)


def combine_partials(
    acc: jnp.ndarray,  # (b, hq, splits, d)
    m: jnp.ndarray,    # (b, hq, splits)
    l: jnp.ndarray,    # (b, hq, splits)
) -> jnp.ndarray:
    """Log-sum-exp merge of flash-decoding partials (also used across chips
    by the sequence-parallel decoder)."""
    m_glob = jnp.max(m, axis=-1, keepdims=True)
    w = jnp.exp(m - m_glob)
    l_glob = jnp.sum(l * w, axis=-1)
    num = jnp.sum(acc * w[..., None], axis=2)
    return num / jnp.maximum(l_glob, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# Paged variant: read only the resident pages of the pool, in place.
#
# The paged engine's KV lives in an (N, page_size, hkv * d) pool per layer,
# stacked (L, N, ...) and read through a layer index, addressed through
# per-slot page tables (models/common.py, DESIGN.md §5.2).  The
# gather path copies every slot's whole table (max_len positions) into a
# dense view each decode step.  Here each split of the grid walks a list of
# (slot, block) work items — only the blocks below each slot's length — and
# fetches a block's pages straight from the pool by manual DMA, one copy per
# page, double-buffered so the next block's pages stream in while this one
# is folded.  A slot of length 0 (free or parked) has no work item: it reads
# no page, and its partials stay at the (0, -inf, 0) start, which combine
# to a finite 0.  With the same splits and ``bkv = pages_per_block *
# page_size`` the dense kernel folds the same blocks in the same order, so
# the two paths are bit-identical — the CI identity gate relies on that.
# ---------------------------------------------------------------------------


def _paged_kernel(len_ref, pages_ref, count_ref, slot_ref, blk_ref,
                  layer_ref, q_ref, k_hbm, v_hbm, acc_ref, m_ref, l_ref,
                  k_buf, v_buf, sems, *, psz: int, ppb: int, n_pages: int,
                  pool_pages: int, items: int, blocks_per_split: int,
                  scale: float):
    sp = pl.program_id(0)
    _init_state(m_ref, l_ref, acc_ref)
    total = count_ref[sp]
    k_hbm, v_hbm = k_hbm.at[layer_ref[0]], v_hbm.at[layer_ref[0]]

    def item(i):
        """Slot, first logical page, pages to fetch and length of item i."""
        slot = slot_ref[sp * items + i]
        page0 = (sp * blocks_per_split + blk_ref[sp * items + i]) * ppb
        length = len_ref[slot]
        n = jnp.minimum(cdiv(length, psz) - page0, ppb)
        return slot, page0, n, length

    def copies(i, buf, j):
        slot, page0, _, _ = item(i)
        page = jnp.clip(pages_ref[slot * n_pages + page0 + j], 0,
                        pool_pages - 1)
        rows = pl.ds(pl.multiple_of(j * psz, psz), psz)
        return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf, rows],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf, rows],
                                      sems.at[1, buf]))

    def fetch(i, buf):
        def start(j, carry):
            for c in copies(i, buf, j):
                c.start()
            return carry
        jax.lax.fori_loop(0, item(i)[2], start, 0)

    @pl.when(total > 0)
    def _first():
        fetch(0, 0)

    def step(i, carry):
        buf = i % 2

        @pl.when(i + 1 < total)
        def _next():
            fetch(i + 1, 1 - buf)

        slot, page0, n, length = item(i)

        def wait(j, c):
            for cp in copies(i, buf, j):
                cp.wait()
            return c
        jax.lax.fori_loop(0, n, wait, 0)

        # Rows of pages past the slot's last one hold an earlier block's
        # bytes: zero V there, as 0 * inf would survive the mask.
        def zero(j, c):
            v_buf[buf, pl.ds(pl.multiple_of(j * psz, psz), psz), :] = (
                jnp.zeros((psz, v_buf.shape[-1]), v_buf.dtype))
            return c
        jax.lax.fori_loop(n, ppb, zero, 0)

        _fold_block(q_ref[slot], k_buf[buf], v_buf[buf], page0 * psz, length,
                    m_ref.at[slot], l_ref.at[slot], acc_ref.at[slot],
                    scale=scale)
        return carry

    jax.lax.fori_loop(0, total, step, 0)


def _work_items(lengths, psz, ppb, splits, blocks_per_split):
    """Per split, the (slot, block-in-split) pairs below each slot's length,
    slot-major, padded to b * blocks_per_split; and their count."""
    b = lengths.shape[0]
    blocks = cdiv(cdiv(lengths, psz), ppb)                       # (b,)
    first = jnp.arange(splits)[:, None] * blocks_per_split
    per = jnp.clip(blocks[None, :] - first, 0, blocks_per_split)  # (S, b)
    ends = jnp.cumsum(per, axis=1)
    i = jnp.arange(b * blocks_per_split)
    slot = jnp.sum(i[None, :, None] >= ends[:, None, :], axis=2)  # (S, I)
    slot = jnp.minimum(slot, b - 1)
    blk = i[None, :] - jnp.take_along_axis(ends - per, slot, axis=1)
    return (ends[:, -1].astype(jnp.int32), slot.reshape(-1).astype(jnp.int32),
            blk.reshape(-1).astype(jnp.int32))


@functools.partial(
    jax.jit, static_argnames=("scale", "splits", "pages_per_block",
                              "interpret")
)
def paged_decode_attention(
    q: jnp.ndarray,          # (b, hq, d)
    k_pool: jnp.ndarray,     # ([L,] N, page_size, hkv * d) physical pool
    v_pool: jnp.ndarray,     # ([L,] N, page_size, hkv * d)
    pages: jnp.ndarray,      # (b, P) int32 page table, -1 = unmapped
    lengths: jnp.ndarray | None = None,   # (b,) valid lengths, <= P*psz
    layer: jnp.ndarray | None = None,     # which of L stacked pools
    *,
    scale: float | None = None,
    splits: int = 1,
    pages_per_block: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if k_pool.ndim == 3:
        k_pool, v_pool = k_pool[None], v_pool[None]
    layer = jnp.reshape(0 if layer is None else layer, (1,)).astype(jnp.int32)
    b, hq, d = q.shape
    _, N, psz, hd = k_pool.shape
    hkv = hd // d
    P = pages.shape[1]
    assert hq % hkv == 0 and hkv * d == hd
    scale = float(scale if scale is not None else d ** -0.5)
    lengths = (jnp.full((b,), P * psz, jnp.int32) if lengths is None
               else jnp.minimum(lengths.astype(jnp.int32), P * psz))
    ppb = max(1, min(int(pages_per_block), P))
    splits = max(1, min(int(splits), cdiv(P, ppb)))
    blocks_per_split = cdiv(cdiv(P, ppb), splits)
    items = b * blocks_per_split
    count, slot, blk = _work_items(lengths, psz, ppb, splits,
                                   blocks_per_split)

    def split_block(width):     # every slot's partials of split sp
        return pl.BlockSpec((b, None, hq, width),
                            lambda sp, *_: (0, sp, 0, 0))

    acc, m, l = pl.pallas_call(
        functools.partial(
            _paged_kernel, psz=psz, ppb=ppb, n_pages=P, pool_pages=N,
            items=items, blocks_per_split=blocks_per_split, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(splits,),
            in_specs=[
                pl.BlockSpec((b, hq, hd), lambda sp, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[split_block(hd), split_block(1), split_block(1)],
            scratch_shapes=[
                pltpu.VMEM((2, ppb * psz, hd), k_pool.dtype),
                pltpu.VMEM((2, ppb * psz, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, splits, hq, hd), _F32),
            jax.ShapeDtypeStruct((b, splits, hq, 1), _F32),
            jax.ShapeDtypeStruct((b, splits, hq, 1), _F32),
        ],
        compiler_params=compiler_params("parallel"),
        interpret=interpret_mode(interpret),
    )(lengths, pages.astype(jnp.int32).reshape(-1), count, slot, blk, layer,
      _block_diagonal(q, hkv, k_pool.dtype), k_pool, v_pool)
    return combine_partials(*_partials(acc, m, l, hkv)).astype(q.dtype)
