"""GQA decode attention with split-KV (flash-decoding) Pallas kernel.

Decode is the paper's throughput-sensitive regime personified: the KV cache
is a huge, zero-reuse stream (each cache line is touched exactly once per
step), so the right policy is pure STREAM with maximal HBM bandwidth —
bypass, don't cache.  The only RESIDENT_ACCUM state is the online-softmax
accumulator (hq, d), tiny and revisited every block.

``splits > 1`` partitions the KV sequence across grid workers that each
write (acc, m, l) partials; a cheap log-sum-exp combine merges them.  On
real TPUs the split dimension is marked PARALLEL so Mosaic can spread it
over cores; it is also the schedule the sequence-parallel decoder uses
across chips (see repro/distributed/sp_decode.py).
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

# Grid (b, splits, kv_steps): every axis but the KV sweep is parallel.
_SEMANTICS = ("parallel", "parallel", "arbitrary")
_HIGHEST = jax.lax.Precision.HIGHEST


def _decode_kernel(*refs, n_prefetch: int, group: int, hkv: int, d: int,
                   bkv: int, kv_steps: int, scale: float):
    """One (slot, split) accumulator over ``kv_steps`` KV blocks.

    A block holds every head of ``bkv`` positions as (bkv, hkv * d) rows —
    one page of the paged pool, or ``bkv`` rows of the dense view — so its
    last two dims are whole array dims for any head count or width.  Head
    h's scores sum lanes [h*d, (h+1)*d) of ``q * k``; that segmented sum,
    and the broadcast of per-head weights back over those lanes, are
    matmuls with a 0/1 head-indicator matrix at full f32 precision.

    Shared block for block by the dense and the paged kernels: only the
    index maps that choose each K/V block differ, so with ``bkv ==
    page_size`` and equal splits the two are bit-identical.  The per-slot
    ``lengths`` ride in as the last scalar-prefetch operand (SMEM)."""
    len_ref = refs[n_prefetch - 1]
    (q_ref, k_ref, v_ref, acc_out, m_out, l_out,
     acc_ref, m_ref, l_ref) = refs[n_prefetch:]
    ib = pl.program_id(0)
    s_idx = pl.program_id(1)   # split index
    ik = pl.program_id(2)      # kv block within split
    hd = hkv * d

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # seg[i, h] = 1 iff lane i belongs to head h; bcast is its transpose.
    seg = (jax.lax.broadcasted_iota(jnp.int32, (hd, hkv), 0) // d
           == jax.lax.broadcasted_iota(jnp.int32, (hd, hkv), 1)
           ).astype(jnp.float32)
    bcast = (jax.lax.broadcasted_iota(jnp.int32, (hkv, hd), 1) // d
             == jax.lax.broadcasted_iota(jnp.int32, (hkv, hd), 0)
             ).astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)                   # (bkv, hd)
    v = v_ref[...].astype(jnp.float32)                   # (bkv, hd)
    base = (s_idx * kv_steps + ik) * bkv
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (bkv, hkv), 0)
    mask = pos < len_ref[ib]                             # (bkv, hkv)

    for g in range(group):     # query heads h * group + g, h = 0..hkv-1
        row = pl.ds(g, 1)
        qg = q_ref[row, :].astype(jnp.float32)           # (1, hd)
        s = jnp.dot(qg * k, seg, precision=_HIGHEST,
                    preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)                  # (bkv, hkv)
        m_prev = m_ref[row, :]                           # (1, hkv)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        l_ref[row, :] = l_ref[row, :] * alpha + jnp.sum(p, axis=0,
                                                        keepdims=True)
        pv = jnp.dot(p, bcast, precision=_HIGHEST,
                     preferred_element_type=jnp.float32) * v
        acc_ref[row, :] = acc_ref[row, :] * jnp.dot(
            alpha, bcast, precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        ) + jnp.sum(pv, axis=0, keepdims=True)
        m_ref[row, :] = m_cur

    @pl.when(ik == kv_steps - 1)
    def _flush():
        acc_out[...] = acc_ref[...]
        m_out[...] = m_ref[...]
        l_out[...] = l_ref[...]


def _partials_call(prefetch, q, k, v, kv_spec, *, hkv, splits, kv_steps,
                   bkv, scale, interpret):
    """pallas_call shared by both kernels over grid (b, splits, kv_steps).

    q goes in as (b, group, hkv * d) — row g holds query heads h * group +
    g — and the partials come out as (b, splits, group, ·), returned in
    ``combine_partials``' (b, hq, splits, ·) layout."""
    b, hq, d = q.shape
    group = hq // hkv
    hd = hkv * d
    qg = jnp.swapaxes(q.reshape(b, hkv, group, d), 1, 2).reshape(b, group, hd)

    def out_spec(width):
        return pl.BlockSpec((None, None, group, width),
                            lambda ib, sp, ik, *_: (ib, sp, 0, 0))

    acc, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, n_prefetch=len(prefetch),
                          group=group, hkv=hkv, d=d, bkv=bkv,
                          kv_steps=kv_steps, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, splits, kv_steps),
            in_specs=[
                pl.BlockSpec((None, group, hd),
                             lambda ib, sp, ik, *_: (ib, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=[out_spec(hd), out_spec(hkv), out_spec(hkv)],
            scratch_shapes=[
                pltpu.VMEM((group, hd), jnp.float32),
                pltpu.VMEM((group, hkv), jnp.float32),
                pltpu.VMEM((group, hkv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, splits, group, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, group, hkv), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, group, hkv), jnp.float32),
        ],
        compiler_params=compiler_params(*_SEMANTICS),
        interpret=interpret_mode(interpret),
    )(*prefetch, qg, k, v)
    # (b, splits, group, hkv[, d]) -> (b, hkv, group, splits[, d]) -> hq.
    acc = acc.reshape(b, splits, group, hkv, d).transpose(0, 3, 2, 1, 4)
    m, l = (x.transpose(0, 3, 2, 1) for x in (m, l))
    return (acc.reshape(b, hq, splits, d), m.reshape(b, hq, splits),
            l.reshape(b, hq, splits))


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
            .reshape(b, s_pad, hkv * d) for x in (k, v))
    kv_steps = per_split // bkv

    kv_spec = pl.BlockSpec(
        (None, bkv, hkv * d),
        lambda ib, sp, ik, ln, ks=kv_steps: (ib, sp * ks + ik, 0),
    )
    acc, m, l = _partials_call(
        (lengths.astype(jnp.int32),), q, k, v, kv_spec, hkv=hkv,
        splits=splits, kv_steps=kv_steps, bkv=bkv, scale=scale,
        interpret=interpret,
    )
    return combine_partials(acc, m, l).astype(q.dtype)


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
# Paged variant: dereference the page table inside the kernel.
#
# The paged engine's KV lives in an (N, page_size, hkv * d) pool addressed
# through per-slot page tables (models/common.py, DESIGN.md §5.2).  The
# dense path pays ``gather_pages`` — an XLA copy of the whole resident
# context — before every decode step.  Here the gather disappears: the page
# table rides in as a scalar-prefetch operand, the K/V BlockSpec index maps
# dereference it per grid step, and the pool is read in place, one page per
# block.  Everything downstream (online-softmax accumulator, partials,
# combine_partials merge) is shared with the dense kernel, block for block,
# so with bkv == page_size and equal ``splits`` the two paths are
# bit-identical — the CI identity gate relies on exactly that.
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("scale", "splits", "interpret")
)
def paged_decode_attention(
    q: jnp.ndarray,          # (b, hq, d)
    k_pool: jnp.ndarray,     # (N, page_size, hkv * d) physical page pool
    v_pool: jnp.ndarray,     # (N, page_size, hkv * d)
    pages: jnp.ndarray,      # (b, P) int32 page table, -1 = unmapped
    lengths: jnp.ndarray | None = None,   # (b,) valid lengths, <= P*psz
    *,
    scale: float | None = None,
    splits: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    b, hq, d = q.shape
    N, psz, hd = k_pool.shape
    hkv = hd // d
    P = pages.shape[1]
    assert hq % hkv == 0 and hkv * d == hd
    scale = float(scale if scale is not None else d ** -0.5)
    if lengths is None:
        lengths = jnp.full((b,), P * psz, jnp.int32)

    # One KV block per page: the page table is the block index map, so the
    # split-K decomposition is over logical pages.  Grid overrun past P
    # (when splits does not divide P) is clamped in the map and masked in
    # the kernel — an exact no-op, same as the dense kernel's zero padding.
    splits = max(1, min(int(splits), P))
    page_steps = cdiv(P, splits)

    # Index maps get the grid indices plus the scalar-prefetch refs; the
    # K/V maps dereference the table (clamping unmapped entries to page 0,
    # mirroring gather_pages) so only the referenced page is ever pulled
    # from HBM — no dense per-slot copy exists anywhere.  Unmapped (-1) and
    # grid-overrun pages contribute lanes at pos >= valid_len only, which
    # the kernel's length mask zeroes exactly (p == 0.0, alpha == 1.0).
    kv_spec = pl.BlockSpec(
        (None, psz, hd),
        lambda ib, sp, ik, pt, ln, ps=page_steps, Pn=P, Nn=N: (
            jnp.clip(pt[ib, jnp.minimum(sp * ps + ik, Pn - 1)], 0, Nn - 1),
            0, 0,
        ),
    )
    acc, m, l = _partials_call(
        (pages.astype(jnp.int32), lengths.astype(jnp.int32)), q, k_pool,
        v_pool, kv_spec, hkv=hkv, splits=splits, kv_steps=page_steps,
        bkv=psz, scale=scale, interpret=interpret,
    )
    return combine_partials(acc, m, l).astype(q.dtype)
