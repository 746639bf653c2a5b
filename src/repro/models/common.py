"""Shared model blocks: norms, RoPE, GQA attention, MLP, MoE — pure JAX.

Models are parameterized as nested dicts of jnp arrays (stacked over layers
for scan).  Weight layouts keep named logical axes so the sharding rules in
``repro.distributed.sharding`` can map them onto the mesh:

    attention:  wq (d, hq, dh)   wk/wv (d, hkv, dh)   wo (hq, dh, d)
    mlp:        wg/wu (d, f)     wd (f, d)
    moe:        router (d, E)    wg/wu (e, d, f)      wd (e, f, d)
                (E experts routed over, e of them held here)
    embed:      (v, d)           unembed (d, v)

The XLA path (these functions) is what trains and what the dry-run lowers;
the Pallas kernels in ``repro.kernels`` are the TPU hot-spot implementations
validated against the same math.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FULL, SLIDING, ModelConfig, YaRN

Params = dict[str, Any]

# ---------------------------------------------------------------------------
# Scan wrapper: dry-run cost counting needs fully-unrolled loops because XLA
# cost_analysis counts a while body once regardless of trip count.  Models
# call cm.scan(...); launch/dryrun flips the flag for the reduced-depth
# counting lowers only.
# ---------------------------------------------------------------------------

_SCAN_UNROLL = False


def set_scan_unroll(flag: bool) -> None:
    global _SCAN_UNROLL
    _SCAN_UNROLL = flag


def scan(body, carry, xs, length: int | None = None):
    if _SCAN_UNROLL:
        n = length
        if n is None:
            n = jax.tree_util.tree_leaves(xs)[0].shape[0]
        return jax.lax.scan(body, carry, xs, length=length, unroll=n)
    return jax.lax.scan(body, carry, xs, length=length)


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size: int, dtype) -> jnp.ndarray:
    scale = in_axis_size ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, d: int | None = None) -> Params:
    d = d or cfg.d_model
    p = {"w": jnp.ones((d,), jnp.float32)}
    if cfg.norm_kind == "layer":
        p["b"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    h = x.astype(jnp.float32)
    if cfg.norm_kind == "layer":
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        y = (h - mu) * jax.lax.rsqrt(var + cfg.norm_eps) * p["w"] + p["b"]
    else:
        ms = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        y = h * jax.lax.rsqrt(ms + cfg.norm_eps) * p["w"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_inv_freq(dh: int, theta: float, yarn: YaRN) -> np.ndarray:
    """YaRN's (half,) inverse frequencies, as transformers'
    ``_compute_yarn_parameters`` computes them with ``truncate`` on."""
    pos_freqs = theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    extrapolated, interpolated = 1.0 / pos_freqs, 1.0 / (yarn.factor * pos_freqs)

    def correction_dim(rotations):
        return (dh * math.log(yarn.original_max_position_embeddings
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dh - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dh // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp              # share of the extrapolated frequency
    return (interpolated * ramp + extrapolated * keep).astype(np.float32)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         yarn: YaRN | None = None) -> jnp.ndarray:
    """x: (b, s, h, dh); positions: (b, s) or (s,).  With ``yarn`` the
    frequencies are YaRN's and cos and sin carry its attention factor."""
    dh = x.shape[-1]
    half = dh // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn_inv_freq(dh, theta, yarn))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (b, s, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.scale, sin * yarn.scale
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA self / cross), train + cached decode
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, kv_d_model: int | None = None) -> Params:
    d = cfg.d_model
    kd = kv_d_model or d
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, hq, dh), d, dt),
        "wk": dense_init(ks[1], (kd, hkv, dh), kd, dt),
        "wv": dense_init(ks[2], (kd, hkv, dh), kd, dt),
        "wo": dense_init(ks[3], (hq, dh, d), hq * dh, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, dh), dt)
        p["bk"] = jnp.zeros((hkv, dh), dt)
        p["bv"] = jnp.zeros((hkv, dh), dt)
    return p


# Above this many score elements per (batch, head), attention switches to
# the blocked online-softmax path (never materializes s x t scores) — the
# XLA-graph twin of the flash_attention Pallas kernel's STREAM-KV /
# RESIDENT_ACCUM-output policy.  Large-t decode also chunks: the KV stream
# is huge even though s=1.
_SDPA_CHUNK_THRESHOLD = 4096 * 2048
_SDPA_DECODE_T = 8192
# Float32 logits above this many bytes in all (batch x heads x s x t)
# cannot sit beside a chip's weights and cache: they go blocked too, and
# each block of the blocked path stays under _SDPA_BLOCK_BYTES.
_SDPA_LOGITS_BYTES = 5 << 30
_SDPA_BLOCK_BYTES = 512 << 20


def _ambient_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside any mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _ambient_model_axis() -> int | None:
    """Size of the 'model' axis of the ambient mesh, if it has one."""
    mesh = _ambient_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        return int(mesh.shape["model"])
    return None


def _maybe_shard(x, spec_dims: tuple):
    """with_sharding_constraint if an ambient mesh provides the axes and
    every named dim divides evenly; no-op otherwise (tests, single dev)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    from jax.sharding import PartitionSpec as P

    for i, axis in enumerate(spec_dims):
        if axis is None:
            continue
        names = (axis,) if isinstance(axis, str) else tuple(axis)
        size = 1
        for n in names:
            if n not in mesh.axis_names:
                return x
            size *= mesh.shape[n]
        if x.shape[i] % size != 0:
            return x
    return jax.lax.with_sharding_constraint(x, P(*spec_dims))


def _offset_rows(q_offset) -> jnp.ndarray:
    """Normalize a query-position offset to a (B,) vector, B in {1, b}.

    A scalar offset is the uniform-cursor case; a (b,) vector carries the
    per-slot ragged cursors of continuous batching."""
    off = jnp.asarray(q_offset)
    return off[None] if off.ndim == 0 else off


def _sdpa_naive(q, k, v, causal: bool, q_offset, kv_len=None, window=0,
                kv_start=None):
    """q: (b, s, hq, dh); k/v: (b, t, hkv, dh). fp32 softmax.

    Key j is visible to the query at position i = q_offset + row when
    j <= i (causal), i - j < window (a sliding window, when set),
    kv_start[b] <= j (keys below it hold nothing yet) and j < kv_len[b]."""
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qf = q.astype(jnp.float32) * (dh ** -0.5)
    qf = qf.reshape(b, s, hkv, group, dh)
    logits = jnp.einsum("bshgd,bthd->bhgst", qf, k.astype(jnp.float32))
    if causal:
        off = _offset_rows(q_offset)                          # (1,) or (b,)
        qi = off[:, None, None] + jnp.arange(s)[None, :, None]
        ki = jnp.arange(t)[None, None, :]
        mask = ki <= qi                                       # (B, s, t)
        if window:
            mask = mask & (qi - ki < window)
        logits = jnp.where(mask[:, None, None], logits, -1e30)
    if kv_len is not None:
        mask = jnp.arange(t)[None, :] < kv_len[:, None]          # (b, t)
        logits = jnp.where(mask[:, None, None, None, :], logits, -1e30)
    if kv_start is not None:
        mask = jnp.arange(t)[None, :] >= kv_start[:, None]       # (b, t)
        logits = jnp.where(mask[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, hq, dh).astype(q.dtype)


def _chunk_sizes(s: int, t: int, rows: int = 1) -> tuple[int, int]:
    """Block shapes bounding both the live logits buffer (rows*qb*ck, rows
    = batch x heads) and the loop trip count (<= ~8x8)."""
    qb = min(s, max(1024, -(-s // 8)))
    ck = min(t, max(1024, -(-t // 8)))
    while rows * qb * ck * 4 > _SDPA_BLOCK_BYTES and max(qb, ck) > 128:
        if qb >= ck:
            qb = -(-qb // 2)
        else:
            ck = -(-ck // 2)
    return qb, ck


def _sdpa_chunked(q, k, v, causal: bool, q_offset, kv_len=None,
                  chunk: int | None = None, q_block: int | None = None,
                  shard_rows: bool = False, window: int = 0, kv_start=None):
    """Blocked online-softmax attention: outer loop over q blocks, inner
    scan over KV chunks; masks as :func:`_sdpa_naive`.  ``shard_rows``
    hints GSPMD to reduce-scatter the per-chunk logits over `model` along q
    rows (used when heads are not TP-shardable, e.g. minicpm/whisper/qwen
    head counts).

    Chunks no query can see are skipped, not masked: with a window and a
    static ``q_offset`` each q block scans only the chunks its window and
    the causal edge reach, and a chunk wholly at or past every slot's
    ``kv_len`` (or wholly below every ``kv_start``) is passed over at run
    time.  A skipped chunk would have folded nothing in, so no bit
    changes."""
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qb, ck = _chunk_sizes(s, t, b * hq)
    if chunk is not None:
        ck = chunk
    if q_block is not None:
        qb = min(q_block, s)
    qpad, tpad = (-s) % qb, (-t) % ck
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0)))
    if tpad:
        k = jnp.pad(k, ((0, 0), (0, tpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, tpad), (0, 0), (0, 0)))
    nq, nc = (s + qpad) // qb, (t + tpad) // ck
    qf = (q.astype(jnp.float32) * (dh ** -0.5)).reshape(
        b, nq, qb, hkv, group, dh
    )
    qf = jnp.moveaxis(qf, 1, 0)                               # (nq, b, qb, ...)
    kc = jnp.moveaxis(k.astype(jnp.float32).reshape(b, nc, ck, hkv, dh), 1, 0)
    vc = jnp.moveaxis(v.astype(jnp.float32).reshape(b, nc, ck, hkv, dh), 1, 0)
    valid = kv_len if kv_len is not None else jnp.full((b,), t)
    off = _offset_rows(q_offset)                              # (1,) or (b,)
    # Run-time skip bounds: chunks outside [lo_chunk, hi_chunk) hold no
    # visible key for any slot.
    hi_chunk = (jnp.max(valid) + ck - 1) // ck
    lo_chunk = (jnp.min(kv_start) // ck if kv_start is not None
                else jnp.int32(0))

    def q_block_out(qblk, iq, kcs, vcs, j0):
        qi = iq * qb + jnp.arange(qb)[None, :, None] + off[:, None, None]

        def fold(carry, kj, vj, j):
            m_prev, l_prev, acc = carry
            ki = j * ck + jnp.arange(ck)[None, :]             # (1, ck)
            logits = jnp.einsum("bshgd,bthd->bhgst", qblk, kj)
            logits = _maybe_shard(
                logits, (None, None, None, "model" if shard_rows else None,
                         None),
            )
            mask = ki[None] < valid[:, None, None]            # (b, 1, ck)
            if kv_start is not None:
                mask = mask & (ki[None] >= kv_start[:, None, None])
            if causal:
                mask = mask & (ki[None] <= qi)                # (b, qb, ck)
                if window:
                    mask = mask & (qi - ki[None] < window)
            logits = jnp.where(mask[:, None, None], logits, -1e30)
            m_cur = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(logits - m_cur[..., None])
            l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgst,bthd->bhgsd", p, vj
            )
            return m_cur, l_cur, acc

        def kv_body(carry, inp):
            kj, vj, j = inp
            if kv_len is None and kv_start is None:
                return fold(carry, kj, vj, j), None
            live = (j < hi_chunk) & (j >= lo_chunk)
            return jax.lax.cond(live, lambda c: fold(c, kj, vj, j),
                                lambda c: c, carry), None

        m0 = jnp.full((b, hkv, group, qb), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, group, qb), jnp.float32)
        acc0 = jnp.zeros((b, hkv, group, qb, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, acc0),
            (kcs, vcs, j0 + jnp.arange(kcs.shape[0])),
        )
        return acc / jnp.maximum(l, 1e-30)[..., None]         # (b,hkv,g,qb,dh)

    if window and causal and isinstance(q_offset, int):
        # Static reach of each q block: keys (first - window, last].
        outs = []
        for iq in range(nq):
            first = q_offset + iq * qb
            j0 = max(0, first - window + 1) // ck
            j1 = min(nc, (first + qb - 1) // ck + 1)
            outs.append(q_block_out(qf[iq], iq, kc[j0:j1], vc[j0:j1], j0))
        outs = jnp.stack(outs)
    else:
        _, outs = jax.lax.scan(
            lambda _, q_in: (None, q_block_out(q_in[0], q_in[1], kc, vc, 0)),
            None, (qf, jnp.arange(nq)),
        )
    # (nq, b, hkv, g, qb, dh) -> (b, s, hq, dh)
    outs = jnp.moveaxis(outs, 0, 1).reshape(b, nq, hq, qb, dh)
    outs = jnp.moveaxis(outs, 2, 3).reshape(b, nq * qb, hq, dh)
    return outs[:, :s].astype(q.dtype)


def _sdpa(q, k, v, causal: bool, q_offset, kv_len=None, window=0,
          kv_start=None):
    b, s, hq, dh = q.shape
    t = k.shape[1]
    # Row-shard the logits when heads cannot be TP-sharded (minicpm 36H,
    # whisper 12H, qwen 40H): the dh-contraction then reduce-scatters
    # instead of all-reducing, bounding the per-chip buffer.
    tp = _ambient_model_axis()
    shard_rows = tp is not None and hq % tp != 0
    if (s * t > _SDPA_CHUNK_THRESHOLD or t > _SDPA_DECODE_T
            or b * hq * s * t * 4 > _SDPA_LOGITS_BYTES):
        return _sdpa_chunked(q, k, v, causal, q_offset, kv_len,
                             shard_rows=shard_rows, window=window,
                             kv_start=kv_start)
    return _sdpa_naive(q, k, v, causal, q_offset, kv_len, window, kv_start)


def seg_mask(s: int, seg_lens: jnp.ndarray | None) -> jnp.ndarray | None:
    """(b, s) validity mask for a ragged block: col i valid iff i < seg_lens[b]."""
    if seg_lens is None:
        return None
    return jnp.arange(s)[None, :] < seg_lens[:, None]


def last_valid_slice(x: jnp.ndarray, seg_lens: jnp.ndarray | None) -> jnp.ndarray:
    """Gather each slot's last *valid* position: x (b, s, d) -> (b, 1, d).

    seg_lens None means the whole block is valid (uniform prefill) — the
    seed's ``x[:, -1:]``.  Slots with seg_lens == 0 return row 0 (garbage
    by contract; the serve engine never reads them)."""
    if seg_lens is None:
        return x[:, -1:]
    idx = jnp.clip(seg_lens - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)


def append_kv(cache_kv: jnp.ndarray, new: jnp.ndarray, lengths: jnp.ndarray,
              seg_lens: jnp.ndarray | None,
              layer: jnp.ndarray | None = None) -> jnp.ndarray:
    """Scatter a (b, s, ...) block into a (b, S, ...) ring at per-slot
    cursors, or, given ``layer``, into that layer's ring of an (L, b, S,
    ...) stack, in place.

    Row i of slot b lands at position lengths[b] + i.  Invalid rows
    (i >= seg_lens[b]) and overflow (pos >= S) are redirected out of bounds
    and DROPPED by the scatter — padding never lands in the cache and a
    full slot can never clobber its own valid tail."""
    b, s = new.shape[:2]
    S = cache_kv.shape[-3]
    pos = lengths[:, None] + jnp.arange(s)[None, :]           # (b, s)
    valid = seg_mask(s, seg_lens)
    if valid is not None:
        pos = jnp.where(valid, pos, S)
    at = (jnp.arange(b)[:, None], pos)
    if layer is not None:
        at = (layer,) + at
    return cache_kv.at[at].set(new.astype(cache_kv.dtype), mode="drop")


# ---------------------------------------------------------------------------
# Paged KV layout (DESIGN.md §5.2): K/V live in an (n_pages, page_size,
# hkv * head_dim) pool shared across slots; a per-slot page table (b,
# pages_per_slot) maps logical page indices to physical page ids (-1 =
# unmapped).  Heads are flattened into the minor dim: on a TPU a minor dim
# below 128 lanes (head_dim 64) pads every row to 128, and XLA then keeps
# padded relayout copies of the whole pool inside the decode loop, which
# does not fit one chip at minicpm-2b width; hkv * head_dim is a multiple of
# 128 for the registered models, and one page is one (page_size, hkv *
# head_dim) tile for the Pallas decode kernel.  The serve engine's host-side
# free-list assigns pages at admission, so HBM cost follows each request's
# actual footprint instead of slots x max_len.
#
# Nothing here knows whether two tables alias the same physical page:
# gather/scatter are pure functions of (pool, table), so prefix sharing
# (DESIGN.md §5.4) is entirely a host-side page-table/refcount concern —
# slots whose tables map a shared page read identical bytes, and write
# isolation holds because the engine only ever shares pages that sit
# wholly below every sharer's cursor (the scatter never writes below
# `lengths`, and drop-semantics fence everything else).
# ---------------------------------------------------------------------------

def paged_kv_spec(batch: int, max_len: int, page_size: int,
                  n_pages: int | None = None) -> tuple[int, int]:
    """(pages_per_slot, n_pages) for a paged pool over ``batch`` slots.

    ``n_pages`` None sizes the pool to full contiguous capacity (every slot
    can hold max_len); the serve engine passes a smaller pool to
    oversubscribe."""
    per_slot = -(-max_len // page_size)
    return per_slot, (batch * per_slot if n_pages is None else n_pages)


def paged_kv_buffers(lead: tuple, batch: int, max_len: int, cfg,
                     n_pages: int | None = None):
    """Zeroed paged K/V pool with leading stack axes ``lead``, plus the
    all-unmapped (batch, pages_per_slot) page table — the shared cache-init
    path for every paged cache family."""
    per_slot, N = paged_kv_spec(batch, max_len, cfg.kv_page_size, n_pages)
    shape = (*lead, N, cfg.kv_page_size, cfg.n_kv_heads * cfg.head_dim_)
    dt = jnp.dtype(cfg.dtype)
    kv = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    return kv, jnp.full((batch, per_slot), -1, jnp.int32)


def append_kv_paged(pool: jnp.ndarray, new: jnp.ndarray, lengths: jnp.ndarray,
                    seg_lens: jnp.ndarray | None, pages: jnp.ndarray,
                    layer: jnp.ndarray | None = None) -> jnp.ndarray:
    """Scatter a (b, s, hkv, d) block into an (N, page_size, hkv * d) pool,
    or, given ``layer``, into that layer's pool of an (L, N, ...) stack, in
    place.

    Row i of slot b lands at logical position ``lengths[b] + i``, translated
    through ``pages`` (b, P) to physical page ``pages[b, pos // page_size]``,
    offset ``pos % page_size``.  Invalid rows (i >= seg_lens[b]), positions
    beyond the mapped page range, and unmapped pages (-1) all redirect to
    physical page N and are DROPPED by the scatter — the paged twin of
    :func:`append_kv`'s overflow semantics."""
    b, s = new.shape[:2]
    N, psz = pool.shape[-3], pool.shape[-2]
    P = pages.shape[1]
    pos = lengths[:, None] + jnp.arange(s)[None, :]           # (b, s)
    pi, wi = pos // psz, pos % psz
    phys = jnp.take_along_axis(pages, jnp.clip(pi, 0, P - 1), axis=1)
    drop = (pi >= P) | (phys < 0)
    valid = seg_mask(s, seg_lens)
    if valid is not None:
        drop = drop | ~valid
    phys = jnp.where(drop, N, phys)
    at = (phys.reshape(-1), wi.reshape(-1))
    if layer is not None:
        at = (layer,) + at
    return pool.at[at].set(
        new.reshape(b * s, -1).astype(pool.dtype), mode="drop"
    )


def gather_pages(pool: jnp.ndarray, pages: jnp.ndarray,
                 layer: jnp.ndarray | None = None) -> jnp.ndarray:
    """(N, page_size, hkv * d) pool + (b, P) table -> dense (b, P*page_size,
    hkv * d): the contiguous ring's rows, heads still flattened.  Given
    ``layer``, the pool is that layer's of an (L, N, ...) stack, gathered
    from the stack directly.

    Unmapped entries (-1) clamp to page 0: their content is garbage by
    contract and masked by the caller's ``kv_len``, exactly like the stale
    tail bytes of the contiguous ring.  With page_size dividing max_len the
    gathered width equals the contiguous ring width, so the downstream
    online-softmax is bit-identical between layouts.

    Layout-pure under sharing: the gather depends only on (pool bytes,
    table entries), never on which slot "owns" a page — tables that alias
    the same physical page (prefix sharing, DESIGN.md §5.4) materialize
    bit-identical rows for the aliased positions, whichever dispatch wrote
    them (a same-wave sharer's prefill runs after its owner's, so it reads
    the owner's fresh K/V)."""
    N, psz, hd = pool.shape[-3:]
    b, P = pages.shape
    idx = jnp.clip(pages, 0, N - 1)
    if layer is not None:
        pool, idx = pool.reshape(-1, psz, hd), layer * N + idx
    g = jnp.take(pool, idx, axis=0)                           # (b, P, psz, hd)
    return g.reshape(b, P * psz, hd)


# ---------------------------------------------------------------------------
# Window ring (DESIGN.md §5.2): a sliding layer's keys live in a per-slot
# ring of exactly ``sliding_window`` (W) positions — position p at row
# p % W — laid out as pages of the paged pool, (L, b * W / page_size,
# page_size, hkv * head_dim), with a fixed table: slot b owns pages
# [b * W / page_size, (b + 1) * W / page_size).  Rows [0, min(len, W)) of a
# slot always hold its last min(len, W) positions, so a decode step reads
# the ring in place with ``kv_len = min(len + 1, W)``: the paged kernel, or
# the gather path, unchanged (softmax is blind to row order).  The ring
# never grows, whatever the context: W positions per slot and layer.
# ---------------------------------------------------------------------------

def window_ring_buffers(lead: tuple, batch: int, cfg) -> Params:
    """Zeroed window rings with leading stack axes ``lead``."""
    psz, hd = cfg.kv_page_size, cfg.n_kv_heads * cfg.head_dim_
    shape = (*lead, batch * cfg.sliding_window // psz, psz, hd)
    dt = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def ring_table(batch: int, window: int, page_size: int) -> jnp.ndarray:
    """(b, W / page_size) page table of the window rings."""
    n = window // page_size
    return jnp.arange(batch * n, dtype=jnp.int32).reshape(batch, n)


def append_ring(ring: jnp.ndarray, new: jnp.ndarray, lengths: jnp.ndarray,
                seg_lens: jnp.ndarray | None, window: int,
                layer: jnp.ndarray | None = None) -> jnp.ndarray:
    """Scatter a (b, s, hkv, d) block into the slots' window rings (of
    layer ``layer`` of a stack), in place: position p of slot b at row
    p % W.  Only each slot's last W valid rows are written, so no two rows
    of one scatter meet; invalid rows drop."""
    b, s = new.shape[:2]
    N, psz = ring.shape[-3], ring.shape[-2]
    per_slot = window // psz
    i = jnp.arange(s)[None, :]
    seg = jnp.full((b,), s) if seg_lens is None else seg_lens
    keep = (i < seg[:, None]) & (i >= seg[:, None] - window)
    r = (lengths[:, None] + i) % window                       # (b, s)
    phys = jnp.where(keep, jnp.arange(b)[:, None] * per_slot + r // psz, N)
    at = (phys.reshape(-1), (r % psz).reshape(-1))
    if layer is not None:
        at = (layer,) + at
    return ring.at[at].set(
        new.reshape(b * s, -1).astype(ring.dtype), mode="drop"
    )


def _window_attn(q, k, v, cache, cfg, seg_lens, window):
    """Sliding-window attention against the slots' rings; returns (out,
    new ring).

    Decode (s == 1): append, then attend over the ring in place with
    ``kv_len = min(len + 1, W)``.  A block (prefill): the ring's W rows in
    position order (key j is position ``len - W + j``; negative positions
    are masked by ``kv_start``) and then the block, so query i sits at key
    index W + i and sees keys (i, W + i]: the blocked path skips every
    chunk of keys outside that reach.  The block's last W valid rows then
    replace the ring's oldest."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    lengths, layer = cache["lengths"], cache.get("layer")
    table = ring_table(b, window, cfg.kv_page_size)
    seg = jnp.full((b,), s, jnp.int32) if seg_lens is None else seg_lens
    if s == 1:
        kc = append_ring(cache["k"], k, lengths, seg_lens, window, layer)
        vc = append_ring(cache["v"], v, lengths, seg_lens, window, layer)
        kv_len = jnp.minimum(lengths + seg, window)
        if cfg.decode_kernel in ("pallas_paged", "pallas_gather"):
            out = _decode_step_kernel(q, kc, vc, kv_len, cfg, table, layer)
        else:
            kd, vd = (gather_pages(x, table, layer).reshape(b, window, hkv, dh)
                      for x in (kc, vc))
            out = _sdpa(q, kd, vd, causal=False, q_offset=0, kv_len=kv_len)
        return out, {"k": kc, "v": vc}
    order = (lengths[:, None] + jnp.arange(window)[None, :]) % window
    old_k, old_v = (jnp.take_along_axis(
        gather_pages(x, table, layer), order[..., None], axis=1
    ).reshape(b, window, hkv, dh) for x in (cache["k"], cache["v"]))
    keys = jnp.concatenate([old_k, k.astype(old_k.dtype)], axis=1)
    vals = jnp.concatenate([old_v, v.astype(old_v.dtype)], axis=1)
    out = _sdpa_chunked(q, keys, vals, causal=True, q_offset=window,
                        kv_len=window + seg, window=window,
                        kv_start=window - lengths)
    kc = append_ring(cache["k"], k, lengths, seg_lens, window, layer)
    vc = append_ring(cache["v"], v, lengths, seg_lens, window, layer)
    return out, {"k": kc, "v": vc}


def _decode_step_kernel(q, kc, vc, kv_len, cfg, pages, layer=None):
    """Route the s == 1 decode step through the Pallas split-KV kernels.

    A single causal query sits at its slot's cursor, so the causal mask is
    subsumed by the length mask (``ki <= cursor``  <=>  ``ki < kv_len``):
    the kernels' per-slot ``lengths`` masking reproduces ``_sdpa``'s
    causal + ``kv_len`` masking exactly.

    ``pallas_paged`` on a paged cache reads only the resident pages of the
    pool, in place (no ``gather_pages`` copy); ``pallas_gather`` runs the
    dense kernel over the gathered view with the same compute block
    (``pages_per_block x page_size`` positions) and splits, which makes it
    the bit-identity reference for the paged path (see
    kernels/decode_attention).  On a contiguous cache both fall back to the
    dense kernel over the ring.  The kernels plan their blocks and splits
    from the shapes they receive and the chip (``dec_ops.split_count``).
    """
    from repro.kernels.decode_attention import ops as dec_ops

    q1 = q[:, 0]                                       # (b, hq, dh)
    if pages is not None:
        if cfg.decode_kernel == "pallas_paged":
            out = dec_ops.paged_decode_attention(
                q1, kc, vc, pages, kv_len, layer
            )
        else:
            b, hq, dh = q1.shape
            psz, hd = kc.shape[-2:]
            n_pages = pages.shape[1]
            ppb = dec_ops.pages_per_block(psz * hd * kc.dtype.itemsize,
                                          n_pages)
            kd, vd = (jnp.swapaxes(gather_pages(x, pages, layer).reshape(
                b, n_pages * psz, -1, dh), 1, 2) for x in (kc, vc))
            out = dec_ops.decode_attention(q1, kd, vd, kv_len, bkv=ppb * psz)
    else:
        if layer is not None:
            kc, vc = kc[layer], vc[layer]
        out = dec_ops.decode_attention(
            q1, jnp.swapaxes(kc, 1, 2), jnp.swapaxes(vc, 1, 2), kv_len)
    return out[:, None]                                # (b, 1, hq, dh)


def apply_attn(
    p: Params,
    x: jnp.ndarray,                   # (b, s, d)
    cfg: ModelConfig,
    positions: jnp.ndarray,           # (b, s) or (s,)
    kv_src: jnp.ndarray | None = None,  # cross-attn source (b, t, d)
    cache: Params | None = None,      # {"k","v": (b, S, hkv, dh), "lengths": (b,)}
    causal: bool = True,
    use_rope: bool = True,
    seg_lens: jnp.ndarray | None = None,  # (b,) valid new tokens per slot
    kind: str = FULL,                 # the layer's attention kind
) -> tuple[jnp.ndarray, Params | None]:
    """Self- or cross-attention.  A SLIDING layer sees the last
    ``cfg.sliding_window`` positions (its cache is a window ring); a FULL
    layer takes ``cfg.rope_yarn``'s frequencies, if any.  A cache with a
    ``layer`` index holds every layer's K/V of its kind stacked, updated in
    place."""
    b, s, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    src = x if kv_src is None else kv_src
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", src, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", src, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if use_rope and kv_src is None:
        yarn = cfg.rope_yarn if kind == FULL else None
        q = rope(q, positions, cfg.rope_theta, yarn)
        k = rope(k, positions, cfg.rope_theta, yarn)
    window = cfg.sliding_window if kind == SLIDING else 0

    # KV append, page gather or paged kernel, and the attention math:
    # what a change of decode-attention implementation replaces.
    with jax.named_scope("attn"):
        if window and cache is not None:
            out, new_cache = _window_attn(q, k, v, cache, cfg, seg_lens,
                                          window)
            return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache
        new_cache = None
        kv_len = None
        kernel_out = None
        q_offset: Any = 0
        is_cross_cached = cache is not None and "lengths" not in cache
        if cache is not None:
            if kv_src is None and not is_cross_cached:
                # Self-attention decode/prefill-append: scatter at per-slot
                # cursors.  cache["lengths"] is the (b,) int32 ragged
                # cursor vector; slots free and re-admit independently.
                # Positions at or beyond each slot's valid length hold
                # stale bytes but are masked out via kv_len below and
                # overwritten as the cursor advances.
                lengths = cache["lengths"]
                if "pages" in cache:
                    # Paged pool: scatter through the page table (into
                    # layer ``cache["layer"]`` of an (L, N, ...) stack when
                    # given).  The XLA path then gathers a dense per-slot
                    # view for the masked online-softmax; the paged
                    # decode-step kernel below reads only the resident pages
                    # in place instead.
                    pages, layer = cache["pages"], cache.get("layer")
                    kc = append_kv_paged(cache["k"], k, lengths,
                                         seg_lens, pages, layer)
                    vc = append_kv_paged(cache["v"], v, lengths,
                                         seg_lens, pages, layer)
                else:
                    layer = cache.get("layer")
                    kc = append_kv(cache["k"], k, lengths, seg_lens, layer)
                    vc = append_kv(cache["v"], v, lengths, seg_lens, layer)
                kv_len = lengths + (
                    jnp.int32(s) if seg_lens is None else seg_lens
                )
                new_cache = {"k": kc, "v": vc}
                q_offset = lengths
                if (cfg.decode_kernel in ("pallas_paged", "pallas_gather")
                        and s == 1 and causal):
                    kernel_out = _decode_step_kernel(
                        q, kc, vc, kv_len, cfg, cache.get("pages"),
                        cache.get("layer"),
                    )
                elif "pages" in cache:
                    k = gather_pages(kc, pages, layer).reshape(b, -1, hkv, dh)
                    v = gather_pages(vc, pages, layer).reshape(b, -1, hkv, dh)
                elif layer is not None:
                    k, v = kc[layer], vc[layer]
                else:
                    k, v = kc, vc
            else:
                # Cross-attention: cache holds precomputed source K/V.
                k, v = cache["k"], cache["v"]
                new_cache = cache
        is_cross = kv_src is not None or is_cross_cached
        if kernel_out is not None:
            out = kernel_out
        else:
            out = _sdpa(q, k, v, causal=causal and not is_cross,
                        q_offset=q_offset, kv_len=kv_len, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {
            "wg": dense_init(ks[0], (d, f), d, dt),
            "wu": dense_init(ks[1], (d, f), d, dt),
            "wd": dense_init(ks[2], (f, d), f, dt),
        }
    return {
        "wu": dense_init(ks[0], (d, f), d, dt),
        "wd": dense_init(ks[1], (f, d), f, dt),
    }


def apply_mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    with jax.named_scope("mlp"):
        if cfg.act == "swiglu":
            h = jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])
        else:
            h = jax.nn.gelu(x @ p["wu"])
        return h @ p["wd"]


# ---------------------------------------------------------------------------
# MoE (top-k routing over every expert; this chip's experts computed)
# ---------------------------------------------------------------------------

# Token rows per block of the dense expert dispatch: a prefill wave's rows
# go through the held experts a block at a time, so its (experts, rows,
# d_expert) activations stay a few hundred MB.
_MOE_ROW_BLOCK = 4096


def moe_init(key, cfg: ModelConfig) -> Params:
    """The router over all ``n_experts``; the weights of the experts this
    chip holds (``experts_held`` of them)."""
    d, f, e = cfg.d_model, cfg.d_expert_, cfg.experts_held
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, cfg.n_experts), d, jnp.float32),
        "wg": dense_init(ks[1], (e, d, f), d, dt),
        "wu": dense_init(ks[2], (e, d, f), d, dt),
        "wd": dense_init(ks[3], (e, f, d), f, dt),
    }


def _route(p, xf, cfg: ModelConfig):
    """float32 softmax over every expert's logit, top-k, renormalized."""
    e, k = cfg.n_experts, cfg.experts_per_token
    logits = (xf.astype(jnp.float32)) @ p["router"]          # (t, e)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                  # (t, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)        # (t, k, e)
    density = jnp.mean(onehot.sum(1), axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_weight * e * jnp.sum(density * prob_mean)
    return gate_vals, idx, onehot, aux


def _held_experts(p, xf, combine):
    """sum_e combine[t, e] * expert_e(x_t) over the held experts, every
    row through every expert (a row's unpicked experts get weight 0)."""
    e = combine.shape[1]

    def rows(xb, cb):
        xe = jnp.broadcast_to(xb[None], (e,) + xb.shape)      # (e, t, d)
        h = jax.nn.silu(jnp.einsum("etd,edf->etf", xe, p["wg"])) * jnp.einsum(
            "etd,edf->etf", xe, p["wu"]
        )
        h = h * cb.T[:, :, None].astype(h.dtype)
        return jnp.einsum("etf,efd->td", h, p["wd"])

    t, d = xf.shape
    if t <= _MOE_ROW_BLOCK:
        return rows(xf, combine)
    n = -(-t // _MOE_ROW_BLOCK)
    pad = n * _MOE_ROW_BLOCK - t
    xb = jnp.pad(xf, ((0, pad), (0, 0))).reshape(n, _MOE_ROW_BLOCK, d)
    cb = jnp.pad(combine, ((0, pad), (0, 0))).reshape(n, _MOE_ROW_BLOCK, e)
    y = jax.lax.map(lambda a: rows(*a), (xb, cb))
    return y.reshape(n * _MOE_ROW_BLOCK, d)[:t]


def apply_moe(p: Params, x: jnp.ndarray, cfg: ModelConfig,
              valid: jnp.ndarray | None = None):
    """Returns (y, aux_loss, picks_held).

    The router scores all ``cfg.n_experts`` experts; this chip holds the
    ``expert_shard``-th of ``expert_shards`` index ranges of them and ``y``
    is those experts' part of each token's result (the whole result when
    one shard holds them all).  ``picks_held`` counts the (token, expert)
    picks of the ``valid`` (b, s) tokens that land on held experts.

    Two dispatch schedules (cfg.moe_dispatch):

    * ``dense`` — every held expert processes every token, weighted by the
      token's renormalized gate for it (0 where unpicked): exact, and
      compute scales with the experts held (useful share ~ picks held /
      experts held: top_k/E, 0.125 for olmoe).  A wide block goes through
      ``_MOE_ROW_BLOCK`` rows at a time.
    * ``sorted`` — capacity-based sorted dispatch (MegaBlocks/GShard
      style): (token, slot) pairs sort by expert, gather into (E, C, d)
      capacity buffers, batched expert GEMM, scatter back.  Compute
      scales with top_k * capacity_factor — the E/(k*cf) FLOP cut the A4
      §Perf iteration quantifies.  Tokens overflowing an expert's
      capacity are dropped (standard GShard semantics).  Whole layers
      only (one shard).
    """
    with jax.named_scope("moe"):
        b, s, d = x.shape
        e, k = cfg.n_experts, cfg.experts_per_token
        held = cfg.experts_held
        first = cfg.expert_shard * held
        xf = x.reshape(b * s, d)
        t = b * s
        gate_vals, idx, onehot, aux = _route(p, xf, cfg)
        mine = (idx >= first) & (idx < first + held)          # (t, k)
        if valid is not None:
            mine = mine & valid.reshape(t, 1)
        picks = jnp.sum(mine, dtype=jnp.int32)

        if cfg.moe_dispatch == "sorted":
            if held != e:
                raise ValueError("sorted MoE dispatch needs every expert "
                                 "on one shard")
            cap = int(-(-t * k * cfg.capacity_factor // e))
            cap = min(max(128, -(-cap // 128) * 128), t * k)
            eidx = idx.reshape(-1)                            # (t*k,)
            gates = gate_vals.reshape(-1).astype(jnp.float32)
            tok = jnp.repeat(jnp.arange(t), k)
            order = jnp.argsort(eidx)                         # stable
            eidx_s, tok_s, gate_s = eidx[order], tok[order], gates[order]
            counts = jnp.bincount(eidx, length=e)
            starts = jnp.cumsum(counts) - counts              # exclusive
            pos = jnp.arange(t * k) - starts[eidx_s]          # rank in expert
            keep = pos < cap
            pos_c = jnp.where(keep, pos, 0)
            eidx_c = jnp.where(keep, eidx_s, 0)
            xe = jnp.zeros((e, cap, d), x.dtype).at[
                eidx_c, pos_c
            ].add(xf[tok_s] * keep[:, None].astype(x.dtype))  # (e, C, d)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wg"])) * (
                jnp.einsum("ecd,edf->ecf", xe, p["wu"]))
            ye = jnp.einsum("ecf,efd->ecd", h, p["wd"])       # (e, C, d)
            contrib = ye[eidx_c, pos_c] * (
                gate_s * keep.astype(jnp.float32)
            )[:, None].astype(x.dtype)
            y = jnp.zeros((t, d), x.dtype).at[tok_s].add(contrib)
            return y.reshape(b, s, d), aux, picks

        # The gates post-expert (y = sum_i g_i * expert_i(x), standard MoE
        # semantics), restricted to the held experts' columns.
        combine = jnp.einsum("tk,tke->te", gate_vals, onehot)
        combine = combine[:, first:first + held]
        y = _held_experts(p, xf, combine)
        return y.reshape(b, s, d).astype(x.dtype), aux, picks


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init_params(key, cfg: ModelConfig) -> Params:
    v, d = cfg.padded_vocab, cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 2)
    p = {"tok": embed_init(ks[0], (v, d), dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (d, v), d, dt)
    return p


def embed(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p: Params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    with jax.named_scope("unembed"):
        if cfg.tie_embeddings:
            return jnp.einsum("bsd,vd->bsv", h, p["tok"])
        return jnp.einsum("bsd,dv->bsv", h, p["unembed"])


# Vocab-chunked logsumexp above this size: never materializes the fp32
# logit tensor (a 51k-vocab, 65k-token device batch would need 12.5 GiB).
_CE_CHUNK_VOCAB = 16384


def _chunked_logsumexp(logits: jnp.ndarray, vocab_valid: int) -> jnp.ndarray:
    v = logits.shape[-1]
    chunk = _CE_CHUNK_VOCAB
    pad = (-v) % chunk
    nc = (v + pad) // chunk
    lead = logits.shape[:-1]
    lc = jnp.moveaxis(
        jnp.pad(logits, [(0, 0)] * (logits.ndim - 1) + [(0, pad)],
                constant_values=-1e30).reshape(*lead, nc, chunk),
        -2, 0,
    )

    def body(carry, inp):
        m_prev, l_prev = carry
        lj, j = inp
        idx = j * chunk + jnp.arange(chunk)
        x = jnp.where(idx < vocab_valid, lj.astype(jnp.float32), -1e30)
        m_cur = jnp.maximum(m_prev, jnp.max(x, axis=-1))
        l_cur = l_prev * jnp.exp(m_prev - m_cur) + jnp.sum(
            jnp.exp(x - m_cur[..., None]), axis=-1
        )
        return (m_cur, l_cur), None

    m0 = jnp.full(lead, -1e30, jnp.float32)
    l0 = jnp.zeros(lead, jnp.float32)
    (m, l), _ = jax.lax.scan(body, (m0, l0), (lc, jnp.arange(nc)))
    return m + jnp.log(jnp.maximum(l, 1e-30))


def cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, vocab_valid: int
) -> jnp.ndarray:
    """Mean next-token loss; padded vocab entries masked out."""
    v = logits.shape[-1]
    if logits.size > 256 * 1024**2 // 4 and v > _CE_CHUNK_VOCAB:
        logz = _chunked_logsumexp(logits, vocab_valid)
        gold = jnp.take_along_axis(
            logits, labels[..., None], axis=-1
        )[..., 0].astype(jnp.float32)
        return jnp.mean(logz - gold)
    lf = logits.astype(jnp.float32)
    if vocab_valid < v:
        pad_mask = jnp.arange(v) < vocab_valid
        lf = jnp.where(pad_mask, lf, -1e30)
    logz = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


@dataclasses.dataclass
class ModelApply:
    """Bundle returned by each model module.

    ``prefill``/``decode_step`` accept an optional keyword ``seg_lens``
    ((b,) int32): the number of valid new tokens per slot in this call.
    None means the whole block is valid for every slot (the uniform path).
    ``seg_lens[b] == 0`` leaves slot b's cache state untouched — how the
    serve engine parks finished slots inside a decode chunk.

    ``prefill(..., all_logits=True)`` returns logits for every position of
    the block ((b, s, v) instead of the last-valid (b, 1, v)) — the
    speculative verify path scores all draft positions in one dispatch
    (DESIGN.md §5.3).  Rows at or beyond ``seg_lens[b]`` are garbage by
    contract, exactly like ``last_valid_slice`` on a parked slot.

    A MoE model's cache carries ``moe_picks_held``: the (token, expert)
    picks on held experts by the valid tokens of the call that returned it
    (the serve engine reads it with the tokens it fetches).

    ``reset_slots(cache, mask)`` clears per-slot recurrent state (cursor,
    SSM/conv state) for slots where mask is True, so a freed slot can be
    re-admitted mid-stream without a fresh cache allocation."""

    config: ModelConfig
    init: Any            # (key) -> params
    forward: Any         # (params, tokens, extras) -> logits
    loss: Any            # (params, batch) -> (loss, metrics)
    init_cache: Any      # (params, batch, max_len, extras) -> cache
    prefill: Any         # (params, cache, tokens, seg_lens) -> (logits, cache)
    decode_step: Any     # (params, cache, tokens, seg_lens) -> (logits, cache)
    reset_slots: Any = None  # (cache, mask (b,) bool) -> cache


def reset_lengths(cache: Params, mask: jnp.ndarray) -> Params:
    """Default reset: rewind the ragged cursor; stale KV is masked/overwritten."""
    cache = dict(cache)
    cache["lengths"] = jnp.where(mask, 0, cache["lengths"]).astype(jnp.int32)
    return cache


def reset_recurrent(cache: Params, mask: jnp.ndarray,
                    state_keys: tuple = ("ssm", "conv")) -> Params:
    """reset_lengths plus zeroed recurrent-state leaves.

    Unlike KV buffers, SSM/conv state has no validity mask — a re-admitted
    slot must start from genuinely zero state.  Each ``state_keys`` entry is
    either a key (batch expected on axis 1, the (L, b, ...) stacked-layer
    layout) or a ``(key, axis)`` pair; the leaf's shape is checked against
    the mask so a cache family with a different batch axis fails loudly
    instead of silently corrupting parked slots.  Leaves not named (e.g.
    zamba2's "kv") pass through untouched."""
    out = reset_lengths(cache, mask)
    b = mask.shape[0]
    keep = ~mask
    for entry in state_keys:
        key, axis = entry if isinstance(entry, tuple) else (entry, 1)
        leaf = cache[key]
        if leaf.ndim <= axis or leaf.shape[axis] != b:
            raise ValueError(
                f"reset_recurrent: cache leaf '{key}' has shape "
                f"{tuple(leaf.shape)} but the batch axis ({axis}) must have "
                f"size {b}; pass (key, axis) in state_keys for this layout"
            )
        shape = [1] * leaf.ndim
        shape[axis] = b
        out[key] = leaf * keep.astype(leaf.dtype).reshape(shape)
    return out
