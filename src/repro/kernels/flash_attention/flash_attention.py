"""Causal GQA flash attention (Pallas TPU) with policy-driven KV schedule.

Online-softmax attention: grid (batch, q_head, q_blocks, kv_blocks), kv
innermost; the output tile, running max and running sum live in VMEM scratch
across the kv sweep (the RESIDENT_ACCUM policy applied to the attention
output — one HBM writeback per q tile).

KV policy shows up as block sizing from the engine's allocator: small KV
working sets get a large ``bkv`` (whole-KV-resident per (batch, kv_head)),
streaming workloads get double-buffered tiles.  GQA sharing is expressed in
the K/V index maps (q heads in a group revisit the same KV block index — the
VMEM-reuse analogue of the paper's cache hit).

``q_offset`` supports chunked prefill: query position i attends to kv
positions <= i + q_offset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, cdiv, compiler_params, interpret_mode


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    kv_steps: int,
    bq: int,
    bkv: int,
    scale: float,
    causal: bool,
    q_offset: int,
    sq_valid: int,
    skv_valid: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + q_offset
    k_pos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = k_pos < skv_valid
    if causal:
        mask &= k_pos <= q_pos

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)            # (bkv, d)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_cur

    if causal:
        # Skip kv blocks entirely above the causal diagonal.
        first_q_pos = iq * bq + q_offset
        block_needed = ik * bkv <= first_q_pos + bq - 1

        @pl.when(block_needed)
        def _():
            _body()
    else:
        _body()

    @pl.when(ik == kv_steps - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "bq", "bkv", "q_offset", "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,      # (b, hq, sq, d)
    k: jnp.ndarray,      # (b, hkv, skv, d)
    v: jnp.ndarray,      # (b, hkv, skv, d)
    *,
    causal: bool = True,
    scale: float | None = None,
    bq: int = 256,
    bkv: int = 256,
    q_offset: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = float(scale if scale is not None else d ** -0.5)
    bq = min(bq, sq)
    bkv = min(bkv, skv)

    sq_pad = cdiv(sq, bq) * bq
    skv_pad = cdiv(skv, bkv) * bkv
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
    if skv_pad != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_pad - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_pad - skv), (0, 0)))

    kv_steps = skv_pad // bkv
    grid = (b, hq, sq_pad // bq, kv_steps)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            kv_steps=kv_steps, bq=bq, bkv=bkv, scale=scale, causal=causal,
            q_offset=q_offset, sq_valid=sq, skv_valid=skv,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec(
                (1, 1, bkv, d),
                lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, bkv, d),
                lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(q, k, v)
    return out[:, :, :sq, :]
