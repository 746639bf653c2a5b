"""Mamba-2 SSD chunked-scan Pallas kernel.

The state-space-duality algorithm splits the sequence into chunks: within a
chunk the output is a masked-decay matmul (MXU-friendly), across chunks a
small (ds, dh) state carries the recurrence.

Policy story (DESIGN.md §5): the inter-chunk state is a textbook
``RESIDENT_ACCUM`` operand — tiny, revisited every chunk, kept in VMEM
scratch for the whole sweep and never written to HBM until the final chunk.
x/B/C are pure ``STREAM`` operands (touched once each).  An attention-free
layer has no KV-policy site; this is its analogue.

Grid: (batch, heads, chunks) — chunks innermost so the state scratch
persists across the sequential TPU grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, compiler_params, interpret_mode


def _ssd_kernel(
    xdt_ref,   # (1, Q, 1, dh)
    alog_ref,  # (1, Q, 1)
    b_ref,     # (1, Q, 1, ds)
    c_ref,     # (1, Q, 1, ds)
    y_ref,     # (1, Q, 1, dh)
    sout_ref,  # (1, 1, ds, dh)
    s_ref,     # scratch (ds, dh) fp32 — the RESIDENT_ACCUM state
    *,
    n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    xdt = xdt_ref[0, :, 0, :].astype(jnp.float32)    # (Q, dh)
    alog = alog_ref[0, :, 0].astype(jnp.float32)     # (Q,)
    bmat = b_ref[0, :, 0, :].astype(jnp.float32)     # (Q, ds)
    cmat = c_ref[0, :, 0, :].astype(jnp.float32)     # (Q, ds)

    cum = jnp.cumsum(alog)                           # inclusive decay cumsum
    q = alog.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # L[t, s] = exp(cum_t - cum_s) for s <= t (decay accumulated after s).
    # Mask before exp: s>t lanes have positive diffs that overflow.
    lmat = jnp.exp(
        jnp.where(si <= ti, cum[:, None] - cum[None, :], -jnp.inf)
    )

    cb = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32)  # (Q, Q)
    y_intra = jnp.dot(cb * lmat, xdt, preferred_element_type=jnp.float32)
    y_inter = jnp.exp(cum)[:, None] * jnp.dot(
        cmat, s_ref[...], preferred_element_type=jnp.float32
    )
    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)

    # State update: S <- exp(cum_Q) S + sum_s exp(cum_Q - cum_s) B_s xdt_s.
    total = cum[-1]
    b_scaled = bmat * jnp.exp(total - cum)[:, None]
    s_ref[...] = s_ref[...] * jnp.exp(total) + jnp.dot(
        b_scaled.T, xdt, preferred_element_type=jnp.float32
    )

    @pl.when(ic == n_chunks - 1)
    def _flush():
        sout_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(
    x: jnp.ndarray,    # (b, l, h, dh)
    dt: jnp.ndarray,   # (b, l, h)
    A: jnp.ndarray,    # (h,)
    B: jnp.ndarray,    # (b, l, g, ds)
    C: jnp.ndarray,    # (b, l, g, ds)
    D: jnp.ndarray | None = None,   # (h,)
    *,
    chunk: int = 128,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, final_state) matching ref.ssd."""
    b, l, h, dh = x.shape
    g, ds = B.shape[2], B.shape[3]
    hpg = h // g
    chunk = min(chunk, l)
    l_pad = cdiv(l, chunk) * chunk
    if l_pad != l:
        # dt = 0 on padding => decay exp(0)=1, no state contribution.
        x = jnp.pad(x, ((0, 0), (0, l_pad - l), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, l_pad - l), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, l_pad - l), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, l_pad - l), (0, 0), (0, 0)))
    n_chunks = l_pad // chunk

    # Cheap streaming precompute (elementwise, fused by XLA).
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    alog = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]

    grid = (b, h, n_chunks)
    y, s_final = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, dh), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, chunk, 1), lambda ib, ih, ic: (ib, ic, ih)),
            pl.BlockSpec(
                (1, chunk, 1, ds), lambda ib, ih, ic, s=hpg: (ib, ic, ih // s, 0)
            ),
            pl.BlockSpec(
                (1, chunk, 1, ds), lambda ib, ih, ic, s=hpg: (ib, ic, ih // s, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, dh), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, 1, ds, dh), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l_pad, h, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, h, ds, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ds, dh), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(xdt, alog, B, C)

    y = y[:, :l]
    if D is not None:
        y = y + D[None, None, :, None] * x[:, :l].astype(jnp.float32)
    return y.astype(x.dtype), s_final


def ssd_decode_step(
    x: jnp.ndarray,    # (b, h, dh) one token
    dt: jnp.ndarray,   # (b, h)
    A: jnp.ndarray,    # (h,)
    B: jnp.ndarray,    # (b, g, ds)
    C: jnp.ndarray,    # (b, g, ds)
    D: jnp.ndarray | None,
    state: jnp.ndarray,  # (b, h, ds, dh) fp32
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """O(1)-state single-token update (pure jnp — bandwidth-bound on state)."""
    b, h, dh = x.shape
    g = B.shape[1]
    hpg = h // g
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bx = jnp.repeat(B.astype(jnp.float32), hpg, axis=1)
    Cx = jnp.repeat(C.astype(jnp.float32), hpg, axis=1)
    decay = jnp.exp(dtf * A[None, :])[..., None, None]
    state = state * decay + (dtf[..., None] * Bx)[..., None] * xf[..., None, :]
    y = jnp.einsum("bhs,bhsd->bhd", Cx, state)
    if D is not None:
        y = y + D[None, :, None] * xf
    return y.astype(x.dtype), state
