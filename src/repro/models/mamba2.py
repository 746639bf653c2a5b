"""Mamba-2 LM (SSD, attention-free) — arXiv:2405.21060.

The XLA training path uses the same chunked SSD math as the Pallas kernel
(`repro.kernels.ssd`), implemented as a `lax.scan` over chunks so the
(b, h, Q, Q) intra-chunk attention temp is bounded to one chunk at a time —
the inter-chunk state (b, h, ds, dh) is the RESIDENT_ACCUM carry.

Decode is O(1) in context length: conv buffer (width-1 tokens) + SSM state.
This is why mamba2 (and zamba2) run the long_500k shape cell that pure
full-attention architectures skip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.remat import RematPolicy, apply_remat
from repro.kernels.ssd.ssd import ssd_decode_step
from repro.models import common as cm


# ---------------------------------------------------------------------------
# Chunked SSD (jnp; mirrors kernels/ssd math)
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, A, B, C, D=None, chunk: int = 128, init_state=None):
    """x (b,l,h,dh), dt (b,l,h), A (h,), B/C (b,l,g,ds) -> (y, final_state)."""
    b, l, h, dh = x.shape
    g, ds = B.shape[2], B.shape[3]
    hpg = h // g
    q = min(chunk, l)
    pad = (-l) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (l + pad) // q

    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    alog = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]

    def chunk_view(t, extra):  # (b, nc*q, ...) -> (nc, b, q, ...)
        return jnp.moveaxis(t.reshape(b, nc, q, *extra), 1, 0)

    xdt_c = chunk_view(xdt, (h, dh))
    alog_c = chunk_view(alog, (h,))
    b_c = chunk_view(B.astype(jnp.float32), (g, ds))
    c_c = chunk_view(C.astype(jnp.float32), (g, ds))

    ti = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = si <= ti

    def step(S, inp):
        xd, al, bb, cc = inp           # (b,q,h,dh) (b,q,h) (b,q,g,ds) (b,q,g,ds)
        cum = jnp.cumsum(al, axis=1)   # (b, q, h)
        cumT = jnp.moveaxis(cum, 1, 2)  # (b, h, q)
        diff = cumT[:, :, :, None] - cumT[:, :, None, :]
        # Mask BEFORE exp: the s>t lanes have positive diffs that overflow
        # and would poison gradients through the where.
        lmat = jnp.exp(jnp.where(tril[None, None], diff, -jnp.inf))
        cb = jnp.einsum("btgd,bsgd->bgts", cc, bb)      # (b, g, t, s)
        cb = jnp.repeat(cb, hpg, axis=1)                 # (b, h, t, s)
        y_intra = jnp.einsum("bhts,bshd->bthd", cb * lmat, xd)
        cch = jnp.repeat(cc, hpg, axis=2)                # (b, q, h, ds)
        y_inter = jnp.moveaxis(jnp.exp(cumT), 1, 2)[..., None] * jnp.einsum(
            "bthn,bhnd->bthd", cch, S
        )
        total = cumT[:, :, -1]                           # (b, h)
        bbh = jnp.repeat(bb, hpg, axis=2)                # (b, s, h, ds)
        b_scaled = bbh * jnp.exp(total[:, None, :] - cum)[..., None]
        S = S * jnp.exp(total)[..., None, None] + jnp.einsum(
            "bshn,bshd->bhnd", b_scaled, xd
        )
        return S, y_intra + y_inter

    S0 = (
        jnp.zeros((b, h, ds, dh), jnp.float32)
        if init_state is None else init_state.astype(jnp.float32)
    )
    S, ys = cm.scan(step, S0, (xdt_c, alog_c, b_c, c_c))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * q, h, dh)[:, :l]
    if D is not None:
        y = y + D[None, None, :, None] * x[:, :l].astype(jnp.float32)
    return y.astype(x.dtype), S


# ---------------------------------------------------------------------------
# Mamba-2 block
# ---------------------------------------------------------------------------

def mamba_init(key, cfg: ModelConfig) -> cm.Params:
    d, di = cfg.d_model, cfg.d_inner
    g, ds, h = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    conv_ch = di + 2 * g * ds
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "in_proj": cm.dense_init(
            ks[0], (d, 2 * di + 2 * g * ds + h), d, dt
        ),
        "conv_w": cm.dense_init(ks[1], (cfg.ssm_conv, conv_ch), cfg.ssm_conv, dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "A_log": jnp.log(
            jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)
        ),
        "D": jnp.ones((h,), jnp.float32),
        "norm_w": jnp.ones((di,), jnp.float32),
        "out_proj": cm.dense_init(ks[2], (di, d), di, dt),
    }


def _split_in_proj(z_all, cfg: ModelConfig):
    di, g, ds, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z = z_all[..., :di]
    xbc = z_all[..., di:di + di + 2 * g * ds]
    dt = z_all[..., -h:]
    return z, xbc, dt


def _causal_conv(xbc, w, b, prev=None, seg_lens=None):
    """Depthwise causal conv1d.  xbc (b, l, ch); w (width, ch).

    ``prev`` (b, width-1, ch) continues a streaming sequence; returns
    (out, new_prev).  With ragged ``seg_lens``, each slot's new window ends
    at its own last valid token: ext[b, seg_lens[b] : seg_lens[b]+width-1]
    (the first width-1 entries of ext are ``prev``, so seg_lens == 0 keeps
    the window untouched — a parked slot)."""
    width = w.shape[0]
    if prev is None:
        prev = jnp.zeros((xbc.shape[0], width - 1, xbc.shape[2]), xbc.dtype)
    ext = jnp.concatenate([prev, xbc], axis=1)          # (b, l+w-1, ch)
    out = sum(
        ext[:, i:i + xbc.shape[1]] * w[i][None, None] for i in range(width)
    ) + b[None, None]
    if width == 1:
        new_prev = prev
    elif seg_lens is None:
        new_prev = ext[:, -(width - 1):]
    else:
        new_prev = jax.vmap(
            lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, width - 1, axis=0)
        )(ext, seg_lens)
    return out, new_prev


def apply_mamba(p, x, cfg: ModelConfig, state=None, conv_prev=None,
                seg_lens=None):
    """x (b, l, d) -> (y, (ssm_state, conv_prev)).

    Ragged blocks gate dt to zero on invalid positions: the SSD update
    with dt == 0 is the identity (decay exp(0)=1, zero input), so padding
    — and parked slots with seg_lens == 0 — never touch the SSM state."""
    b, l, d = x.shape
    di, g, ds, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    dh = cfg.ssm_headdim
    zall = x @ p["in_proj"]
    z, xbc, dtr = _split_in_proj(zall, cfg)
    xbc, new_conv = _causal_conv(
        xbc, p["conv_w"], p["conv_b"], conv_prev, seg_lens=seg_lens
    )
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(b, l, h, dh)
    B = xbc[..., di:di + g * ds].reshape(b, l, g, ds)
    C = xbc[..., di + g * ds:].reshape(b, l, g, ds)
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])
    valid = cm.seg_mask(l, seg_lens)
    if valid is not None:
        dt = dt * valid.astype(dt.dtype)[..., None]
    A = -jnp.exp(p["A_log"])
    if l == 1 and state is not None:
        y1, new_state = ssd_decode_step(
            xs[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], p["D"], state
        )
        y = y1[:, None]
    else:
        # Adaptive chunk: bound the scan trip count (<=16) while keeping the
        # intra-chunk (b, h, Q, Q) buffer head-sharded and modest.
        chunk = min(max(128, l // 16), 1024)
        y, new_state = ssd_chunked(
            xs, dt, A, B, C, p["D"], chunk=chunk, init_state=state
        )
    y = y.reshape(b, l, di)
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    y = (gated * jax.lax.rsqrt(ms + cfg.norm_eps) * p["norm_w"]).astype(x.dtype)
    return y @ p["out_proj"], (new_state, new_conv)


# ---------------------------------------------------------------------------
# LM
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig):
    return {"ln": cm.norm_init(cfg), "mamba": mamba_init(key, cfg)}


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {
        "embed": cm.embed_init_params(ks[0], cfg),
        "ln_f": cm.norm_init(cfg),
        "layers": jax.vmap(lambda k2: _layer_init(k2, cfg))(
            jax.random.split(ks[1], cfg.n_layers)
        ),
    }


def forward(params, tokens, cfg: ModelConfig,
            remat: RematPolicy = RematPolicy.SAVE_DOTS):
    x = cm.embed(params["embed"], tokens)

    def body(h, lp):
        y, _ = apply_mamba(lp["mamba"], cm.apply_norm(lp["ln"], h, cfg), cfg)
        return h + y, None

    body = apply_remat(body, remat)
    x, _ = cm.scan(body, x, params["layers"])
    x = cm.apply_norm(params["ln_f"], x, cfg)
    return cm.unembed(params["embed"], x, cfg), jnp.zeros((), jnp.float32)


def loss_fn(params, batch, cfg: ModelConfig,
            remat: RematPolicy = RematPolicy.SAVE_DOTS):
    logits, aux = forward(params, batch["tokens"], cfg, remat=remat)
    ce = cm.cross_entropy(logits, batch["labels"], cfg.vocab)
    return ce + aux, {"ce": ce, "aux": aux}


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int, vis=None,
               n_pages=None):
    """``n_pages`` is accepted for serve-engine API uniformity; mamba2's
    decode state is O(1) per slot (no KV), so there is nothing to page."""
    h, ds, dh = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_headdim
    conv_ch = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    L = cfg.n_layers
    return {
        "ssm": jnp.zeros((L, batch, h, ds, dh), jnp.float32),
        "conv": jnp.zeros((L, batch, cfg.ssm_conv - 1, conv_ch), jnp.dtype(cfg.dtype)),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params, cache, tokens, cfg: ModelConfig, seg_lens=None,
            all_logits=False):
    b, s = tokens.shape
    x = cm.embed(params["embed"], tokens)

    def body(h, inp):
        lp, st, cv = inp
        y, (new_st, new_cv) = apply_mamba(
            lp["mamba"], cm.apply_norm(lp["ln"], h, cfg), cfg,
            state=st, conv_prev=cv, seg_lens=seg_lens,
        )
        return h + y, (new_st, new_cv)

    x, (new_ssm, new_conv) = cm.scan(
        body, x, (params["layers"], cache["ssm"], cache["conv"])
    )
    x = cm.apply_norm(params["ln_f"], x, cfg)
    out = x if all_logits else cm.last_valid_slice(x, seg_lens)
    logits = cm.unembed(params["embed"], out, cfg)
    return logits, {
        "ssm": new_ssm, "conv": new_conv,
        "lengths": cache["lengths"] + (s if seg_lens is None else seg_lens),
    }


def decode_step(params, cache, tokens, cfg: ModelConfig, seg_lens=None):
    return prefill(params, cache, tokens, cfg, seg_lens=seg_lens)


def build(cfg: ModelConfig) -> cm.ModelApply:
    return cm.ModelApply(
        config=cfg,
        init=functools.partial(init, cfg=cfg),
        forward=functools.partial(forward, cfg=cfg),
        loss=functools.partial(loss_fn, cfg=cfg),
        init_cache=functools.partial(init_cache, cfg=cfg),
        prefill=functools.partial(prefill, cfg=cfg),
        decode_step=functools.partial(decode_step, cfg=cfg),
        reset_slots=cm.reset_recurrent,
    )
