"""Whisper-style encoder-decoder backbone — arXiv:2212.04356.

The conv audio frontend is a STUB per the assignment: ``input_specs``
provides precomputed frame embeddings (b, enc_seq, d); sinusoidal positions
are added here.  Encoder: bidirectional self-attention; decoder: causal
self-attention (learned positions) + cross-attention to encoder states.
LayerNorm + GELU, pre-norm with final norms, per the architecture.

Policy note: the encoder output K/V are the canonical RESIDENT operands of
enc-dec serving — computed once, reused by every decode step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.remat import RematPolicy, apply_remat
from repro.models import common as cm

MAX_DEC_POS = 65536  # learned decoder position table (covers decode_32k)


def _sinusoid(seq: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_layer_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {
        "ln1": cm.norm_init(cfg), "attn": cm.attn_init(ks[0], cfg),
        "ln2": cm.norm_init(cfg), "mlp": cm.mlp_init(ks[1], cfg),
    }


def _dec_layer_init(key, cfg):
    ks = jax.random.split(key, 3)
    return {
        "ln1": cm.norm_init(cfg), "self_attn": cm.attn_init(ks[0], cfg),
        "ln2": cm.norm_init(cfg), "cross_attn": cm.attn_init(ks[1], cfg),
        "ln3": cm.norm_init(cfg), "mlp": cm.mlp_init(ks[2], cfg),
    }


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "embed": cm.embed_init_params(ks[0], cfg),
        "dec_pos": cm.embed_init(ks[3], (MAX_DEC_POS, cfg.d_model),
                                 jnp.dtype(cfg.dtype)),
        "enc_layers": jax.vmap(lambda k2: _enc_layer_init(k2, cfg))(
            jax.random.split(ks[1], cfg.enc_layers)
        ),
        "dec_layers": jax.vmap(lambda k2: _dec_layer_init(k2, cfg))(
            jax.random.split(ks[2], cfg.n_layers)
        ),
        "ln_enc": cm.norm_init(cfg),
        "ln_f": cm.norm_init(cfg),
    }


def encode(params, frames, cfg: ModelConfig,
           remat: RematPolicy = RematPolicy.SAVE_DOTS):
    """frames: stub embeddings (b, s_enc, d)."""
    b, s, d = frames.shape
    x = frames.astype(jnp.dtype(cfg.dtype)) + _sinusoid(s, d).astype(cfg.dtype)
    positions = jnp.arange(s)[None, :]

    def body(h, lp):
        a, _ = cm.apply_attn(
            lp["attn"], cm.apply_norm(lp["ln1"], h, cfg), cfg, positions,
            causal=False, use_rope=False,
        )
        h = h + a
        h = h + cm.apply_mlp(lp["mlp"], cm.apply_norm(lp["ln2"], h, cfg), cfg)
        return h, None

    body = apply_remat(body, remat)
    x, _ = cm.scan(body, x, params["enc_layers"])
    return cm.apply_norm(params["ln_enc"], x, cfg)


def _dec_block(lp, h, cfg, positions, enc_out, self_cache=None,
               cross_cache=None, seg_lens=None):
    a, new_self = cm.apply_attn(
        lp["self_attn"], cm.apply_norm(lp["ln1"], h, cfg), cfg, positions,
        cache=self_cache, causal=True, use_rope=False, seg_lens=seg_lens,
    )
    h = h + a
    c, new_cross = cm.apply_attn(
        lp["cross_attn"], cm.apply_norm(lp["ln2"], h, cfg), cfg, positions,
        kv_src=enc_out, cache=cross_cache, causal=False, use_rope=False,
    )
    h = h + c
    h = h + cm.apply_mlp(lp["mlp"], cm.apply_norm(lp["ln3"], h, cfg), cfg)
    return h, new_self, new_cross


def forward(params, tokens, cfg: ModelConfig, frames=None,
            remat: RematPolicy = RematPolicy.SAVE_DOTS):
    assert frames is not None, "whisper forward needs encoder frames"
    enc_out = encode(params, frames, cfg, remat)
    b, s = tokens.shape
    x = cm.embed(params["embed"], tokens) + params["dec_pos"][None, :s]
    positions = jnp.arange(s)[None, :]

    def body(h, lp):
        h, _, _ = _dec_block(lp, h, cfg, positions, enc_out)
        return h, None

    body = apply_remat(body, remat)
    x, _ = cm.scan(body, x, params["dec_layers"])
    x = cm.apply_norm(params["ln_f"], x, cfg)
    return cm.unembed(params["embed"], x, cfg), jnp.zeros((), jnp.float32)


def loss_fn(params, batch, cfg: ModelConfig,
            remat: RematPolicy = RematPolicy.SAVE_DOTS):
    logits, aux = forward(
        params, batch["tokens"], cfg, frames=batch["frames"], remat=remat
    )
    ce = cm.cross_entropy(logits, batch["labels"], cfg.vocab)
    return ce + aux, {"ce": ce, "aux": aux}


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int, vis=None,
               frames=None, n_pages=None):
    """vis doubles as the encoder frames argument for API uniformity."""
    frames = frames if frames is not None else vis
    assert frames is not None, "whisper cache needs encoder frames"
    enc_out = encode(params, frames, cfg)
    hkv, dh = cfg.n_kv_heads, cfg.head_dim_
    dt = jnp.dtype(cfg.dtype)
    L = cfg.n_layers

    def cross_kv(lp):
        k = jnp.einsum("btd,dhk->bthk", enc_out, lp["cross_attn"]["wk"])
        v = jnp.einsum("btd,dhk->bthk", enc_out, lp["cross_attn"]["wv"])
        return {"k": k, "v": v}

    cross = jax.vmap(cross_kv)(params["dec_layers"])
    if cfg.cache_layout == "paged":
        self_kv, pages = cm.paged_kv_buffers((L,), batch, max_len, cfg,
                                             n_pages)
    else:
        shape = (L, batch, max_len, hkv, dh)
        self_kv = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        pages = None
    cache = {
        "self": self_kv,
        "cross": cross,              # RESIDENT: reused by every decode step
        "lengths": jnp.zeros((batch,), jnp.int32),
    }
    if pages is not None:
        cache["pages"] = pages
    return cache


def prefill(params, cache, tokens, cfg: ModelConfig, seg_lens=None,
            all_logits=False):
    b, s = tokens.shape
    lengths = cache["lengths"]
    pages = cache.get("pages")
    positions = lengths[:, None] + jnp.arange(s)[None, :]     # (b, s)
    # Per-slot learned position rows (ragged cursors need a gather, not a
    # uniform dynamic slice); jnp.take clamps at the table edge.
    x = cm.embed(params["embed"], tokens) + jnp.take(
        params["dec_pos"], positions, axis=0
    )

    def body(h, inp):
        lp, sc, cc = inp
        self_cache = {"k": sc["k"], "v": sc["v"], "lengths": lengths}
        if pages is not None:
            self_cache["pages"] = pages
        h, new_self, _ = _dec_block(
            lp, h, cfg, positions, None, self_cache=self_cache, cross_cache=cc,
            seg_lens=seg_lens,
        )
        return h, {"k": new_self["k"], "v": new_self["v"]}

    x, new_self = cm.scan(
        body, x, (params["dec_layers"], cache["self"], cache["cross"])
    )
    x = cm.apply_norm(params["ln_f"], x, cfg)
    out = x if all_logits else cm.last_valid_slice(x, seg_lens)
    logits = cm.unembed(params["embed"], out, cfg)
    new_cache = {
        "self": new_self, "cross": cache["cross"],
        "lengths": lengths + (s if seg_lens is None else seg_lens),
    }
    if pages is not None:
        new_cache["pages"] = pages
    return logits, new_cache


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
        reset_slots=cm.reset_lengths,
    )
