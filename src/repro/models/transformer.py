"""Decoder-only transformer LM covering dense GQA, MoE, and VLM variants.

One implementation serves yi-9b / granite-20b / minicpm-2b / qwen2.5-32b
(dense), phi3.5-moe / olmoe (MoE FFN), and llama-3.2-vision (interleaved
cross-attention to stub vision-patch embeddings).

Layers are stacked and scanned (``jax.lax.scan``) so trace/compile time is
O(1) in depth; the activation (remat) policy comes from the cache-policy
engine and wraps the scan body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.remat import RematPolicy, apply_remat
from repro.models import common as cm


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": cm.norm_init(cfg),
        "attn": cm.attn_init(ks[0], cfg),
        "ln2": cm.norm_init(cfg),
    }
    if cfg.family == "moe" and not cross:
        p["moe"] = cm.moe_init(ks[1], cfg)
    else:
        p["mlp"] = cm.mlp_init(ks[1], cfg)
    return p


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    params = {"embed": cm.embed_init_params(ks[0], cfg), "ln_f": cm.norm_init(cfg)}
    if cfg.cross_attn_every:
        g = cfg.n_layers // cfg.cross_attn_every
        span = cfg.cross_attn_every - 1
        self_keys = jax.random.split(ks[1], g * span).reshape(g, span, 2)
        cross_keys = jax.random.split(ks[2], g)
        params["self_layers"] = jax.vmap(
            lambda kk: jax.vmap(lambda k2: _layer_init(k2, cfg))(kk)
        )(self_keys)
        params["cross_layers"] = jax.vmap(
            lambda k2: _layer_init(k2, cfg, cross=True)
        )(cross_keys)
        params["vis_proj"] = cm.dense_init(
            ks[3], (cfg.d_model, cfg.d_model), cfg.d_model, jnp.dtype(cfg.dtype)
        )
    else:
        layer_keys = jax.random.split(ks[1], cfg.n_layers)
        params["layers"] = jax.vmap(lambda k2: _layer_init(k2, cfg))(layer_keys)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _self_block(p, x, cfg: ModelConfig, positions, cache=None, seg_lens=None):
    h, new_cache = cm.apply_attn(
        p["attn"], cm.apply_norm(p["ln1"], x, cfg), cfg, positions, cache=cache,
        seg_lens=seg_lens,
    )
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    h2 = cm.apply_norm(p["ln2"], x, cfg)
    if "moe" in p:
        m, aux = cm.apply_moe(p["moe"], h2, cfg)
    else:
        m = cm.apply_mlp(p["mlp"], h2, cfg)
    return x + m, aux, new_cache


def _cross_block(p, x, cfg: ModelConfig, positions, vis, cache=None):
    h, new_cache = cm.apply_attn(
        p["attn"], cm.apply_norm(p["ln1"], x, cfg), cfg, positions,
        kv_src=vis, cache=cache, causal=False, use_rope=False,
    )
    x = x + h
    m = cm.apply_mlp(p["mlp"], cm.apply_norm(p["ln2"], x, cfg), cfg)
    return x + m, new_cache


# ---------------------------------------------------------------------------
# Stacks (train/no-cache and cached paths)
# ---------------------------------------------------------------------------

def _stack_nocache(params, x, cfg: ModelConfig, positions, vis,
                   remat: RematPolicy):
    if cfg.cross_attn_every:
        span = cfg.cross_attn_every - 1

        def group_body(carry, gp):
            h, aux = carry

            def one_self(c, lp):
                hh, a = c
                hh, da, _ = _self_block(lp, hh, cfg, positions)
                return (hh, a + da), None

            (h, aux), _ = cm.scan(one_self, (h, aux), gp["self"])
            h, _ = _cross_block(gp["cross"], h, cfg, positions, vis)
            return (h, aux), None

        body = apply_remat(group_body, remat)
        (x, aux), _ = cm.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            {"self": params["self_layers"], "cross": params["cross_layers"]},
        )
        return x, aux

    def body(carry, lp):
        h, aux = carry
        h, da, _ = _self_block(lp, h, cfg, positions)
        return (h, aux + da), None

    body = apply_remat(body, remat)
    (x, aux), _ = cm.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["layers"]
    )
    return x, aux


def _stack_cached(params, x, cfg: ModelConfig, positions, vis, cache,
                  seg_lens=None):
    """Scan over layers threading per-layer KV caches (stacked leading dim).

    ``cache["lengths"]`` is the (b,) ragged cursor vector shared by every
    layer (each layer sees the same tokens); per-layer caches carry only
    the K/V buffers."""
    lengths = cache["lengths"]
    pages = cache.get("pages")            # paged layout: (b, P) page table
    s = x.shape[1]
    new_lengths = lengths + (s if seg_lens is None else seg_lens)

    def kv_in(lc):
        c = {"k": lc["k"], "v": lc["v"], "lengths": lengths}
        if pages is not None:
            c["pages"] = pages
        return c

    def out_cache(layers):
        out = {"layers": layers, "lengths": new_lengths}
        if pages is not None:
            out["pages"] = pages
        return out

    if cfg.cross_attn_every:
        def group_body(h, inp):
            gp, gcache = inp

            def one_self(hh, inp2):
                lp, lc = inp2
                hh, _, nc = _self_block(
                    lp, hh, cfg, positions, cache=kv_in(lc),
                    seg_lens=seg_lens,
                )
                return hh, nc

            h, new_self = cm.scan(
                one_self, h, (gp["self"], gcache["self"])
            )
            # The nested scan's stacked KV output loses its sharding
            # through the outer while loop, replicating per-chip temps
            # ~33x the cache size (EXPERIMENTS.md §Perf S2).  Pin it.
            # Contiguous (span, b, S, hkv, dh) shards batch over "data";
            # the paged pool (span, N, psz, hkv * dh) has no batch axis —
            # any slot may reference any page — so only heads are pinned.
            spec = ((None, None, None, ("model",)) if pages is not None
                    else (None, ("data",), ("model",), None, None))
            for key in ("k", "v"):
                new_self[key] = cm._maybe_shard(new_self[key], spec)
            h, new_cross = _cross_block(
                gp["cross"], h, cfg, positions, vis, cache=gcache["cross"]
            )
            return h, {"self": new_self, "cross": new_cross}

        x, new_cache = cm.scan(
            group_body, x,
            ({"self": params["self_layers"], "cross": params["cross_layers"]},
             cache["layers"]),
        )
        return x, out_cache(new_cache)

    def body(h, inp):
        lp, lc = inp
        h, _, nc = _self_block(
            lp, h, cfg, positions, cache=kv_in(lc), seg_lens=seg_lens,
        )
        return h, nc

    x, new_layers = cm.scan(body, x, (params["layers"], cache["layers"]))
    return x, out_cache(new_layers)


# ---------------------------------------------------------------------------
# Public model functions
# ---------------------------------------------------------------------------

def forward(params, tokens, cfg: ModelConfig, vis=None,
            remat: RematPolicy = RematPolicy.SAVE_DOTS):
    b, s = tokens.shape
    x = cm.embed(params["embed"], tokens)
    if cfg.cross_attn_every:
        assert vis is not None, "vlm forward needs vision embeddings"
        vis = vis.astype(x.dtype) @ params["vis_proj"]
    positions = jnp.arange(s)[None, :]
    x, aux = _stack_nocache(params, x, cfg, positions, vis, remat)
    x = cm.apply_norm(params["ln_f"], x, cfg)
    return cm.unembed(params["embed"], x, cfg), aux


def loss_fn(params, batch, cfg: ModelConfig,
            remat: RematPolicy = RematPolicy.SAVE_DOTS):
    logits, aux = forward(
        params, batch["tokens"], cfg, vis=batch.get("vis"), remat=remat
    )
    ce = cm.cross_entropy(logits, batch["labels"], cfg.vocab)
    return ce + aux, {"ce": ce, "aux": aux}


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int, vis=None,
               n_pages=None):
    hkv, dh = cfg.n_kv_heads, cfg.head_dim_
    dt = jnp.dtype(cfg.dtype)
    pages = None

    def kv(*lead):
        nonlocal pages
        if cfg.cache_layout == "paged":
            kvs, pages = cm.paged_kv_buffers(lead, batch, max_len, cfg,
                                             n_pages)
            return kvs
        shape = (*lead, batch, max_len, hkv, dh)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def with_pages(cache):
        cache["lengths"] = jnp.zeros((batch,), jnp.int32)
        if pages is not None:
            cache["pages"] = pages
        return cache

    if cfg.cross_attn_every:
        g = cfg.n_layers // cfg.cross_attn_every
        span = cfg.cross_attn_every - 1
        assert vis is not None, "vlm cache needs vision embeddings"
        visp = vis.astype(dt) @ params["vis_proj"]
        # Precompute cross K/V once per cross layer (reused every step —
        # the RESIDENT operand of VLM decoding).  Cross K/V stay contiguous
        # regardless of layout: they are fixed-source and never appended.
        def cross_kv(lp):
            k = jnp.einsum("btd,dhk->bthk", visp, lp["attn"]["wk"])
            v = jnp.einsum("btd,dhk->bthk", visp, lp["attn"]["wv"])
            if cfg.qkv_bias:
                k = k + lp["attn"]["bk"]
                v = v + lp["attn"]["bv"]
            return {"k": k, "v": v}

        cross = jax.vmap(cross_kv)(params["cross_layers"])
        return with_pages({
            "layers": {"self": kv(g, span), "cross": cross}, "vis": visp,
        })
    return with_pages({"layers": kv(cfg.n_layers)})


def prefill(params, cache, tokens, cfg: ModelConfig, vis=None, seg_lens=None,
            all_logits=False):
    b, s = tokens.shape
    x = cm.embed(params["embed"], tokens)
    positions = cache["lengths"][:, None] + jnp.arange(s)[None, :]
    visp = cache.get("vis") if cfg.cross_attn_every else None
    x, new_cache = _stack_cached(
        params, x, cfg, positions, visp, cache, seg_lens=seg_lens
    )
    if cfg.cross_attn_every:
        new_cache["vis"] = cache["vis"]
    x = cm.apply_norm(params["ln_f"], x, cfg)
    out = x if all_logits else cm.last_valid_slice(x, seg_lens)
    logits = cm.unembed(params["embed"], out, cfg)
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
