"""Decoder-only transformer LM covering dense GQA, MoE, and VLM variants.

One implementation serves yi-9b / granite-20b / minicpm-2b / qwen2.5-32b
(dense), phi3.5-moe / olmoe / mellum2 (MoE FFN), and llama-3.2-vision
(interleaved cross-attention to stub vision-patch embeddings).

Layers are stacked and scanned (``jax.lax.scan``) so trace/compile time is
O(1) in depth; the activation (remat) policy comes from the cache-policy
engine and wraps the scan body.

A config with ``layer_types`` (mellum2: three sliding-window layers, then
one full, seven times) scans over periods of that pattern, each kind of
layer unrolled in the period body.  Its cache holds two kinds of KV state
side by side: the full layers' K/V in the layout's cache (the paged pool,
whose pages admission gates on), and the sliding layers' window rings,
``sliding_window`` positions per slot and layer whatever the context
(``models.common`` §Window ring).  Full layers take the config's YaRN
frequencies; sliding layers keep plain RoPE.

A MoE layer routes over every expert and computes the experts this chip
holds (``expert_shards``/``expert_shard``, models.common.apply_moe); the
cached stacks return the valid tokens' picks on those experts as the new
cache's ``moe_picks_held``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import FULL, SLIDING, ModelConfig
from repro.models.remat import RematPolicy, apply_remat
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

def _self_block(p, x, cfg: ModelConfig, positions, cache=None, seg_lens=None,
                kind: str = FULL):
    """One decoder layer.  Returns (x, aux loss, MoE picks on held experts
    by the valid tokens (0 for a dense MLP), new cache)."""
    h, new_cache = cm.apply_attn(
        p["attn"], cm.apply_norm(p["ln1"], x, cfg), cfg, positions, cache=cache,
        seg_lens=seg_lens, kind=kind,
    )
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    picks = 0
    h2 = cm.apply_norm(p["ln2"], x, cfg)
    if "moe" in p:
        m, aux, picks = cm.apply_moe(p["moe"], h2, cfg,
                                     valid=cm.seg_mask(x.shape[1], seg_lens))
    else:
        m = cm.apply_mlp(p["mlp"], h2, cfg)
    return x + m, aux, picks, new_cache


def _cross_block(p, x, cfg: ModelConfig, positions, vis, cache=None):
    h, new_cache = cm.apply_attn(
        p["attn"], cm.apply_norm(p["ln1"], x, cfg), cfg, positions,
        kv_src=vis, cache=cache, causal=False, use_rope=False,
    )
    x = x + h
    m = cm.apply_mlp(p["mlp"], cm.apply_norm(p["ln2"], x, cfg), cfg)
    return x + m, new_cache


# ---------------------------------------------------------------------------
# Layer-typed stacks: the layers scan in periods of ``cfg.layer_period``
# (Mellum2's [sliding, sliding, sliding, full]), each kind of layer unrolled
# in the period body with its own attention.  Full layers keep the KV
# cache of the layout (the paged pool); sliding layers keep window rings
# (``cm.window_ring_buffers``).  Each kind's K/V are stacked over its
# layers and ride the scan's carry, indexed by layer, updated in place.
# ---------------------------------------------------------------------------

def _periods(tree, n: int):
    """Stacked (L, ...) leaves -> (L / n, n, ...)."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1, n) + a.shape[1:]), tree)


def _layer_of(tree, j: int):
    return jax.tree_util.tree_map(lambda a: a[j], tree)


def _kind_ranks(period: tuple, kind: str) -> dict[int, int]:
    """Position in the period -> rank among the period's layers of
    ``kind``."""
    return {j: r for r, j in enumerate(
        j for j, k in enumerate(period) if k == kind)}


def _stack_typed_nocache(params, x, cfg: ModelConfig, positions,
                         remat: RematPolicy):
    period = cfg.layer_period

    def body(carry, lp):
        h, aux = carry
        for j, kind in enumerate(period):
            h, da, _, _ = _self_block(_layer_of(lp, j), h, cfg, positions,
                                      kind=kind)
            aux = aux + da
        return (h, aux), None

    body = apply_remat(body, remat)
    (x, aux), _ = cm.scan(body, (x, jnp.zeros((), jnp.float32)),
                          _periods(params["layers"], len(period)))
    return x, aux


def _stack_typed_cached(params, x, cfg: ModelConfig, positions, cache,
                        seg_lens=None):
    period = cfg.layer_period
    full, window = _kind_ranks(period, FULL), _kind_ranks(period, SLIDING)
    lengths, pages = cache["lengths"], cache.get("pages")
    s = x.shape[1]

    def body(carry, i):
        h, fk, fv, wk, wv, picks = carry
        for j, kind in enumerate(period):
            # Each layer's weights are sliced out of the whole stack at
            # their use: a period-sized slice feeding several layers is
            # materialized as a copy, one per layer is read in place.
            lp = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, i * len(period) + j, keepdims=False),
                params["layers"])
            if kind == SLIDING:
                c = {"k": wk, "v": wv, "layer": i * len(window) + window[j],
                     "lengths": lengths}
            else:
                c = {"k": fk, "v": fv, "layer": i * len(full) + full[j],
                     "lengths": lengths}
                if pages is not None:
                    c["pages"] = pages
            h, _, n, nc = _self_block(lp, h, cfg, positions, cache=c,
                                      seg_lens=seg_lens, kind=kind)
            if kind == SLIDING:
                wk, wv = nc["k"], nc["v"]
            else:
                fk, fv = nc["k"], nc["v"]
            picks = picks + n
        return (h, fk, fv, wk, wv, picks), None

    kv, ring = cache["layers"], cache["window"]
    n_per = cfg.n_layers // len(period)
    (x, fk, fv, wk, wv, picks), _ = cm.scan(
        body, (x, kv["k"], kv["v"], ring["k"], ring["v"], jnp.int32(0)),
        jnp.arange(n_per),
    )
    out = {"layers": {"k": fk, "v": fv}, "window": {"k": wk, "v": wv},
           "lengths": lengths + (s if seg_lens is None else seg_lens)}
    if pages is not None:
        out["pages"] = pages
    if cfg.family == "moe":
        out["moe_picks_held"] = picks
    return x, out


# ---------------------------------------------------------------------------
# Stacks (train/no-cache and cached paths)
# ---------------------------------------------------------------------------

def _stack_nocache(params, x, cfg: ModelConfig, positions, vis,
                   remat: RematPolicy):
    if cfg.layer_types:
        return _stack_typed_nocache(params, x, cfg, positions, remat)
    if cfg.cross_attn_every:
        span = cfg.cross_attn_every - 1

        def group_body(carry, gp):
            h, aux = carry

            def one_self(c, lp):
                hh, a = c
                hh, da, _, _ = _self_block(lp, hh, cfg, positions)
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
        h, da, _, _ = _self_block(lp, h, cfg, positions)
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
    the K/V buffers.  A MoE model's new cache also carries
    ``moe_picks_held``: this call's picks on held experts."""
    if cfg.layer_types:
        return _stack_typed_cached(params, x, cfg, positions, cache,
                                   seg_lens)
    lengths = cache["lengths"]
    pages = cache.get("pages")            # paged layout: (b, P) page table
    s = x.shape[1]
    new_lengths = lengths + (s if seg_lens is None else seg_lens)

    def kv_in(lc):
        c = {"k": lc["k"], "v": lc["v"], "lengths": lengths}
        if pages is not None:
            c["pages"] = pages
        return c

    def out_cache(layers, picks=None):
        out = {"layers": layers, "lengths": new_lengths}
        if pages is not None:
            out["pages"] = pages
        if picks is not None:
            out["moe_picks_held"] = jnp.sum(picks, dtype=jnp.int32)
        return out

    moe = cfg.family == "moe"

    if cfg.cross_attn_every:
        def group_body(h, inp):
            gp, gcache = inp

            def one_self(hh, inp2):
                lp, lc = inp2
                hh, _, _, nc = _self_block(
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

    if pages is not None:
        # Paged pool: the stacked (L, N, page, hkv * dh) pools ride the
        # scan's carry, and each layer appends its rows and reads its pages
        # in place through its layer index — no layer's pool is sliced out
        # of the stack and written back.
        def paged_body(carry, inp):
            h, kp, vp = carry
            lp, layer = inp
            c = {"k": kp, "v": vp, "layer": layer, "lengths": lengths,
                 "pages": pages}
            h, _, n, nc = _self_block(lp, h, cfg, positions, cache=c,
                                      seg_lens=seg_lens)
            return (h, nc["k"], nc["v"]), (n if moe else None)

        kv = cache["layers"]
        (x, kp, vp), picks = cm.scan(
            paged_body, (x, kv["k"], kv["v"]),
            (params["layers"], jnp.arange(kv["k"].shape[0])),
        )
        return x, out_cache({"k": kp, "v": vp}, picks)

    def body(h, inp):
        lp, lc = inp
        h, _, n, nc = _self_block(
            lp, h, cfg, positions, cache=kv_in(lc), seg_lens=seg_lens,
        )
        return h, (nc, n if moe else None)

    x, (new_layers, picks) = cm.scan(
        body, x, (params["layers"], cache["layers"]))
    return x, out_cache(new_layers, picks)


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

    if cfg.layer_types:
        period = cfg.layer_period
        n_per = cfg.n_layers // len(period)
        cache = with_pages({
            "layers": kv(period.count(FULL) * n_per),
            "window": cm.window_ring_buffers(
                (period.count(SLIDING) * n_per,), batch, cfg),
        })
        if cfg.family == "moe":
            cache["moe_picks_held"] = jnp.int32(0)
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
    cache = with_pages({"layers": kv(cfg.n_layers)})
    if cfg.family == "moe":
        cache["moe_picks_held"] = jnp.int32(0)
    return cache


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


def _check(cfg: ModelConfig) -> None:
    if cfg.layer_types:
        if len(cfg.layer_types) != cfg.n_layers or cfg.cross_attn_every:
            raise ValueError(f"{cfg.arch}: layer_types must name each of "
                             f"{cfg.n_layers} self-attention layers")
        if set(cfg.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"{cfg.arch}: unknown layer types "
                             f"{set(cfg.layer_types) - {FULL, SLIDING}}")
        if cfg.windowed and (cfg.sliding_window <= 0 or cfg.sliding_window
                             % cfg.kv_page_size):
            raise ValueError(f"{cfg.arch}: sliding_window "
                             f"{cfg.sliding_window} must be a positive "
                             f"multiple of kv_page_size {cfg.kv_page_size}")
    if cfg.family == "moe" and (
            cfg.n_experts % cfg.expert_shards
            or not 0 <= cfg.expert_shard < cfg.expert_shards):
        raise ValueError(f"{cfg.arch}: shard {cfg.expert_shard} of "
                         f"{cfg.expert_shards} does not split "
                         f"{cfg.n_experts} experts")


def build(cfg: ModelConfig) -> cm.ModelApply:
    _check(cfg)
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
