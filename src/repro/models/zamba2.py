"""Zamba2-style hybrid: Mamba-2 backbone + ONE shared attention block
applied every k SSM layers — arXiv:2411.15242.

Simplifications vs the released model (recorded in DESIGN.md §5): the shared
block operates at d_model (the release concatenates [hidden, embedding] at
2*d_model before projecting), and per-invocation LoRA deltas are omitted.
Weight sharing is exact: one parameter set, ``n_layers/k`` invocations, each
with its own KV cache (weights shared, cache not).

Decode state is O(1) for the SSM layers plus k-th-layer KV caches — the
sub-quadratic property that qualifies zamba2 for the long_500k cell, where
the shared-block caches are read with sequence-parallel flash-decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.remat import RematPolicy, apply_remat
from repro.models import common as cm
from repro.models import mamba2 as mb


def init(key, cfg: ModelConfig):
    assert cfg.shared_attn_every > 0
    ks = jax.random.split(key, 4)
    n_groups = cfg.n_layers // cfg.shared_attn_every
    params = {
        "embed": cm.embed_init_params(ks[0], cfg),
        "ln_f": cm.norm_init(cfg),
        "layers": jax.vmap(lambda k2: mb._layer_init(k2, cfg))(
            jax.random.split(ks[1], cfg.n_layers)
        ),
        # The single shared transformer block (params counted once).
        "shared": {
            "ln1": cm.norm_init(cfg),
            "attn": cm.attn_init(ks[2], cfg),
            "ln2": cm.norm_init(cfg),
            "mlp": cm.mlp_init(ks[3], cfg),
        },
    }
    del n_groups
    return params


def _shared_block(p, x, cfg, positions, cache=None, seg_lens=None):
    h, new_cache = cm.apply_attn(
        p["attn"], cm.apply_norm(p["ln1"], x, cfg), cfg, positions, cache=cache,
        seg_lens=seg_lens,
    )
    x = x + h
    x = x + cm.apply_mlp(p["mlp"], cm.apply_norm(p["ln2"], x, cfg), cfg)
    return x, new_cache


def _group_view(tree, n_groups: int, k: int):
    """Reshape stacked (L, ...) layer params to (G, k, ...)."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape(n_groups, k, *a.shape[1:]), tree
    )


def forward(params, tokens, cfg: ModelConfig,
            remat: RematPolicy = RematPolicy.SAVE_DOTS):
    k = cfg.shared_attn_every
    g = cfg.n_layers // k
    x = cm.embed(params["embed"], tokens)
    positions = jnp.arange(tokens.shape[1])[None, :]
    glayers = _group_view(params["layers"], g, k)
    shared = params["shared"]

    def group_body(h, gp):
        def one_mamba(hh, lp):
            y, _ = mb.apply_mamba(
                lp["mamba"], cm.apply_norm(lp["ln"], hh, cfg), cfg
            )
            return hh + y, None

        h, _ = cm.scan(one_mamba, h, gp)
        h, _ = _shared_block(shared, h, cfg, positions)
        return h, None

    body = apply_remat(group_body, remat)
    x, _ = cm.scan(body, x, glayers)
    x = cm.apply_norm(params["ln_f"], x, cfg)
    return cm.unembed(params["embed"], x, cfg), jnp.zeros((), jnp.float32)


def loss_fn(params, batch, cfg: ModelConfig,
            remat: RematPolicy = RematPolicy.SAVE_DOTS):
    logits, aux = forward(params, batch["tokens"], cfg, remat=remat)
    ce = cm.cross_entropy(logits, batch["labels"], cfg.vocab)
    return ce + aux, {"ce": ce, "aux": aux}


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int, vis=None,
               n_pages=None):
    k = cfg.shared_attn_every
    g = cfg.n_layers // k
    h, ds, dh = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_headdim
    conv_ch = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    dt = jnp.dtype(cfg.dtype)
    if cfg.cache_layout == "paged":
        kv, pages = cm.paged_kv_buffers((g,), batch, max_len, cfg, n_pages)
    else:
        kv_shape = (g, batch, max_len, cfg.n_kv_heads, cfg.head_dim_)
        kv = {"k": jnp.zeros(kv_shape, dt), "v": jnp.zeros(kv_shape, dt)}
        pages = None
    cache = {
        "ssm": jnp.zeros((cfg.n_layers, batch, h, ds, dh), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, conv_ch), dt),
        "kv": kv,
        "lengths": jnp.zeros((batch,), jnp.int32),
    }
    if pages is not None:
        cache["pages"] = pages
    return cache


def prefill(params, cache, tokens, cfg: ModelConfig, seg_lens=None,
            all_logits=False):
    b, s = tokens.shape
    k = cfg.shared_attn_every
    g = cfg.n_layers // k
    x = cm.embed(params["embed"], tokens)
    lengths = cache["lengths"]
    pages = cache.get("pages")
    positions = lengths[:, None] + jnp.arange(s)[None, :]
    glayers = _group_view(params["layers"], g, k)
    gssm = cache["ssm"].reshape(g, k, *cache["ssm"].shape[1:])
    gconv = cache["conv"].reshape(g, k, *cache["conv"].shape[1:])
    shared = params["shared"]

    def group_body(h, inp):
        gp, ssm_g, conv_g, kv_g = inp

        def one_mamba(hh, inp2):
            lp, st, cv = inp2
            y, (nst, ncv) = mb.apply_mamba(
                lp["mamba"], cm.apply_norm(lp["ln"], hh, cfg), cfg,
                state=st, conv_prev=cv, seg_lens=seg_lens,
            )
            return hh + y, (nst, ncv)

        h, (nssm, nconv) = cm.scan(one_mamba, h, (gp, ssm_g, conv_g))
        kv_in = {"k": kv_g["k"], "v": kv_g["v"], "lengths": lengths}
        if pages is not None:
            kv_in["pages"] = pages
        h, nkv = _shared_block(
            shared, h, cfg, positions, cache=kv_in, seg_lens=seg_lens
        )
        return h, (nssm, nconv, nkv)

    x, (nssm, nconv, nkv) = cm.scan(
        group_body, x,
        (glayers, gssm, gconv,
         {"k": cache["kv"]["k"], "v": cache["kv"]["v"]}),
    )
    x = cm.apply_norm(params["ln_f"], x, cfg)
    out = x if all_logits else cm.last_valid_slice(x, seg_lens)
    logits = cm.unembed(params["embed"], out, cfg)
    new_cache = {
        "ssm": nssm.reshape(cfg.n_layers, *nssm.shape[2:]),
        "conv": nconv.reshape(cfg.n_layers, *nconv.shape[2:]),
        "kv": {"k": nkv["k"], "v": nkv["v"]},
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
        reset_slots=cm.reset_recurrent,
    )
