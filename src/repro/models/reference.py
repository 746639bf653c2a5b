"""Plain float32 reference forward of the transformer decoder block, the
oracle the serving tests hold the engine to (model-configs §3).

One sequence, no cache, no batching, no kernels, every matmul at
``jax.default_matmul_precision("highest")``, written from the published
description and not from ``repro.models.common``:

* token embedding; per layer ``x += Attn(RMSNorm(x))`` and
  ``x += FFN(RMSNorm(x))``; a final RMSNorm and the (tied or untied)
  output matrix;
* attention: grouped K/V heads, rotary embedding on the two halves of each
  head; a ``sliding_attention`` layer lets query i see key j iff
  0 <= i - j < ``sliding_window``, a full one every j <= i; full layers
  take YaRN frequencies (transformers' ``_compute_yarn_parameters`` with
  truncation) with cos and sin scaled by its attention factor;
* FFN: SwiGLU, or a MoE whose float32 router softmax spans all
  ``n_experts``, keeps the top ``experts_per_token`` renormalized, and sums
  gate x SwiGLU over the experts held by ``expert_shard`` (all of them for
  one shard).

Weights are the program's own parameter tree (``params["layers"]``
stacked over layers), read only.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

SLIDING = "sliding_attention"


def _inv_freq(dh: int, theta: float, yarn) -> tuple[np.ndarray, float]:
    """(dh/2,) rotary inverse frequencies and the cos/sin scale."""
    base = theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    if yarn is None:
        return 1.0 / base, 1.0

    def dim_of(rotations):
        return (dh * math.log(yarn.original_max_position_embeddings
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_of(yarn.beta_fast)), 0)
    hi = min(math.ceil(dim_of(yarn.beta_slow)), dh - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dh // 2) - lo) / (hi - lo), 0.0, 1.0)
    inv = (1.0 / base) * (1.0 - ramp) + (1.0 / (yarn.factor * base)) * ramp
    scale = yarn.attention_factor or 0.1 * math.log(yarn.factor) + 1.0
    return inv, scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def forward(params, tokens, cfg) -> jnp.ndarray:
    """Logits (len(tokens), padded vocab) of one sequence, float32."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens), cfg)


def _forward(params, tokens, cfg):
    f32 = jnp.float32
    n = tokens.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    types = cfg.layer_types or ("full_attention",) * cfg.n_layers
    pos = np.arange(n, dtype=np.float64)
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    x = params["embed"]["tok"][tokens].astype(f32)
    for layer in range(cfg.n_layers):
        p = jax.tree_util.tree_map(lambda a: a[layer].astype(f32),
                                   params["layers"])
        sliding = types[layer] == SLIDING
        inv, scale = _inv_freq(dh, cfg.rope_theta,
                               None if sliding else cfg.rope_yarn)
        ang = pos[:, None] * inv[None, :]
        cos = jnp.asarray(np.cos(ang) * scale, f32)[:, None]
        sin = jnp.asarray(np.sin(ang) * scale, f32)[:, None]

        def rope(t):
            a, b = t[..., :dh // 2], t[..., dh // 2:]
            return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

        h = _rms(x, p["ln1"]["w"], cfg.norm_eps)
        q = rope(jnp.einsum("sd,dhk->shk", h, p["attn"]["wq"]))
        k = rope(jnp.einsum("sd,dhk->shk", h, p["attn"]["wk"]))
        v = jnp.einsum("sd,dhk->shk", h, p["attn"]["wv"])
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        visible = j <= i
        if sliding:
            visible = visible & (i - j < cfg.sliding_window)
        s = jnp.einsum("shk,thk->hst", q, k) / math.sqrt(dh)
        s = jnp.where(jnp.asarray(visible)[None], s, -jnp.inf)
        o = jnp.einsum("hst,thk->shk", jax.nn.softmax(s, -1), v)
        x = x + jnp.einsum("shk,hkd->sd", o, p["attn"]["wo"])
        h = _rms(x, p["ln2"]["w"], cfg.norm_eps)
        x = x + (_moe(p["moe"], h, cfg) if "moe" in p
                 else _swiglu(p["mlp"], h))
    x = _rms(x, params["ln_f"]["w"].astype(f32), cfg.norm_eps)
    out = (params["embed"]["tok"].T if cfg.tie_embeddings
           else params["embed"]["unembed"])
    return x @ out.astype(f32)


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]


def _moe(p, h, cfg):
    probs = jax.nn.softmax(h @ p["router"], -1)              # (n, experts)
    top, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    top = top / jnp.sum(top, -1, keepdims=True)
    held = p["wg"].shape[0]
    first = cfg.expert_shard * held
    y = jnp.zeros_like(h)
    for e in range(held):
        gate = jnp.sum(jnp.where(idx == first + e, top, 0.0), -1)
        y = y + gate[:, None] * _swiglu(
            {"wg": p["wg"][e], "wu": p["wu"][e], "wd": p["wd"][e]}, h)
    return y
