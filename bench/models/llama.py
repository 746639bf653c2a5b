"""The benchmark's own model of a llama-style dense decoder: random weights
from a seed, a plain float32 reference forward, its lower-precision control,
and the FLOP counts the ``*_mfu`` metrics divide by.  Imports nothing of the
program under test.

Block (published llama / MiniCPM description): token embedding (times
``scale_emb``), then per layer ``x += s * Attn(RMSNorm(x))`` and
``x += s * SwiGLU(RMSNorm(x))`` with ``s = scale_depth / sqrt(layers)``,
causal multi-head attention with grouped K/V heads and rotary embedding on
the two halves of each head, a final RMSNorm, and logits ``h / (hidden /
dim_model_base)`` times the (tied or separate) output matrix.  A config
without the MiniCPM keys gets 1 for each scale.

Weights are laid out stacked over layers:

    embed.tok (rows, d)   [embed.unembed (d, rows)]   ln_f.w (d,)
    layers.ln1.w / ln2.w (L, d)
    layers.attn.wq (L, d, hq, dh)  wk / wv (L, d, hkv, dh)  wo (L, hq, dh, d)
    layers.mlp.wg / wu (L, d, f)   wd (L, f, d)

``rows`` may exceed the vocabulary (a padded table, as checkpoints are
padded); the rows past ``vocab_size`` are zero and no logit is read there.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    hq = c["num_attention_heads"]
    return dict(
        L=c["num_hidden_layers"], d=d, hq=hq,
        hkv=c.get("num_key_value_heads", hq),
        dh=c.get("head_dim", d // hq), f=c["intermediate_size"],
        V=c["vocab_size"], tied=bool(c.get("tie_word_embeddings", False)),
        eps=c.get("rms_norm_eps", 1e-6), theta=c.get("rope_theta", 1e4),
        scale_emb=c.get("scale_emb", 1.0),
        res_scale=c.get("scale_depth", math.sqrt(c["num_hidden_layers"]))
        / math.sqrt(c["num_hidden_layers"]),
        logit_div=d / c.get("dim_model_base", d),
    )


def seed_key(seed: int):
    """A PRNG key from any whole number (64 bits are folded in)."""
    seed %= 2 ** 64
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def init_weights(c: dict, seed: int, rows: int):
    """Every weight, on the device, in one jitted call: matrices in bf16
    (the served type), norm gains in float32."""
    m = dims(c)
    L, d, hq, hkv, dh, f, V = (m[k] for k in ("L", "d", "hq", "hkv", "dh",
                                                "f", "V"))
    bf = jnp.bfloat16

    def make(key):
        ks = iter(jax.random.split(key, 12))

        def dense(shape, fan_in):
            return (jax.random.normal(next(ks), shape, bf)
                    * bf(fan_in ** -0.5))

        def gain(shape):
            return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

        tok = jax.random.normal(next(ks), (rows, d), bf) * bf(0.02)
        tok = jnp.where(jnp.arange(rows)[:, None] < V, tok, 0).astype(bf)
        embed = {"tok": tok}
        if not m["tied"]:
            un = dense((d, rows), d)
            embed["unembed"] = jnp.where(jnp.arange(rows)[None] < V, un, 0
                                         ).astype(bf)
        return {
            "embed": embed,
            "ln_f": {"w": gain((d,))},
            "layers": {
                "ln1": {"w": gain((L, d))},
                "ln2": {"w": gain((L, d))},
                "attn": {"wq": dense((L, d, hq, dh), d),
                         "wk": dense((L, d, hkv, dh), d),
                         "wv": dense((L, d, hkv, dh), d),
                         "wo": dense((L, hq, dh, d), hq * dh)},
                "mlp": {"wg": dense((L, d, f), d), "wu": dense((L, d, f), d),
                        "wd": dense((L, f, d), f)},
            },
        }

    return jax.jit(make)(seed_key(seed))


def _fp8(x, axis=None):
    """Round to float8 e4m3 with an amax scale (per tensor, or per row of
    ``axis``) and return float32: the control's lower precision."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _forward(w, c: dict, tokens, low: bool):
    """Logits (W, V) of one sequence, float32 at HIGHEST precision; with
    ``low`` every matmul operand is first rounded to float8."""
    m = dims(c)
    f32 = jnp.float32
    wq8 = (lambda a: _fp8(a.astype(f32))) if low else (lambda a: a.astype(f32))
    aq8 = (lambda a: _fp8(a, axis=-1)) if low else (lambda a: a)

    def mm(spec, a, b):
        return jnp.einsum(spec, aq8(a), wq8(b), precision=HIGHEST)

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + m["eps"]) * g

    W = tokens.shape[0]
    pos = jnp.arange(W, dtype=f32)
    half = m["dh"] // 2
    ang = pos[:, None] * m["theta"] ** (-jnp.arange(half, dtype=f32) / half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    group = m["hq"] // m["hkv"]
    causal = jnp.arange(W)[:, None] >= jnp.arange(W)[None, :]

    def layer(x, p):
        h = norm(x, p["ln1"]["w"])
        q = rope(mm("sd,dhk->shk", h, p["attn"]["wq"]))
        k = rope(mm("sd,dhk->shk", h, p["attn"]["wk"]))
        v = mm("sd,dhk->shk", h, p["attn"]["wv"])
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("shk,thk->hst", q, k, precision=HIGHEST)
        s = jnp.where(causal, s * m["dh"] ** -0.5, -jnp.inf)
        o = jnp.einsum("hst,thk->shk", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST)
        x = x + m["res_scale"] * mm("shk,hkd->sd", o, p["attn"]["wo"])
        h = norm(x, p["ln2"]["w"])
        g = jax.nn.silu(mm("sd,df->sf", h, p["mlp"]["wg"]))
        u = mm("sd,df->sf", h, p["mlp"]["wu"])
        x = x + m["res_scale"] * mm("sf,fd->sd", g * u, p["mlp"]["wd"])
        return x, None

    x = w["embed"]["tok"][tokens].astype(f32) * m["scale_emb"]
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = norm(x, w["ln_f"]["w"]) / m["logit_div"]
    V = m["V"]
    if m["tied"]:
        return mm("sd,vd->sv", x, w["embed"]["tok"][:V])
    return mm("sd,dv->sv", x, w["embed"]["unembed"][:, :V])


def make_gap_fn(c: dict, control: bool = False):
    """Jitted ``(w, tokens (W,), targets (W,)) -> gaps (W,)``: how far the
    token ``targets[i]`` served after position i sits below the reference's
    largest logit there.  With ``control`` it also returns the same gap for
    the token the float8 control puts first at each position."""

    @jax.jit
    def gaps(w, tokens, targets):
        ref = _forward(w, c, tokens, low=False)
        top = jnp.max(ref, -1)
        served = jnp.take_along_axis(ref, targets[:, None], 1)[:, 0]
        if not control:
            return top - served
        low = _forward(w, c, tokens, low=True)
        pick = jnp.argmax(low, -1)
        ctl = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
        return top - served, top - ctl

    return gaps


def matmul_params(c: dict) -> int:
    """Weights each token multiplies through, without the output matrix."""
    m = dims(c)
    attn = m["d"] * (m["hq"] + 2 * m["hkv"]) * m["dh"] + m["hq"] * m["dh"] * m["d"]
    return m["L"] * (attn + 3 * m["d"] * m["f"])


def prefill_flops(c: dict, start: int, n: int) -> float:
    """Useful FLOPs of prefilling positions start .. start+n-1 behind a
    cached prefix of ``start`` tokens: the matmuls of n tokens, causal
    attention over the keys each position sees, and one row of logits."""
    m = dims(c)
    keys = n * start + n * (n + 1) / 2
    return (2.0 * matmul_params(c) * n + 2.0 * m["d"] * m["V"]
            + 4.0 * m["L"] * m["hq"] * m["dh"] * keys)


def decode_flops(c: dict, context: int) -> float:
    """Useful FLOPs of one decoded token attending to ``context`` keys."""
    m = dims(c)
    return (2.0 * (matmul_params(c) + m["d"] * m["V"])
            + 4.0 * m["L"] * m["hq"] * m["dh"] * context)
