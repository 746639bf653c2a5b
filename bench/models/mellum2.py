"""The benchmark's own model of Mellum2-12B-A2.5B (JetBrains/Mellum2-12B-
A2.5B-Instruct, its config.json) as one chip's share of an expert-parallel
deployment: random weights from a seed, a plain float32 reference forward,
its float8 control, and the FLOP counts the ``*_mfu`` metrics divide by.
Imports nothing of the program under test.

Block: token embedding, then per layer ``x += Attn(RMSNorm(x))`` and
``x += MoE(RMSNorm(x))``, a final RMSNorm and an untied output matrix.

* Attention: grouped K/V heads, rotary embedding on the two halves of each
  head.  ``layer_types`` gives each layer's kind: a ``sliding_attention``
  layer lets query i see key j iff 0 <= i - j < ``sliding_window`` and
  uses ``rope_parameters["sliding_attention"]`` (default RoPE); a
  ``full_attention`` layer sees every j <= i and uses
  ``rope_parameters["full_attention"]`` (YaRN, as transformers'
  ``_compute_yarn_parameters`` computes it with truncation, cos and sin
  scaled by its attention factor on q and k alike).
* MoE: float32 router logits over all ``published["num_experts"]``
  experts, softmax, the top ``num_experts_per_tok``, renormalized
  (``norm_topk_prob``); each picked expert is SwiGLU of width
  ``moe_intermediate_size``.  This chip holds ``num_experts`` of them, the
  ``deployment.expert_parallel.shard``-th index range, and the layer's
  output is their part alone: sum over held e of gate_e(x) SwiGLU_e(x).
  What the absent experts would add is left out, as on the chip.

Weights are laid out stacked over layers:

    embed.tok (rows, d)   embed.unembed (d, rows)   ln_f.w (d,)
    layers.ln1.w / ln2.w (L, d)
    layers.attn.wq (L, d, hq, dh)  wk / wv (L, d, hkv, dh)  wo (L, hq, dh, d)
    layers.moe.router (L, d, E) float32
    layers.moe.wg / wu (L, e, d, f)   wd (L, e, f, d)

The forward runs its attention ``Q_BLOCK`` query rows at a time, so that a
6144-position stream fits beside the weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_BLOCK = 256
FULL, SLIDING = "full_attention", "sliding_attention"


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    hq = c["num_attention_heads"]
    e = c["num_experts"]
    E = c.get("published", {}).get("num_experts", e)
    shard = c.get("deployment", {}).get("expert_parallel", {}).get("shard", 0)
    types = c["layer_types"]
    return dict(
        L=c["num_hidden_layers"], d=d, hq=hq,
        hkv=c.get("num_key_value_heads", hq),
        dh=c.get("head_dim", d // hq), f=c["moe_intermediate_size"],
        e=e, E=E, k=c["num_experts_per_tok"], first=shard * e,
        V=c["vocab_size"], eps=c.get("rms_norm_eps", 1e-6),
        W=c["sliding_window"], types=types,
        n_full=sum(t == FULL for t in types),
        n_sliding=sum(t == SLIDING for t in types),
    )


def seed_key(seed: int):
    """A PRNG key from any whole number (64 bits are folded in)."""
    seed %= 2 ** 64
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def init_weights(c: dict, seed: int, rows: int):
    """Every weight, on the device, in one jitted call: matrices in bf16
    (the served type), norm gains and the router in float32."""
    m = dims(c)
    L, d, hq, hkv, dh, f, e, E, V = (m[x] for x in (
        "L", "d", "hq", "hkv", "dh", "f", "e", "E", "V"))
    bf = jnp.bfloat16

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def dense(shape, fan_in, dtype=bf):
            return (jax.random.normal(next(ks), shape, dtype)
                    * dtype(fan_in ** -0.5))

        def gain(shape):
            return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

        tok = jax.random.normal(next(ks), (rows, d), bf) * bf(0.02)
        tok = jnp.where(jnp.arange(rows)[:, None] < V, tok, 0).astype(bf)
        un = dense((d, rows), d)
        un = jnp.where(jnp.arange(rows)[None] < V, un, 0).astype(bf)
        return {
            "embed": {"tok": tok, "unembed": un},
            "ln_f": {"w": gain((d,))},
            "layers": {
                "ln1": {"w": gain((L, d))},
                "ln2": {"w": gain((L, d))},
                "attn": {"wq": dense((L, d, hq, dh), d),
                         "wk": dense((L, d, hkv, dh), d),
                         "wv": dense((L, d, hkv, dh), d),
                         "wo": dense((L, hq, dh, d), hq * dh)},
                "moe": {"router": dense((L, d, E), d, jnp.float32),
                        "wg": dense((L, e, d, f), d),
                        "wu": dense((L, e, d, f), d),
                        "wd": dense((L, e, f, d), f)},
            },
        }

    return jax.jit(make)(seed_key(seed))


def rope_tables(dh: int, p: dict, positions: np.ndarray):
    """(cos, sin), each (W, dh / 2), of one ``rope_parameters`` entry."""
    theta = p["rope_theta"]
    base = theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    inv = 1.0 / base
    scale = 1.0
    if p.get("rope_type", "default") == "yarn":
        factor = p["factor"]
        orig = p["original_max_position_embeddings"]

        def dim_of(rotations):
            return (dh * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        lo = max(math.floor(dim_of(p.get("beta_fast", 32))), 0)
        hi = min(math.ceil(dim_of(p.get("beta_slow", 1))), dh - 1)
        if lo == hi:
            hi += 0.001
        ramp = np.clip((np.arange(dh // 2) - lo) / (hi - lo), 0.0, 1.0)
        inv = inv * (1.0 - ramp) + inv / factor * ramp
        scale = p.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    ang = positions[:, None].astype(np.float64) * inv[None, :]
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


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
    dh, half = m["dh"], m["dh"] // 2
    positions = np.arange(W)
    rp = c["rope_parameters"]
    tables = {kind: rope_tables(dh, rp[kind], positions)
              for kind in (FULL, SLIDING)}
    is_full = jnp.asarray([t == FULL for t in m["types"]])
    group = m["hq"] // m["hkv"]
    blocks = -(-W // Q_BLOCK)
    rows = jnp.arange(blocks * Q_BLOCK).reshape(blocks, Q_BLOCK)

    def layer(x, inp):
        p, full = inp
        cos = jnp.where(full, tables[FULL][0], tables[SLIDING][0])[:, None]
        sin = jnp.where(full, tables[FULL][1], tables[SLIDING][1])[:, None]

        def rope(t):
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos],
                                   -1)

        h = norm(x, p["ln1"]["w"])
        q = rope(mm("sd,dhk->shk", h, p["attn"]["wq"]))
        k = jnp.repeat(rope(mm("sd,dhk->shk", h, p["attn"]["wk"])), group, 1)
        v = jnp.repeat(mm("sd,dhk->shk", h, p["attn"]["wv"]), group, 1)
        reach = jnp.where(full, W + 1, m["W"])

        def attend(qi):
            qb = q[jnp.clip(qi, 0, W - 1)]
            s = jnp.einsum("shk,thk->hst", qb, k, precision=HIGHEST)
            back = qi[:, None] - jnp.arange(W)[None, :]
            s = jnp.where((back >= 0) & (back < reach), s * dh ** -0.5,
                          -jnp.inf)
            return jnp.einsum("hst,thk->shk", jax.nn.softmax(s, -1), v,
                              precision=HIGHEST)

        o = jax.lax.map(attend, rows).reshape(-1, m["hq"], dh)[:W]
        x = x + mm("shk,hkd->sd", o, p["attn"]["wo"])

        h = norm(x, p["ln2"]["w"])
        mo = p["moe"]
        probs = jax.nn.softmax(mm("sd,de->se", h, mo["router"]), -1)
        top, idx = jax.lax.top_k(probs, m["k"])
        top = top / jnp.sum(top, -1, keepdims=True)
        gate = jnp.zeros((W, m["E"]), f32).at[
            jnp.arange(W)[:, None], idx].set(top)
        gate = gate[:, m["first"]:m["first"] + m["e"]]        # held experts

        def expert(acc, ep):
            wg, wu, wd, g = ep
            a = jax.nn.silu(mm("sd,df->sf", h, wg)) * mm("sd,df->sf", h, wu)
            return acc + g[:, None] * mm("sf,fd->sd", a, wd), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (mo["wg"], mo["wu"], mo["wd"], gate.T))
        return x + y, None

    x = w["embed"]["tok"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, (w["layers"], is_full))
    x = norm(x, w["ln_f"]["w"])
    return mm("sd,dv->sv", x, w["embed"]["unembed"][:, :m["V"]])


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


def matmul_params(c: dict) -> float:
    """Weights each token multiplies through, without the output matrix:
    attention, the router, and the held experts it is expected to pick
    (k x held / published of them, 2 for 8 picks over 16 of 64)."""
    m = dims(c)
    attn = (m["d"] * (m["hq"] + 2 * m["hkv"]) * m["dh"]
            + m["hq"] * m["dh"] * m["d"])
    picks = m["k"] * m["e"] / m["E"]
    return m["L"] * (attn + m["d"] * m["E"] + picks * 3 * m["d"] * m["f"])


def _sum_min(a: int, b: int, cap: int) -> int:
    """sum of min(x, cap) for x in [a, b]."""
    if b < a:
        return 0
    hi = min(b, cap)
    low = (a + hi) * (hi - a + 1) // 2 if hi >= a else 0
    return low + cap * max(0, b - max(a - 1, cap))


def prefill_flops(c: dict, start: int, n: int) -> float:
    """Useful FLOPs of prefilling positions start .. start+n-1 behind a
    cached prefix of ``start`` tokens: the matmuls of n tokens, attention
    over the keys each position sees (all earlier ones in a full layer, at
    most the window in a sliding one), and one row of logits."""
    m = dims(c)
    full_keys = n * start + n * (n + 1) / 2
    window_keys = _sum_min(start + 1, start + n, m["W"])
    keys = m["n_full"] * full_keys + m["n_sliding"] * window_keys
    return (2.0 * matmul_params(c) * n + 2.0 * m["d"] * m["V"]
            + 4.0 * m["hq"] * m["dh"] * keys)


def decode_flops(c: dict, context: int) -> float:
    """Useful FLOPs of one decoded token attending to ``context`` keys
    (at most the window of them in a sliding layer)."""
    m = dims(c)
    keys = m["n_full"] * context + m["n_sliding"] * min(context, m["W"])
    return (2.0 * (matmul_params(c) + m["d"] * m["V"])
            + 4.0 * m["hq"] * m["dh"] * keys)
