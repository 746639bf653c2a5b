"""Sharding rules: map every param/activation/cache tensor onto the mesh.

Mesh axes: ``(data, model)`` single-pod, ``(pod, data, model)`` multi-pod.
``pod`` is outer data parallelism (gradient all-reduce crosses pods).

Tensor-parallel policy (DESIGN.md §5):
* attention: shard heads over ``model`` when divisible (most archs);
  minicpm (36H) / whisper (12H) shard head_dim instead (contraction
  sharding).  K/V weights with few KV heads (GQA kv<16) are replicated —
  they are small — while the decode KV *cache* is sharded over ``model``
  along the sequence dim (distributed flash-decode; partial-softmax
  collectives), which is also how long_500k shards over ``data``.
* MLP: d_ff over ``model``; vocab (padded) over ``model``; MoE experts over
  ``model`` (EP); Mamba d_inner projections over ``model``.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def model_axis_size(mesh: Mesh) -> int:
    return mesh.shape["model"]


def _attn_dims(cfg: ModelConfig, n_heads: int, tp: int):
    """(head_spec, dh_spec) for a (…, heads, dh) QUERY/OUTPUT weight."""
    if n_heads % tp == 0:
        return "model", None
    if cfg.head_dim_ % tp == 0:
        return None, "model"
    return None, None


def _kv_dims(cfg: ModelConfig, tp: int):
    """(head_spec, dh_spec) for K/V weights — COUPLED to the Q rule.

    Mixing layouts (Q on heads, K/V on head_dim) makes the attention
    contraction unpartitionable and SPMD falls back to full
    rematerialization (activation-sized all-gathers per layer).  So:
    shard kv heads when divisible; otherwise follow Q exactly — replicated
    K/V weights when Q is heads-sharded (GQA K/V is small), dh-sharded when
    Q is dh-sharded."""
    qh, qd = _attn_dims(cfg, cfg.n_heads, tp)
    if cfg.n_kv_heads % tp == 0:
        return "model", None
    if qh == "model":
        return None, None        # replicate: q heads-sharded, kv tiny
    return None, qd              # dh-sharded with q, or fully replicated


def _add_fsdp(spec: P, shape: tuple[int, ...], mesh: Mesh,
              min_bytes: int = 1 << 20) -> P:
    """ZeRO-3/FSDP: additionally shard a big parameter's largest free,
    data-divisible dim over `data`.  GSPMD then all-gathers weights
    per-layer in the forward and reduce-scatters gradients — the standard
    way >16GB-per-TP-shard models fit v5e."""
    import math

    if math.prod(shape) * 2 < min_bytes:
        return spec
    full = tuple(spec) + (None,) * (len(shape) - len(spec))
    used = {a for part in full if part for a in
            ((part,) if isinstance(part, str) else part)}
    if "data" in used:
        return spec
    data = mesh.shape["data"]
    cands = [i for i, part in enumerate(full)
             if part is None and shape[i] % data == 0 and shape[i] >= data]
    if not cands:
        return spec
    i = max(cands, key=lambda j: shape[j])
    new = list(full)
    new[i] = "data"
    return P(*new)


def param_spec(path: str, shape: tuple[int, ...], cfg: ModelConfig,
               mesh: Mesh, fsdp: bool = False) -> P:
    """PartitionSpec for a parameter leaf (stacking dims auto-padded)."""
    tp = model_axis_size(mesh)
    d, f = cfg.d_model, cfg.d_ff
    dh = cfg.head_dim_

    def pad(rule: tuple) -> P:
        extra = len(shape) - len(rule)
        assert extra >= 0, (path, shape, rule)
        spec = P(*([None] * extra + list(rule)))
        return _add_fsdp(spec, shape, mesh) if fsdp else spec

    qh, qd = _attn_dims(cfg, cfg.n_heads, tp)
    kh, kd = _kv_dims(cfg, tp)
    if ("wq" in path or path.endswith("bq")) and shape[-1] == dh:
        return pad((None, qh, qd) if "wq" in path else (qh, qd))
    if any(k in path for k in ("wk", "wv", "bk", "bv")) and shape[-1] == dh:
        rule = (None, kh, kd) if ("wk" in path or "wv" in path) else (kh, kd)
        return pad(rule)
    if "wo" in path and shape[-1] == d:
        return pad((qh, qd, None))
    if "router" in path:
        return pad((None, "model" if cfg.n_experts % tp == 0 else None))
    held = cfg.experts_held
    if any(k in path for k in ("wg", "wu")):
        if len(shape) >= 3 and shape[-3] == held and shape[-2] == d:
            # EP: experts over model (d_ff stays local per expert shard).
            return pad(("model" if held % tp == 0 else None, None, None))
        return pad((None, "model" if f % tp == 0 else None))
    if "wd" in path:
        if len(shape) >= 3 and shape[-3] == held and shape[-1] == d:
            return pad(("model" if held % tp == 0 else None, None, None))
        return pad(("model" if f % tp == 0 else None, None))
    if "tok" in path or "unembed" in path:
        v = cfg.padded_vocab
        if "unembed" in path:
            return pad((None, "model" if v % tp == 0 else None))
        return pad(("model" if v % tp == 0 else None, None))
    if "in_proj" in path:
        return pad((None, "model" if shape[-1] % tp == 0 else None))
    if "out_proj" in path:
        return pad(("model" if shape[-2] % tp == 0 else None, None))
    if "conv_w" in path:
        return pad((None, "model" if shape[-1] % tp == 0 else None))
    if "conv_b" in path or "norm_w" in path:
        return pad(("model" if shape[-1] % tp == 0 else None,))
    if "vis_proj" in path:
        return pad((None, "model" if d % tp == 0 else None))
    # norms, biases, A_log, D, dt_bias, dec_pos: replicated.
    return pad(())


def _path_str(path) -> str:
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in path
    )


def params_shardings(params_shape: Any, cfg: ModelConfig, mesh: Mesh,
                     fsdp: bool = False):
    """Tree of NamedShardings matching a params (shape-)pytree."""
    def leaf(path, x):
        spec = param_spec(_path_str(path), tuple(x.shape), cfg, mesh, fsdp=fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, params_shape)


def fsdp_needed(cfg: ModelConfig, mesh: Mesh, train: bool,
                hbm_bytes: int = 16 * 1024**3) -> bool:
    """Napkin check: do params (+ fp32 optimizer state for training) fit a
    single TP shard without data-axis sharding?

    Serving uses a looser threshold: FSDP all-gathers per step are poison
    for decode latency and GSPMD may hoist them into a fully-replicated
    param buffer — keep weights TP-resident unless they truly can't fit."""
    n = cfg.param_count()
    tp = model_axis_size(mesh)
    per_chip = n * 2 / tp            # bf16 params
    if train:
        per_chip += n * 12 / tp      # fp32 grads + mu + nu
        return per_chip > 0.35 * hbm_bytes
    return per_chip > 0.8 * hbm_bytes


def _batch_rule(mesh: Mesh, global_batch: int):
    ba = batch_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in ba]))
    if global_batch % total == 0:
        return ba
    if global_batch % mesh.shape["data"] == 0:
        return ("data",)
    return None


def batch_spec(cfg: ModelConfig, mesh: Mesh, global_batch: int) -> dict[str, P]:
    """Specs for a training/serving input batch."""
    b = _batch_rule(mesh, global_batch)
    return {
        "tokens": P(b, None),
        "labels": P(b, None),
        "vis": P(b, None, None),
        "frames": P(b, None, None),
    }


def cache_spec(cfg: ModelConfig, mesh: Mesh, global_batch: int,
               long_context: bool = False) -> dict[str, Any]:
    """Specs for the serving cache pytree (per leaf name).

    Decode KV caches shard the sequence dim: over ``model`` normally, and
    over ``data`` too for long_context batch-1 (sequence parallelism — the
    distributed flash-decode schedule).
    """
    b = _batch_rule(mesh, global_batch)
    seq_axis = ("data",) if (long_context and b is None) else ("model",)
    tp = model_axis_size(mesh)

    kh, _ = _kv_dims(cfg, tp)

    def leaf_spec(path, x):
        p = _path_str(path)
        nd = x.ndim
        if "window" in p:
            # Window rings (L, batch * W / page, page, hkv * dh): each slot
            # owns a contiguous run of pages, so pages shard with the batch.
            return P(None, b, None, None)
        if p.endswith("/k") or p.endswith("/v") or "/k/" in p or "/v/" in p:
            if "cross" in p:
                # Fixed-source (enc/vision) KV: small, reused every step —
                # the RESIDENT operand; shard kv heads if divisible.
                return P(*([None] * (nd - 4) + [b, None, kh, None]))
            # Self-attention cache (..., batch, S, hkv, dh): shard the
            # sequence dim — distributed flash-decode.  Long-context
            # batch-1 shards seq over `data` AND kv heads over `model`.
            head_axis = kh if long_context else None
            return P(*([None] * (nd - 4) + [b, seq_axis, head_axis, None]))
        if "ssm" in p:
            # (L, batch, h, ds, dh): shard heads over model.
            rule = [None] * (nd - 4) + [b, "model" if cfg.ssm_heads % tp == 0 else None, None, None]
            return P(*rule)
        if "conv" in p:
            rule = [None] * (nd - 3) + [b, None,
                                        "model" if (cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state) % tp == 0 else None]
            return P(*rule)
        if "vis" in p:
            return P(*([None] * (nd - 3) + [b, None, None]))
        return P(*([None] * nd))

    return leaf_spec
