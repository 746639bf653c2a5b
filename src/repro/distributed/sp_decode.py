"""Sequence-parallel flash-decode across chips (shard_map).

The long_500k cell: batch=1, KV cache of 524288 tokens — no batch axis to
shard.  The cache's sequence dim is sharded over the ``data`` axis; every
chip computes flash-decode over its local KV shard and the partial
(acc, max, sum) triples merge with the same log-sum-exp combine the
split-KV kernel uses on-chip.  This makes decode bandwidth scale with the
number of chips — the STREAM policy executed fleet-wide.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.decode_attention import ref as dec_ref


def _local_partials(q, k, v, lengths, shard_start, scale):
    """One shard's flash-decode partials over its local KV slice."""
    b, hq, d = q.shape
    s_local = k.shape[2]
    group = hq // k.shape[1]
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum(
        "bhd,bhsd->bhs", q.astype(jnp.float32), kx.astype(jnp.float32)
    ) * scale
    pos = shard_start + jnp.arange(s_local)[None, None, :]
    mask = pos < lengths[:, None, None]
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1)
    p = jnp.where(mask, jnp.exp(logits - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhs,bhsd->bhd", p, vx.astype(jnp.float32))
    return acc, m, l


def sp_decode_attention(
    q: jnp.ndarray,        # (b, hq, d) replicated
    k: jnp.ndarray,        # (b, hkv, S, d) sharded over seq on `axis`
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # (b,)
    mesh: Mesh,
    axis: str = "data",
    scale: float | None = None,
) -> jnp.ndarray:
    """Distributed flash-decode: partial softmax per shard + psum combine."""
    d = q.shape[-1]
    scale = float(scale if scale is not None else d ** -0.5)
    n_shards = mesh.shape[axis]
    s_local = k.shape[2] // n_shards

    def body(q_, k_, v_, len_):
        idx = jax.lax.axis_index(axis)
        acc, m, l = _local_partials(
            q_, k_, v_, len_, idx * s_local, scale
        )
        # Log-sum-exp combine across shards:
        m_glob = jax.lax.pmax(m, axis)
        w = jnp.exp(m - m_glob)
        num = jax.lax.psum(acc * w[..., None], axis)
        den = jax.lax.psum(l * w, axis)
        return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q_.dtype)

    spec_kv = P(None, None, axis, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), spec_kv, spec_kv, P()),
        out_specs=P(),
    )
    return fn(q, k, v, lengths)


def reference(q, k, v, lengths, scale=None):
    return dec_ref.decode_attention(q, k, v, lengths, scale=scale)
