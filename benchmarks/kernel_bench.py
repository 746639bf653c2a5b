"""Kernel microbenchmarks: CPU wall time of the jitted XLA-path ops and the
modeled v5e time per policy (the TPU target numbers come from the roofline
model; CPU wall time anchors relative costs only).

Modeled queries route through the memoized planner (``plan_cache``), so the
per-shape policy ablation shares plans with the engine's own planning."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import StaticMode, make_engine
from repro.core.characterize import attention_op, matmul_op


def _time(fn, *args, n=5):
    y = fn(*args)
    jax.tree_util.tree_map(lambda a: a.block_until_ready(), y)
    t0 = time.perf_counter()
    for _ in range(n):
        y = fn(*args)
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), y)
    return (time.perf_counter() - t0) / n


def matmul_policy_ablation(plan_cache=None):
    """Modeled v5e time for a training GEMM under each policy + the
    engine's plan (paper technique applied to the TPU kernel)."""
    rows = []
    eng = make_engine(plan_cache=plan_cache)
    for (m, k, n) in [(4096, 4096, 4096), (8192, 8192, 1024),
                      (512, 8192, 51200)]:
        op = matmul_op(m, k, n, dtype="bf16")
        for mode in (StaticMode.UNCACHED, StaticMode.CACHER,
                     StaticMode.CACHERW):
            c = eng.planner.cost(op, mode=mode)
            rows.append({
                "name": f"kern_mm/{m}x{k}x{n}/{mode.value}",
                "modeled_us": c.t_total * 1e6,
                "hbm_mb": c.hbm_bytes / 1e6,
            })
        plan = eng.plan_op(op)
        c = eng.cost(op, plan)
        rows.append({
            "name": f"kern_mm/{m}x{k}x{n}/engine",
            "modeled_us": c.t_total * 1e6,
            "hbm_mb": c.hbm_bytes / 1e6,
            "vmem_mb": plan.vmem_bytes / 1e6,
        })
    return rows


def attention_policy_ablation(plan_cache=None):
    rows = []
    eng = make_engine(plan_cache=plan_cache)
    for (b, hq, hkv, s, d) in [(8, 32, 4, 4096, 128), (1, 32, 8, 32768, 128)]:
        op = attention_op(b, hq, hkv, s, s, d)
        plan = eng.plan_op(op)
        for mode in (StaticMode.UNCACHED, StaticMode.CACHERW):
            c = eng.planner.cost(op, mode=mode)
            rows.append({
                "name": f"kern_attn/b{b}h{hq}s{s}/{mode.value}",
                "modeled_us": c.t_total * 1e6,
                "hbm_mb": c.hbm_bytes / 1e6,
            })
        c = eng.cost(op, plan)
        rows.append({
            "name": f"kern_attn/b{b}h{hq}s{s}/engine",
            "modeled_us": c.t_total * 1e6,
            "hbm_mb": c.hbm_bytes / 1e6,
            "blocks": str(plan.block),
        })
    return rows


def decode_attention_ablation(contexts=(256, 512, 1024), page=16):
    """Paged decode attention across resident-context lengths: xla
    ``_sdpa`` over the gathered view vs gather + dense split-KV kernel vs
    the paged kernel reading the pool in place.

    Wall times (CPU, kernels in interpret mode) anchor relative cost only;
    the modeled column is the v5e HBM roofline story and the acceptance
    gate: the gather path pays the full resident-context KV stream three
    times per step (read pool, write dense copy, read dense copy in the
    kernel) where the paged path reads each mapped page exactly once — so
    the modeled advantage must GROW with resident context (asserted), and
    paged-vs-gather bit-identity is asserted on every shape.
    """
    import numpy as np

    from repro import hw
    from repro.kernels.decode_attention import ops
    from repro.models import common as cm

    b, hq, hkv, d = 2, 8, 2, 64

    def dense(pool, pg):
        """gather_pages' (b, t, hkv * d) rows as (b, t, hkv, d)."""
        return cm.gather_pages(pool, pg).reshape(b, -1, hkv, d)

    def xla_path(q, kp, vp, pg, ln):
        kd = dense(kp, pg)
        vd = dense(vp, pg)
        return cm._sdpa(q[:, None], kd, vd, causal=True, q_offset=ln - 1,
                        kv_len=ln)[:, 0]

    xla_jit = jax.jit(xla_path)

    rows, advantages, identity_pairs = [], [], []
    for t in contexts:
        P = t // page
        n_pages = b * P
        ks = jax.random.split(jax.random.PRNGKey(t), 4)
        q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
        k_pool = jax.random.normal(ks[1], (n_pages, page, hkv * d),
                                   jnp.float32)
        v_pool = jax.random.normal(ks[2], (n_pages, page, hkv * d),
                                   jnp.float32)
        perm = jax.random.permutation(ks[3], n_pages)[: b * P]
        pages = perm.reshape(b, P).astype(jnp.int32)
        lengths = jnp.asarray([t, t - page // 2], jnp.int32)
        splits = ops.plan_splits(t, page)

        def gather_kernel(q, kp, vp, pg, ln, s=splits):
            kd = jnp.swapaxes(dense(kp, pg), 1, 2)
            vd = jnp.swapaxes(dense(vp, pg), 1, 2)
            return ops.decode_attention(q, kd, vd, ln, bkv=page, splits=s)

        def paged_kernel(q, kp, vp, pg, ln, s=splits):
            return ops.paged_decode_attention(q, kp, vp, pg, ln, splits=s)

        args = (q, k_pool, v_pool, pages, lengths)
        fns = {"xla_sdpa": xla_jit, "gather_kernel": gather_kernel,
               "paged_kernel": paged_kernel}
        wall = {name: _time(fn, *args, n=3) * 1e6
                for name, fn in fns.items()}
        identity_pairs.append(
            (t, fns["paged_kernel"](*args), fns["gather_kernel"](*args))
        )

        # v5e roofline, per decode step: the KV stream is t*hkv*d*2 bytes
        # per side; gather reads the pool, writes the dense copy, and the
        # kernel reads the copy back — 3 passes.  Paged reads the pool
        # once.  Fixed per-step bytes (q, output, partials) are shared.
        kv_bytes = 2 * b * t * hkv * d * 4            # K and V, fp32
        fixed = (2 * b * hq * d * 4                   # q in, out
                 + 3 * b * hq * splits * (d + 2) * 4)  # (acc, m, l) partials
        gather_us = hw.hbm_time(3 * kv_bytes + fixed) * 1e6
        paged_us = hw.hbm_time(kv_bytes + fixed) * 1e6
        advantage = gather_us / paged_us
        advantages.append(advantage)
        rows.append({
            "name": f"kern_decode/t{t}",
            "us_per_call": wall["paged_kernel"],
            "xla_us": wall["xla_sdpa"],
            "gather_kernel_us": wall["gather_kernel"],
            "modeled_gather_us": gather_us,
            "modeled_paged_us": paged_us,
            "modeled_advantage": advantage,
            "gather_copy_mb_per_step": kv_bytes / 1e6,
            "splits": splits,
        })
    # The in-place page dereference must change nothing vs the gather
    # contract (clamp-to-page-0-then-mask) — the CI identity gate.  One
    # batched device_get for every context's pair.
    for t, paged_out, gather_out in jax.device_get(identity_pairs):
        assert np.array_equal(paged_out, gather_out), (
            f"paged kernel != gather path at t={t}"
        )
    assert all(a2 > a1 for a1, a2 in zip(advantages, advantages[1:])), (
        f"paged advantage must grow with resident context: {advantages}"
    )
    return rows


def xla_wall_times():
    """Wall time of the pure-XLA model ops on CPU (small shapes)."""
    rows = []
    from repro.models import common as cm

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 64, 8, 64), jnp.float32)
    k = jax.random.normal(key, (2, 512, 2, 64), jnp.float32)
    v = jax.random.normal(key, (2, 512, 2, 64), jnp.float32)

    naive = jax.jit(lambda q, k, v: cm._sdpa_naive(q, k, v, True, 0))
    chunk = jax.jit(lambda q, k, v: cm._sdpa_chunked(q, k, v, True, 0,
                                                     chunk=128))
    rows.append({"name": "xla/sdpa_naive",
                 "us_per_call": _time(naive, q, k, v) * 1e6})
    rows.append({"name": "xla/sdpa_chunked",
                 "us_per_call": _time(chunk, q, k, v) * 1e6})
    return rows
