"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

TOL = {jnp.float32: dict(rtol=2e-3, atol=2e-3),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestMatmul:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("mkn", [(256, 128, 256), (512, 384, 128),
                                     (130, 70, 90)])
    @pytest.mark.parametrize("kwargs", [
        dict(bm=128, bn=128, bk=128),
        dict(bm=128, bn=128, bk=128, split_k=3),
    ])
    def test_vs_ref(self, dtype, mkn, kwargs):
        from repro.kernels.matmul import ref
        from repro.kernels.matmul.ops import matmul

        m, k, n = mkn
        a = _rand(jax.random.PRNGKey(0), (m, k), dtype)
        b = _rand(jax.random.PRNGKey(1), (k, n), dtype)
        got = matmul(a, b, **kwargs)
        want = ref.matmul(a, b)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype],
        )

    def test_grid_orders_match(self):
        from repro.kernels.matmul.matmul import matmul as kern

        a = _rand(jax.random.PRNGKey(0), (256, 256), jnp.float32)
        b = _rand(jax.random.PRNGKey(1), (256, 256), jnp.float32)
        y1 = kern(a, b, bm=128, bn=128, bk=128, order="mnk")
        y2 = kern(a, b, bm=128, bn=128, bk=128, order="nmk")
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5)

    def test_engine_planned(self):
        from repro.core import make_engine
        from repro.kernels.matmul.ops import matmul

        a = jnp.ones((200, 300), jnp.float32)
        b = jnp.ones((300, 100), jnp.float32)
        y = matmul(a, b, engine=make_engine())
        np.testing.assert_allclose(np.asarray(y), 300.0, rtol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("cfg", [
        (2, 4, 2, 256, 256, 64, True, 0),
        (1, 8, 1, 128, 256, 32, True, 128),
        (2, 4, 4, 100, 100, 64, False, 0),
        (1, 6, 2, 192, 64, 128, False, 0),
    ])
    def test_vs_ref(self, dtype, cfg):
        from repro.kernels.flash_attention import ref
        from repro.kernels.flash_attention.ops import flash_attention

        b, hq, hkv, sq, skv, d, causal, off = cfg
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(ks[0], (b, hq, sq, d), dtype)
        k = _rand(ks[1], (b, hkv, skv, d), dtype)
        v = _rand(ks[2], (b, hkv, skv, d), dtype)
        got = flash_attention(q, k, v, causal=causal, q_offset=off,
                              bq=64, bkv=64)
        want = ref.attention(q, k, v, causal=causal, q_offset=off)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype],
        )


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("cfg", [
        (2, 8, 2, 512, 64, 128, 1), (2, 8, 2, 512, 64, 128, 4),
        (3, 4, 4, 300, 32, 64, 2), (1, 16, 1, 1024, 128, 256, 8),
    ])
    def test_vs_ref_ragged(self, dtype, cfg):
        from repro.kernels.decode_attention import ref
        from repro.kernels.decode_attention.ops import decode_attention

        b, hq, hkv, s, d, bkv, splits = cfg
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        q = _rand(ks[0], (b, hq, d), dtype)
        k = _rand(ks[1], (b, hkv, s, d), dtype)
        v = _rand(ks[2], (b, hkv, s, d), dtype)
        lengths = jax.random.randint(ks[3], (b,), 1, s + 1).astype(jnp.int32)
        got = decode_attention(q, k, v, lengths, bkv=bkv, splits=splits)
        want = ref.decode_attention(q, k, v, lengths)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype],
        )


def _gather(pool, pages, d):
    """gather_pages' clamp-to-page-0 contract, inlined for independence:
    (N, psz, hkv * d) pool -> the dense kernel's (b, hkv, P*psz, d)."""
    N, psz, hd = pool.shape
    b, P = pages.shape
    g = jnp.take(pool, jnp.clip(pages, 0, N - 1), axis=0)
    return jnp.swapaxes(g.reshape(b, P * psz, hd // d, d), 1, 2)


def _paged_case(key, b, hq, hkv, N, psz, P, d, dtype, unmapped_tail=True):
    """Random pool + page tables with aliasing (pages sampled with
    replacement, so slots share physical pages and single tables repeat
    them — the prefix-sharing/COW shapes) + ragged lengths that include
    exact page-boundary hits, with optional unmapped -1 tails."""
    ks = jax.random.split(key, 6)
    q = _rand(ks[0], (b, hq, d), dtype)
    k_pool = _rand(ks[1], (N, psz, hkv * d), dtype)
    v_pool = _rand(ks[2], (N, psz, hkv * d), dtype)
    pages = jax.random.randint(ks[3], (b, P), 0, N).astype(jnp.int32)
    mapped = jax.random.randint(ks[4], (b,), 1, P + 1)
    if unmapped_tail:
        pages = jnp.where(jnp.arange(P)[None, :] < mapped[:, None],
                          pages, -1)
    # Half the slots land exactly on a page boundary, half mid-page.
    lengths = jax.random.randint(ks[5], (b,), 1, mapped * psz + 1)
    lengths = jnp.where(jnp.arange(b) % 2 == 0,
                        jnp.maximum(lengths // psz, 1) * psz, lengths)
    return q, k_pool, v_pool, pages, lengths.astype(jnp.int32)


class TestPagedDecodeAttention:
    """The paged kernel's contract: bit-identical to gather_pages + the
    dense split-KV kernel (same splits, bkv == page_size) — gather's
    clamp-to-page-0-then-mask semantics are the reference."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("cfg", [
        (2, 8, 2, 12, 16, 4, 64, 1), (2, 8, 2, 12, 16, 4, 64, 4),
        (3, 4, 4, 9, 8, 5, 32, 2), (1, 16, 4, 20, 16, 8, 128, 3),
    ])
    def test_bit_identity_vs_gather_path(self, dtype, cfg):
        from repro.kernels.decode_attention import ops, ref

        b, hq, hkv, N, psz, P, d, splits = cfg
        q, kp, vp, pages, lengths = _paged_case(
            jax.random.PRNGKey(7), b, hq, hkv, N, psz, P, d, dtype
        )
        got = ops.paged_decode_attention(q, kp, vp, pages, lengths,
                                         splits=splits)
        kd = _gather(kp, pages, d)
        vd = _gather(vp, pages, d)
        want = ops.decode_attention(q, kd, vd, lengths, bkv=psz,
                                    splits=splits)
        # Bitwise: the paged index-map indirection must change nothing.
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        oracle = ref.decode_attention(q, kd, vd, lengths)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(oracle, np.float32),
            **TOL[dtype],
        )

    def test_aliased_shared_pages(self):
        """Two slots whose tables alias the same physical pages (prefix
        sharing) see identical rows: same q => bit-identical output."""
        from repro.kernels.decode_attention import ops

        psz, d = 8, 32
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q1 = _rand(ks[0], (1, 4, d), jnp.float32)
        q = jnp.concatenate([q1, q1], axis=0)
        kp = _rand(ks[1], (6, psz, 2 * d), jnp.float32)
        vp = _rand(ks[2], (6, psz, 2 * d), jnp.float32)
        pages = jnp.asarray([[2, 5, 2], [2, 5, 2]], jnp.int32)
        lengths = jnp.asarray([20, 20], jnp.int32)
        out = ops.paged_decode_attention(q, kp, vp, pages, lengths, splits=2)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))

    def test_unmapped_tail_contributes_nothing(self):
        """Poisoning every page not reachable below the cursor (including
        the clamp target of -1 entries' positions past lengths) must not
        change a single bit of the output."""
        from repro.kernels.decode_attention import ops

        b, hq, hkv, N, psz, P, d = 2, 4, 2, 8, 8, 4, 32
        q, kp, vp, pages, _ = _paged_case(
            jax.random.PRNGKey(9), b, hq, hkv, N, psz, P, d, jnp.float32,
            unmapped_tail=False,
        )
        pages = jnp.asarray([[3, 1, -1, -1], [6, -1, -1, -1]], jnp.int32)
        lengths = jnp.asarray([2 * psz, psz - 3], jnp.int32)
        clean = ops.paged_decode_attention(q, kp, vp, pages, lengths)
        reachable = jnp.zeros((N,), bool).at[jnp.asarray([3, 1, 6, 0])].set(
            True
        )  # page 0 is the -1 clamp target: read (masked), so keep it clean
        poison = jnp.where(reachable[:, None, None], kp, 1e9)
        vpois = jnp.where(reachable[:, None, None], vp, -1e9)
        dirty = ops.paged_decode_attention(q, poison, vpois, pages, lengths)
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))

    def test_splits_invariance(self):
        """The split-K decomposition is a numerical no-op (combine merges
        partials in fp32): every split count agrees tightly."""
        from repro.kernels.decode_attention import ops

        b, hq, hkv, N, psz, P, d = 2, 8, 2, 12, 16, 6, 64
        q, kp, vp, pages, lengths = _paged_case(
            jax.random.PRNGKey(10), b, hq, hkv, N, psz, P, d, jnp.float32
        )
        outs = [
            np.asarray(ops.paged_decode_attention(
                q, kp, vp, pages, lengths, splits=s
            ))
            for s in (1, 2, 3, P, P + 5)   # over-asking clamps to P pages
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(outs[0], o, rtol=1e-5, atol=1e-5)


class TestDecodeAttentionPlanning:
    """Regression pins for the ops.py wiring bugs: floor-div split
    planning, the ignored ``engine`` argument, and the inner kernel's
    hard-coded interpret=True."""

    def test_plan_splits_counts_padded_grid_blocks(self):
        from repro.kernels.decode_attention.ops import plan_splits

        # s=513, bkv=512: the padded grid runs 2 blocks — floor division
        # said 1 and starved the second block of a split of its own.
        assert plan_splits(513, 512) == 2
        assert plan_splits(512, 512) == 1
        assert plan_splits(4096, 512) == 8
        assert plan_splits(4097, 512, target_parallelism=16) == 9

    def test_engine_plan_drives_splits(self):
        from repro.core import make_engine
        from repro.core.characterize import attention_op
        from repro.kernels.decode_attention.ops import plan_splits

        eng = make_engine()
        plan = eng.plan_op(attention_op(2, 8, 2, 1, 4096, 64, causal=False,
                                        name="decode_attention"))
        want = max(1, min((4096 + plan.block["bkv"] - 1)
                          // plan.block["bkv"], 4096 // 16))
        assert plan_splits(4096, 16, plan=plan) == want

    def test_engine_argument_is_consulted(self):
        import types

        from repro.kernels.decode_attention import ops, ref

        calls = []

        def plan_op(op):
            calls.append(op)
            return types.SimpleNamespace(block={"bq": 1, "bkv": 64})

        fake = types.SimpleNamespace(plan_op=plan_op)
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        q = _rand(ks[0], (2, 8, 64), jnp.float32)
        k = _rand(ks[1], (2, 2, 256, 64), jnp.float32)
        v = _rand(ks[2], (2, 2, 256, 64), jnp.float32)
        got = ops.decode_attention(q, k, v, engine=fake)
        assert len(calls) == 1, "engine plan must be consulted"
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.decode_attention(q, k, v)),
            **TOL[jnp.float32],
        )

    def test_inner_kernels_default_interpret_from_backend(self):
        import inspect

        from repro.kernels.decode_attention.decode_attention import (
            decode_attention, paged_decode_attention,
        )
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention,
        )
        from repro.kernels.fused_norm.fused_norm import fused_norm
        from repro.kernels.matmul.matmul import matmul
        from repro.kernels.moe_gmm.moe_gmm import grouped_matmul
        from repro.kernels.ssd.ssd import ssd

        for fn in (decode_attention, paged_decode_attention, flash_attention,
                   fused_norm, matmul, grouped_matmul, ssd):
            sig = inspect.signature(fn)
            assert sig.parameters["interpret"].default is None, (
                "inner kernels must defer to interpret_mode(), not "
                "hard-code interpret=True (silently interpreted on TPU)"
            )


class TestChipSelection:
    """On a TPU kernels compile (never interpret), and the chip model comes
    from the device kind, with no default for a TPU it does not describe."""

    def test_interpret_mode_follows_backend(self, monkeypatch):
        from repro.kernels import common

        assert common.interpret_mode(None) is True       # this CPU backend
        assert common.interpret_mode(False) is False
        monkeypatch.setattr(common.jax, "default_backend", lambda: "tpu")
        assert common.interpret_mode(None) is False
        with pytest.raises(ValueError, match="not interpreted"):
            common.interpret_mode(True)

    def test_chip_for_device(self):
        import types

        from repro import hw

        def dev(platform, kind):
            return types.SimpleNamespace(platform=platform, device_kind=kind)

        assert hw.chip_for_device(dev("tpu", "TPU v5 lite")) is hw.V5E
        assert hw.chip_for_device(dev("cpu", "cpu")) is hw.V5E
        with pytest.raises(ValueError, match="TPU v9"):
            hw.chip_for_device(dev("tpu", "TPU v9"))


class TestSSD:
    @pytest.mark.parametrize("cfg", [
        (2, 128, 4, 32, 2, 16, 32), (1, 100, 2, 64, 1, 32, 32),
        (2, 64, 8, 32, 8, 16, 16),
    ])
    def test_vs_ref(self, cfg):
        from repro.kernels.ssd import ref
        from repro.kernels.ssd.ops import ssd

        b, l, h, dh, g, ds, chunk = cfg
        ks = jax.random.split(jax.random.PRNGKey(3), 6)
        x = _rand(ks[0], (b, l, h, dh), jnp.float32)
        dt = jax.nn.softplus(_rand(ks[1], (b, l, h), jnp.float32))
        A = -jnp.exp(_rand(ks[2], (h,), jnp.float32))
        B = _rand(ks[3], (b, l, g, ds), jnp.float32)
        C = _rand(ks[4], (b, l, g, ds), jnp.float32)
        D = _rand(ks[5], (h,), jnp.float32)
        y, S = ssd(x, dt, A, B, C, D, chunk=chunk)
        yr, Sr = ref.ssd(x, dt, A, B, C, D)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(S), np.asarray(Sr),
                                   rtol=2e-3, atol=2e-3)

    def test_decode_step_matches_scan(self):
        from repro.kernels.ssd import ref
        from repro.kernels.ssd.ssd import ssd_decode_step

        b, h, dh, g, ds = 2, 4, 32, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(4), 6)
        x = _rand(ks[0], (b, 1, h, dh), jnp.float32)
        dt = jax.nn.softplus(_rand(ks[1], (b, 1, h), jnp.float32))
        A = -jnp.exp(_rand(ks[2], (h,), jnp.float32))
        B = _rand(ks[3], (b, 1, g, ds), jnp.float32)
        C = _rand(ks[4], (b, 1, g, ds), jnp.float32)
        S0 = _rand(ks[5], (b, h, ds, dh), jnp.float32)
        yr, Sr = ref.ssd(x, dt, A, B, C, None, init_state=S0)
        yd, Sd = ssd_decode_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0],
                                 None, S0)
        np.testing.assert_allclose(np.asarray(yd), np.asarray(yr[:, 0]),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(Sd), np.asarray(Sr),
                                   rtol=2e-3, atol=2e-3)


class TestMoEGmm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("ecKn", [(4, 256, 128, 256), (8, 100, 200, 130)])
    def test_vs_ref(self, dtype, ecKn):
        from repro.kernels.moe_gmm import ref
        from repro.kernels.moe_gmm.ops import grouped_matmul

        e, c, k, n = ecKn
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        x = _rand(ks[0], (e, c, k), dtype)
        w = _rand(ks[1], (e, k, n), dtype)
        counts = jax.random.randint(ks[2], (e,), 0, c + 1).astype(jnp.int32)
        got = grouped_matmul(x, w, counts, bm=64, bn=64, bk=64)
        want = ref.grouped_matmul(x, w, counts)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype],
        )

    def test_empty_experts_write_zero(self):
        from repro.kernels.moe_gmm.ops import grouped_matmul

        x = jnp.ones((2, 64, 64), jnp.float32)
        w = jnp.ones((2, 64, 64), jnp.float32)
        counts = jnp.array([0, 64], jnp.int32)
        y = grouped_matmul(x, w, counts, bm=64, bn=64, bk=64)
        assert float(jnp.abs(y[0]).max()) == 0.0
        assert float(jnp.abs(y[1]).min()) > 0.0


class TestFusedNorm:
    @pytest.mark.parametrize("kind", ["rms", "layer"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(4, 100, 512), (300, 256)])
    def test_vs_ref(self, kind, dtype, shape):
        from repro.kernels.fused_norm import ref
        from repro.kernels.fused_norm.ops import fused_norm

        ks = jax.random.split(jax.random.PRNGKey(6), 4)
        x = _rand(ks[0], shape, dtype)
        w = _rand(ks[1], (shape[-1],), jnp.float32)
        b = _rand(ks[2], (shape[-1],), jnp.float32) if kind == "layer" else None
        r = _rand(ks[3], shape, dtype)
        got = fused_norm(x, w, b, r, kind=kind)
        want = ref.fused_norm(x, w, b, r, kind=kind)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype],
        )
