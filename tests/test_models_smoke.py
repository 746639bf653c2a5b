"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes + finite values; prefill+decode == forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ARCHS, build_model, get_config


def _batch_for(cfg, b=2, s=16, key=jax.random.PRNGKey(0)):
    ks = jax.random.split(key, 3)
    toks = jax.random.randint(ks[0], (b, s), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "vlm":
        batch["vis"] = jax.random.normal(
            ks[1], (b, cfg.n_vis_tokens, cfg.d_model), jnp.float32
        )
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            ks[2], (b, cfg.enc_seq, cfg.d_model), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 16
    batch = _batch_for(cfg, b, s)

    loss, metrics = model.loss(params, batch)
    assert jnp.isfinite(loss), (arch, loss)

    kwargs = {}
    if cfg.family == "vlm":
        kwargs["vis"] = batch["vis"]
    if cfg.family == "encdec":
        kwargs["frames"] = batch["frames"]
    logits, aux = model.forward(params, batch["tokens"], **kwargs)
    assert logits.shape == (b, s, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    # one SGD-ish step: grads exist and are finite
    def scalar_loss(p):
        return model.loss(p, batch)[0]

    grads = jax.grad(scalar_loss)(params)
    gnorm = sum(
        float(jnp.sum(jnp.square(g.astype(jnp.float32))))
        for g in jax.tree_util.tree_leaves(grads)
    )
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode_consistency(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, s = 2, 12
    batch = _batch_for(cfg, b, s, key=jax.random.PRNGKey(7))
    kwargs = {}
    if cfg.family == "vlm":
        kwargs["vis"] = batch["vis"]
    if cfg.family == "encdec":
        kwargs["frames"] = batch["frames"]

    cache = model.init_cache(
        params, batch=b, max_len=s + 4,
        **({"vis": batch.get("vis")} if cfg.family == "vlm" else {}),
        **({"frames": batch.get("frames")} if cfg.family == "encdec" else {}),
    )
    _, cache = model.prefill(params, cache, batch["tokens"][:, : s - 1])
    step_logits, cache = model.decode_step(
        params, cache, batch["tokens"][:, s - 1: s]
    )
    full_logits, _ = model.forward(params, batch["tokens"], **kwargs)
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0], np.float32),
        np.asarray(full_logits[:, -1], np.float32),
        rtol=3e-2, atol=3e-2,
    )


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_param_counts_sane(arch):
    cfg = get_config(arch)
    n = cfg.param_count()
    assert 1e8 < n < 2e11, (arch, n)
    assert cfg.padded_vocab % 16 == 0
    if cfg.family == "moe":
        assert cfg.active_param_count() < n


def test_moe_sorted_dispatch_matches_dense():
    """Capacity-based sorted dispatch == dense one-hot dispatch when no
    tokens overflow capacity (A4 beyond-paper optimization)."""
    import dataclasses as dc

    from repro.configs.base import ModelConfig
    from repro.models import common as cm

    cfg_d = ModelConfig(
        arch="t", family="moe", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=4, d_ff=48, vocab=64, vocab_pad_multiple=64, n_experts=8,
        experts_per_token=2, capacity_factor=8.0, dtype="float32",
    )
    cfg_s = dc.replace(cfg_d, moe_dispatch="sorted")
    p = cm.moe_init(jax.random.PRNGKey(0), cfg_d)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    yd, auxd, _ = cm.apply_moe(p, x, cfg_d)
    ys, auxs, _ = cm.apply_moe(p, x, cfg_s)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(ys),
                               rtol=2e-4, atol=2e-4)
    gd = jax.grad(lambda q: cm.apply_moe(q, x, cfg_d)[0].sum())(p)
    gs = jax.grad(lambda q: cm.apply_moe(q, x, cfg_s)[0].sum())(p)
    for a, b in zip(jax.tree_util.tree_leaves(gd),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)
    # tight capacity drops tokens but stays finite
    yt = cm.apply_moe(p, x, dc.replace(cfg_s, capacity_factor=0.5))[0]
    assert bool(jnp.isfinite(yt).all())
