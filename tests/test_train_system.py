"""Training-substrate system tests: loop, checkpointing, fault tolerance,
optimizer, data determinism."""
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.remat import RematPolicy
from repro.data.pipeline import MemmapLM, Prefetcher, SyntheticLM
from repro.models import build_model, get_config
from repro.train import checkpoint as ckpt
from repro.train import loop as train_loop
from repro.train import optimizer as opt
from repro.train.step import TrainConfig, init_train_state, make_train_step


@pytest.fixture()
def tiny():
    cfg = get_config("yi-9b", smoke=True)
    tcfg = TrainConfig(
        adamw=opt.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50),
        remat=RematPolicy.SAVE_DOTS,
    )
    train_step, model = make_train_step(cfg, tcfg)
    train_step = jax.jit(train_step, donate_argnums=(0,))
    state = init_train_state(model, jax.random.PRNGKey(0))
    data = SyntheticLM(cfg, batch=4, seq=16, seed=7)
    return cfg, train_step, state, data


def test_loss_decreases(tiny):
    cfg, train_step, state, data = tiny
    losses = []
    for step in range(12):
        state, metrics = train_step(state, data(step % 2))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_microbatch_equivalence():
    """mb=4 grad accumulation == mb=1 on the same batch (same update)."""
    cfg = get_config("yi-9b", smoke=True)
    data = SyntheticLM(cfg, batch=4, seq=16, seed=3)
    batch = data(0)
    outs = []
    for mb in (1, 4):
        tcfg = TrainConfig(
            adamw=opt.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10),
            microbatch=mb, batch_axes=(),
        )
        train_step, model = make_train_step(cfg, tcfg)
        state = init_train_state(model, jax.random.PRNGKey(0))
        new_state, _ = jax.jit(train_step)(state, batch)
        outs.append(new_state["params"])
    flat1 = jax.tree_util.tree_leaves(outs[0])
    flat4 = jax.tree_util.tree_leaves(outs[1])
    for a, b in zip(flat1, flat4):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-3, atol=2e-3,
        )


def test_schedules():
    c = opt.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                        schedule="wsd", decay_frac=0.2, min_lr_frac=0.1)
    lr5 = float(opt.schedule_lr(c, jnp.asarray(5)))
    lr50 = float(opt.schedule_lr(c, jnp.asarray(50)))
    lr99 = float(opt.schedule_lr(c, jnp.asarray(99)))
    assert lr5 == pytest.approx(0.5, rel=1e-3)      # warmup
    assert lr50 == pytest.approx(1.0, rel=1e-3)     # stable
    assert 0.09 < lr99 < 0.25                       # decaying
    c2 = opt.AdamWConfig(lr=1.0, warmup_steps=0, total_steps=100,
                         schedule="cosine", min_lr_frac=0.1)
    assert float(opt.schedule_lr(c2, jnp.asarray(100))) == pytest.approx(
        0.1, rel=1e-2
    )


def test_checkpoint_roundtrip_and_atomicity(tmp_path, tiny):
    cfg, train_step, state, data = tiny
    d = str(tmp_path / "ck")
    ckpt.save(state, d, step=10)
    # a stale tmp dir (simulated crash) must be ignored
    os.makedirs(os.path.join(d, "step_00000020.tmp"))
    assert ckpt.latest_step(d) == 10
    restored, step = ckpt.restore(d, template=state)
    assert step == 10
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention(tmp_path, tiny):
    _, _, state, _ = tiny
    d = str(tmp_path / "ck")
    for s in (1, 2, 3, 4):
        ckpt.save(state, d, step=s, keep=2)
    assert ckpt.latest_step(d) == 4
    names = sorted(os.listdir(d))
    assert names == ["step_00000003", "step_00000004"]


def test_loop_resume_determinism(tmp_path):
    """Train 6 steps straight vs 3 + crash + resume 3: identical params."""
    cfg = get_config("yi-9b", smoke=True)
    tcfg = TrainConfig(adamw=opt.AdamWConfig(lr=1e-3, warmup_steps=0,
                                             total_steps=100))
    train_step, model = make_train_step(cfg, tcfg)
    train_step = jax.jit(train_step)
    data = SyntheticLM(cfg, batch=2, seq=16, seed=11)

    def fresh():
        return init_train_state(model, jax.random.PRNGKey(0))

    straight, _ = train_loop.run(
        train_step, fresh(), data,
        train_loop.LoopConfig(total_steps=6, ckpt_every=100,
                              ckpt_dir=str(tmp_path / "a"),
                              handle_signals=False),
    )
    # interrupted run: 3 steps, checkpoint, then resume to 6
    st1, rep1 = train_loop.run(
        train_step, fresh(), data,
        train_loop.LoopConfig(total_steps=3, ckpt_every=3,
                              ckpt_dir=str(tmp_path / "b"),
                              handle_signals=False),
    )
    st2, rep2 = train_loop.run(
        train_step, fresh(), data,
        train_loop.LoopConfig(total_steps=6, ckpt_every=3,
                              ckpt_dir=str(tmp_path / "b"),
                              handle_signals=False),
    )
    assert rep2.resumed_from == 3
    for a, b in zip(jax.tree_util.tree_leaves(straight["params"]),
                    jax.tree_util.tree_leaves(st2["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


def test_loop_preemption_writes_final_checkpoint(tmp_path):
    cfg = get_config("yi-9b", smoke=True)
    tcfg = TrainConfig(adamw=opt.AdamWConfig(warmup_steps=0, total_steps=100))
    train_step, model = make_train_step(cfg, tcfg)
    train_step = jax.jit(train_step)
    state = init_train_state(model, jax.random.PRNGKey(0))
    data = SyntheticLM(cfg, batch=2, seq=16, seed=1)

    fired = {"done": False}

    def on_step(step, metrics):
        if step == 2 and not fired["done"]:
            fired["done"] = True
            os.kill(os.getpid(), signal.SIGTERM)

    _, report = train_loop.run(
        train_step, state, data,
        train_loop.LoopConfig(total_steps=50, ckpt_every=100,
                              ckpt_dir=str(tmp_path / "c")),
        on_step=on_step,
    )
    assert report.preempted
    assert ckpt.latest_step(str(tmp_path / "c")) == report.final_step


def test_nan_fuse(tmp_path):
    def bad_step(state, batch):
        return state, {"loss": jnp.float32(jnp.nan)}

    with pytest.raises(FloatingPointError):
        train_loop.run(
            bad_step, {}, lambda s: {},
            train_loop.LoopConfig(total_steps=3, ckpt_dir=str(tmp_path / "n"),
                                  handle_signals=False),
        )


def test_straggler_watchdog(tmp_path):
    import time

    calls = []

    def slow_step(state, batch):
        if len(calls) == 3:
            time.sleep(0.25)
        return state, {"loss": jnp.float32(1.0)}

    def on_straggler(step, ratio):
        calls.append((step, ratio))

    state, report = train_loop.run(
        slow_step, {}, lambda s: calls.append("d") or {},
        train_loop.LoopConfig(total_steps=6, ckpt_every=100,
                              ckpt_dir=str(tmp_path / "ck"),
                              straggler_factor=3.0, handle_signals=False),
        on_straggler=on_straggler,
    )
    del state
    assert report.straggler_steps, report.step_times
    ratios = [c[1] for c in calls if isinstance(c, tuple)]
    assert ratios and ratios[0] > 3.0  # flagged ratio


def test_data_determinism_and_memmap(tmp_path):
    cfg = get_config("yi-9b", smoke=True)
    a = SyntheticLM(cfg, 4, 16, seed=5)(3)
    b = SyntheticLM(cfg, 4, 16, seed=5)(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticLM(cfg, 4, 16, seed=6)(3)
    assert not np.array_equal(a["tokens"], c["tokens"])

    path = str(tmp_path / "corpus.bin")
    np.arange(10000, dtype=np.uint32).tofile(path)
    mm = MemmapLM(path, cfg, batch=2, seq=16, seed=0)
    b0, b1 = mm(0), mm(0)
    np.testing.assert_array_equal(b0["tokens"], b1["tokens"])
    assert (b0["labels"][:, :-1] == b0["tokens"][:, 1:]).all()


def test_prefetcher_orders_steps():
    cfg = get_config("yi-9b", smoke=True)
    src = SyntheticLM(cfg, 2, 8, seed=0)
    pf = Prefetcher(src, start_step=5, depth=2)
    it = iter(pf)
    steps = [next(it)[0] for _ in range(4)]
    pf.stop()
    assert steps == [5, 6, 7, 8]


def test_elastic_restore_across_shardings(tmp_path):
    """Checkpoint on one topology, restore onto a 2-device mesh layout
    (host resharding path)."""
    cfg = get_config("yi-9b", smoke=True)
    model = build_model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0))
    d = str(tmp_path / "el")
    ckpt.save(state, d, step=5)
    # restore with default placement (single device here) but through the
    # resharding code path
    restored, step = ckpt.restore(d, template=state, shardings=None)
    assert step == 5
    n1 = jax.tree_util.tree_leaves(state)
    n2 = jax.tree_util.tree_leaves(restored)
    for a, b in zip(n1, n2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
