"""Distribution-layer tests.  shard_map/pjit behaviours need >1 device, so
they run in a subprocess with 8 forced host devices (keeping this process,
and every other test, on 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SNIPPET_HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
"""


def _run(snippet: str, timeout=420):
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", _SNIPPET_HEADER + textwrap.dedent(snippet)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_sp_decode_matches_reference():
    _run("""
    from repro.distributed.sp_decode import sp_decode_attention, reference
    mesh = make_mesh((8,), ("data",))
    b, hq, hkv, S, d = 2, 8, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, S, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, S, d), jnp.float32)
    lengths = jnp.array([500, 300], jnp.int32)
    got = sp_decode_attention(q, k, v, lengths, mesh, axis="data")
    want = reference(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    print("sp_decode ok")
    """)


def test_bucketed_and_compressed_all_reduce():
    _run("""
    from repro.distributed.collectives import (bucketed_all_reduce,
                                               compressed_all_reduce)
    mesh = make_mesh((8,), ("d",))
    gs = [jax.random.normal(jax.random.PRNGKey(i), (8, 13 + i), jnp.float32)
          for i in range(5)]

    def f(*gs):
        outs = bucketed_all_reduce(list(gs), "d", bucket_bytes=256)
        return tuple(outs)

    outs = jax.shard_map(f, mesh=mesh,
                     in_specs=tuple(P("d") for _ in gs),
                     out_specs=tuple(P("d") for _ in gs))(*gs)
    for g, o in zip(gs, outs):
        want = jnp.broadcast_to(g.reshape(8, 1, -1).sum(0, keepdims=True),
                                (8, 1, g.shape[1])).reshape(8, -1)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    print("bucketed ok")

    g = jax.random.normal(jax.random.PRNGKey(9), (8, 64), jnp.float32)
    err0 = jnp.zeros_like(g)

    def c(g, e):
        return compressed_all_reduce(g, e, "d")

    red, err = jax.shard_map(c, mesh=mesh, in_specs=(P("d"), P("d")),
                         out_specs=(P("d"), P("d")))(g, err0)
    want = jnp.mean(g, axis=0)
    got = np.asarray(red[0])
    rel = np.abs(got - np.asarray(want)).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.05, rel          # int8 quantization error bound
    assert float(jnp.abs(err).max()) > 0   # error feedback carries residual
    print("compressed ok, rel", rel)
    """)


def test_sharded_train_step_matches_single_device():
    _run("""
    from repro.models import get_config
    from repro.train.step import TrainConfig, init_train_state, make_train_step
    from repro.train import optimizer as opt
    from repro.distributed import sharding as sh
    from repro.data.pipeline import SyntheticLM

    cfg = get_config("yi-9b", smoke=True)
    tcfg = TrainConfig(adamw=opt.AdamWConfig(lr=1e-3, warmup_steps=0,
                                             total_steps=10),
                       batch_axes=("data",))
    train_step, model = make_train_step(cfg, tcfg)
    state = init_train_state(model, jax.random.PRNGKey(0))
    batch = SyntheticLM(cfg, batch=8, seq=16, seed=0)(0)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    # single device reference
    ref_state, ref_metrics = jax.jit(train_step)(state, batch)

    mesh = make_mesh((4, 2), ("data", "model"))
    pshard = sh.params_shardings(state["params"], cfg, mesh)
    oshard = opt.opt_shardings(pshard, state["params"], mesh, zero1=True)
    sshard = {"params": pshard, "opt": oshard}
    bspec = sh.batch_spec(cfg, mesh, 8)
    bshard = {k: NamedSharding(mesh, bspec[k]) for k in batch}
    state2 = init_train_state(model, jax.random.PRNGKey(0))
    with jax.set_mesh(mesh):
        state2 = jax.device_put(state2, sshard)
        batch2 = jax.device_put(batch, bshard)
        new_state, metrics = jax.jit(
            train_step, in_shardings=(sshard, bshard),
            out_shardings=(sshard, None),
        )(state2, batch2)
    np.testing.assert_allclose(float(metrics["loss"]),
                               float(ref_metrics["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(ref_state["params"]),
                    jax.tree_util.tree_leaves(new_state["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=5e-3)
    print("sharded == single device")
    """)


def test_moe_ep_sharded_forward_matches():
    _run("""
    from repro.models import build_model, get_config
    from repro.distributed import sharding as sh

    cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    want, _ = model.forward(params, toks)

    mesh = make_mesh((2, 4), ("data", "model"))
    pshard = sh.params_shardings(params, cfg, mesh)
    with jax.set_mesh(mesh):
        params2 = jax.device_put(params, pshard)
        toks2 = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
        got, _ = jax.jit(model.forward)(params2, toks2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-3, atol=3e-3)
    print("moe ep ok")
    """)
