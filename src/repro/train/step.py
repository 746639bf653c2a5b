"""Train-step builder: loss + grad + AdamW update under pjit.

The activation (remat) policy is assigned by the cache-policy engine — the
paper's technique applied at the trainer level.  Gradients are reduced in a
configurable dtype (bf16 reduction halves collective bytes — a §Perf knob)
and flushed through the rinse scheduler's bucket order when microbatched.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.remat import RematPolicy
from repro.models import build_model
from repro.models import common as model_common
from repro.train import optimizer as opt


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    adamw: opt.AdamWConfig = dataclasses.field(default_factory=opt.AdamWConfig)
    remat: RematPolicy = RematPolicy.SAVE_DOTS
    microbatch: int = 1               # grad-accumulation splits per step
    grad_reduce_dtype: str = "float32"  # "bfloat16" halves collective bytes
    zero1: bool = False
    batch_axes: tuple = ("data",)     # mesh axes the batch dim shards over


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig):
    """Returns train_step(state, batch) -> (state, metrics)."""
    model = build_model(cfg)

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch, remat=tcfg.remat)
        return loss, metrics

    def single_grad(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        return loss, metrics, grads

    def train_step(state, batch):
        params = state["params"]
        if tcfg.microbatch > 1:
            mb = tcfg.microbatch

            def split(x):
                b = x.shape[0]
                x = x.reshape(mb, b // mb, *x.shape[1:])
                if tcfg.batch_axes:
                    # Keep the per-microbatch batch dim sharded over data —
                    # without this GSPMD may shard the microbatch dim
                    # instead and replicate every activation.
                    from jax.sharding import PartitionSpec as P

                    x = jax.lax.with_sharding_constraint(
                        x, P(None, tcfg.batch_axes)
                    )
                return x

            batches = jax.tree_util.tree_map(split, batch)

            def acc_body(carry, mbatch):
                loss_acc, grads_acc = carry
                loss, _, grads = single_grad(params, mbatch)
                grads = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype), grads_acc, grads
                )
                return (loss_acc + loss, grads), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.dtype(tcfg.grad_reduce_dtype)),
                params,
            )
            (loss, grads), _ = model_common.scan(
                acc_body, (jnp.zeros((), jnp.float32), zeros), batches
            )
            loss = loss / mb
            grads = jax.tree_util.tree_map(lambda g: g / mb, grads)
            metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}
        else:
            loss, metrics, grads = single_grad(params, batch)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.dtype(tcfg.grad_reduce_dtype)), grads
            )

        new_params, new_opt, stats = opt.apply_updates(
            params, grads, state["opt"], tcfg.adamw
        )
        metrics = {"loss": loss, **metrics, **stats}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step, model


def init_train_state(model, key) -> dict[str, Any]:
    params = model.init(key)
    return {"params": params, "opt": opt.init_state(params)}


def state_shardings(cfg: ModelConfig, mesh, params_shape, zero1: bool = False):
    from repro.distributed import sharding as sh

    pshard = sh.params_shardings(params_shape["params"], cfg, mesh)
    oshard = opt.opt_shardings(pshard, params_shape["params"], mesh, zero1=zero1)
    return {"params": pshard, "opt": oshard}


@functools.cache
def eval_shape_state(arch: str, smoke: bool = False):
    """Shape-only train state (no allocation) for sharding/dry-run."""
    from repro.models import get_config

    cfg = get_config(arch, smoke=smoke)
    model = build_model(cfg)

    def make():
        return init_train_state(model, jax.random.PRNGKey(0))

    return cfg, model, jax.eval_shape(make)
