"""Compile the decode-attention kernels for a described TPU v5e, here
without one: what Mosaic refuses (block tiling, scalar operands, scoped
VMEM) fails these tests instead of a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, so every pytest
worker must collect the same tests and only the worker running this file
may load it.  Nothing is executed — shapes only.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import ops
from repro.kernels.decode_attention.decode_attention import (
    decode_attention, paged_decode_attention,
)

# (hq, hkv, head_dim): minicpm-2b's multi-head attention and yi-9b's GQA.
WIDTHS = {"minicpm-2b": (36, 36, 64), "yi-9b": (32, 4, 128)}
SLOTS, PAGE, PAGES_PER_SLOT = 8, 16, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_attention_compiles_for_v5e(arch, one_chip):
    hq, hkv, d = WIDTHS[arch]
    n = SLOTS * PAGES_PER_SLOT

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    splits = ops.plan_splits(PAGES_PER_SLOT * PAGE, PAGE)
    hlo = _hlo(
        lambda q, k, v, pg, ln: paged_decode_attention(
            q, k, v, pg, ln, splits=splits, interpret=False),
        s((SLOTS, hq, d)), s((n, PAGE, hkv * d)), s((n, PAGE, hkv * d)),
        s((SLOTS, PAGES_PER_SLOT), jnp.int32), s((SLOTS,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_decode_attention_compiles_for_v5e(arch, one_chip):
    hq, hkv, d = WIDTHS[arch]
    t = PAGES_PER_SLOT * PAGE

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    # bkv == page size: the gather path the paged kernel is identical to.
    splits = ops.plan_splits(t, PAGE)
    hlo = _hlo(
        lambda q, k, v, ln: decode_attention(
            q, k, v, ln, bkv=PAGE, splits=splits, interpret=False),
        s((SLOTS, hq, d)), s((SLOTS, hkv, t, d)), s((SLOTS, hkv, t, d)),
        s((SLOTS,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo
