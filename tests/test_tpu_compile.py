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

# (hq, hkv, head_dim, slots, pool pages): minicpm-2b's multi-head attention
# and yi-9b's GQA, at the benchmark's deployments (bench/configs).
WIDTHS = {"minicpm-2b": (36, 36, 64, 8, 496), "yi-9b": (32, 4, 128, 24, 2400)}
PAGE, PAGES_PER_SLOT = 16, 128


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
    hq, hkv, d, slots, n = WIDTHS[arch]

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    ppb, splits = ops.paged_plan(PAGE * hkv * d * 2, PAGES_PER_SLOT)
    hlo = _hlo(
        lambda q, k, v, pg, ln: paged_decode_attention(
            q, k, v, pg, ln, splits=splits, pages_per_block=ppb,
            interpret=False),
        s((slots, hq, d)), s((n, PAGE, hkv * d)), s((n, PAGE, hkv * d)),
        s((slots, PAGES_PER_SLOT), jnp.int32), s((slots,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_decode_attention_compiles_for_v5e(arch, one_chip):
    hq, hkv, d, slots, _ = WIDTHS[arch]
    t = PAGES_PER_SLOT * PAGE

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    # The paged kernel's compute block: the gather path it is identical to.
    ppb, splits = ops.paged_plan(PAGE * hkv * d * 2, PAGES_PER_SLOT)
    hlo = _hlo(
        lambda q, k, v, ln: decode_attention(
            q, k, v, ln, bkv=ppb * PAGE, splits=splits, interpret=False),
        s((slots, hq, d)), s((slots, hkv, t, d)), s((slots, hkv, t, d)),
        s((slots,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


def test_window_ring_decode_compiles_for_v5e(one_chip):
    """Mellum2's sliding layers at the agent cell's deployment: the paged
    kernel over the stacked window rings (21 layers, 16 slots x 1024
    positions as 64 pages each), read in place through a layer index."""
    hq, hkv, d, slots, layers, per_slot = 32, 4, 128, 16, 21, 1024 // PAGE

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    ppb, splits = ops.paged_plan(PAGE * hkv * d * 2, per_slot)
    ring = (layers, slots * per_slot, PAGE, hkv * d)
    hlo = _hlo(
        lambda q, k, v, pg, ln, layer: paged_decode_attention(
            q, k, v, pg, ln, layer, splits=splits, pages_per_block=ppb,
            interpret=False),
        s((slots, hq, d)), s(ring), s(ring),
        s((slots, per_slot), jnp.int32), s((slots,), jnp.int32),
        s((), jnp.int32),
    )
    assert "tpu_custom_call" in hlo
