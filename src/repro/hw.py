"""Hardware model constants for the target platform (TPU v5e) and roofline math.

These constants describe the chip the framework runs on and drive the
analytical cost model, the VMEM allocator, the kernels' scoped-VMEM limit and
the roofline analysis of the dry-run artifacts.  ``chip_for_device`` maps a
JAX device to its entry; a TPU that no entry describes is an error.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Chip:
    """One TPU chip (v5e by default)."""

    name: str = "tpu-v5e"
    # Compute
    peak_flops_bf16: float = 197e12     # FLOP/s
    peak_flops_fp32: float = 197e12 / 4  # no fp32 MXU path; conservative
    mxu_dim: int = 128                  # systolic array is 128x128
    vpu_lanes: int = 8 * 128            # (8, 128) vector registers
    tensorcores: int = 1                # cores a "parallel" grid axis spans
    # Memory
    hbm_bytes: int = 16 * 1024**3       # 16 GB
    hbm_bw: float = 819e9               # B/s
    vmem_bytes: int = 128 * 1024 * 1024  # 128 MB software-managed scratchpad
    # Interconnect
    ici_bw_per_link: float = 50e9       # B/s per ICI link (per direction)
    ici_links: int = 4                  # 2D torus on v5e: 4 links/chip
    # DMA / burst granularity used by the rinse (write-contiguity) model.
    hbm_burst_bytes: int = 512
    # Fraction of VMEM the planner may claim (leave headroom for compiler
    # temporaries / semaphores / double-buffer bookkeeping).
    vmem_budget_frac: float = 0.75

    @property
    def vmem_budget(self) -> int:
        return int(self.vmem_bytes * self.vmem_budget_frac)

    @property
    def ridge_intensity_bf16(self) -> float:
        """FLOP/byte at which compute and HBM time balance."""
        return self.peak_flops_bf16 / self.hbm_bw


V5E = Chip()

# JAX ``device_kind`` -> chip model.  Only chips described here may run the
# kernels or be planned for; CPU processes (tests in interpret mode, and
# compile-only rehearsals against a described v5e topology) plan for the
# v5e, the chip this framework targets.
CHIPS_BY_KIND = {"TPU v5 lite": V5E}


def chip_for_device(device) -> Chip:
    """The :class:`Chip` entry for a JAX device (``jax.devices()[0]``)."""
    if device.platform == "cpu":
        return V5E
    try:
        return CHIPS_BY_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no hw.Chip entry describes {device.platform} device kind "
            f"{device.device_kind!r}; add it to hw.CHIPS_BY_KIND"
        ) from None

# Calibrated model of the paper's simulated system (Table 1): 64-CU GCN3 APU,
# ~12.3 TFLOP/s fp32, HBM2 @ 512 GB/s, 4 MB GPU L2 (the "cache capacity" that
# plays VMEM's role in the reproduction benches), 2 KB DRAM rows.
PAPER_GPU = Chip(
    name="gem5-apu",
    peak_flops_bf16=12.3e12,   # single-rate fp32 machine; bf16 field = fp32 rate
    peak_flops_fp32=12.3e12,
    mxu_dim=64,                # wavefront/LDS tile granularity
    vpu_lanes=64,
    hbm_bytes=16 * 1024**3,
    hbm_bw=512e9,
    vmem_bytes=4 * 1024 * 1024,  # GPU L2 as the residency capacity
    ici_bw_per_link=0.0,
    ici_links=1,
    hbm_burst_bytes=2048,      # DRAM row-buffer granule
    vmem_budget_frac=0.9,
)

# Default pod geometry for this project (see launch/mesh.py).
PODS = 2
CHIPS_PER_POD = 256          # 16 x 16
POD_MESH = (16, 16)          # (data, model)
MULTIPOD_MESH = (2, 16, 16)  # (pod, data, model)

DTYPE_BYTES = {
    "float32": 4, "f32": 4,
    "bfloat16": 2, "bf16": 2,
    "float16": 2, "f16": 2,
    "float64": 8, "f64": 8,
    "int8": 1, "s8": 1, "u8": 1,
    "int32": 4, "s32": 4, "u32": 4,
    "int64": 8, "s64": 8, "u64": 8,
    "bool": 1, "pred": 1,
}


def dtype_bytes(dtype) -> int:
    """Bytes per element for a numpy/jax dtype or short HLO name."""
    s = str(dtype)
    if s in DTYPE_BYTES:
        return DTYPE_BYTES[s]
    import numpy as np

    return np.dtype(dtype).itemsize


def flops_time(flops: float, chip: Chip = V5E, dtype: str = "bf16") -> float:
    peak = chip.peak_flops_bf16 if dtype_bytes(dtype) <= 2 else chip.peak_flops_fp32
    return flops / peak


def hbm_time(num_bytes: float, chip: Chip = V5E) -> float:
    return num_bytes / chip.hbm_bw


def ici_time(num_bytes: float, chip: Chip = V5E, links: int | None = None) -> float:
    links = chip.ici_links if links is None else links
    return num_bytes / (chip.ici_bw_per_link * links)
