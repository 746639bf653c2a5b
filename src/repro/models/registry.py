"""Architecture registry: ``--arch <id>`` -> (config, model builder).

``<arch>-ep<n>`` names one chip's share of ``<arch>`` under n-way expert
parallelism: the router keeps every expert, this chip holds the first n-th
of them (shard 0 of n), everything else is whole."""
from __future__ import annotations

import dataclasses
import importlib
import re

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig  # noqa: F401
from repro.models import common as cm

_CONFIG_MODULES = {
    "yi-9b": "repro.configs.yi_9b",
    "granite-20b": "repro.configs.granite_20b",
    "minicpm-2b": "repro.configs.minicpm_2b",
    "qwen2.5-32b": "repro.configs.qwen25_32b",
    "whisper-small": "repro.configs.whisper_small",
    "phi3.5-moe-42b-a6.6b": "repro.configs.phi35_moe",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "mamba2-1.3b": "repro.configs.mamba2_13b",
    "zamba2-2.7b": "repro.configs.zamba2_27b",
    "llama-3.2-vision-90b": "repro.configs.llama32_vision_90b",
    "mellum2-12b": "repro.configs.mellum2_12b",
}

ARCHS = tuple(_CONFIG_MODULES)


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    share = re.fullmatch(r"(.+)-ep(\d+)", arch)
    if share is not None and share.group(1) in _CONFIG_MODULES:
        cfg = get_config(share.group(1), smoke)
        shards = int(share.group(2))
        if cfg.family != "moe" or cfg.n_experts % shards:
            raise ValueError(f"{arch}: {cfg.n_experts} experts do not split "
                             f"{shards} ways")
        return dataclasses.replace(cfg, expert_shards=shards, expert_shard=0)
    mod = importlib.import_module(_CONFIG_MODULES[arch])
    return mod.SMOKE if smoke else mod.CONFIG


def build_model(cfg: ModelConfig) -> cm.ModelApply:
    if cfg.family in ("dense", "moe", "vlm"):
        from repro.models import transformer

        return transformer.build(cfg)
    if cfg.family == "ssm":
        from repro.models import mamba2

        return mamba2.build(cfg)
    if cfg.family == "hybrid":
        from repro.models import zamba2

        return zamba2.build(cfg)
    if cfg.family == "encdec":
        from repro.models import whisper

        return whisper.build(cfg)
    raise ValueError(f"unknown family: {cfg.family}")


def runnable_cells() -> list[tuple[str, str]]:
    """All (arch, shape) dry-run cells after the principled skips:
    long_500k only for sub-quadratic archs (DESIGN.md §5)."""
    cells = []
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape_name, shape in SHAPES.items():
            if shape_name == "long_500k" and not cfg.subquadratic:
                continue
            cells.append((arch, shape_name))
    return cells
