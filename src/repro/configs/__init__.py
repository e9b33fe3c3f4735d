"""Architecture registry: ``--arch <id>`` resolves here.

Each module exposes FULL (the published config, exercised only via the
AOT dry-run) and SMOKE (a reduced same-family config that trains a real
step on CPU in the tests). `nemotron3_nano_30b` is not registered: no
dry-run covers it, and its one-chip cut runs in the chip benchmark.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.common.config import ModelConfig, SHAPES, ShapeConfig

_MODULES = {
    "mamba2-1.3b": "mamba2_1p3b",
    "phi3-mini-3.8b": "phi3_mini_3p8b",
    "glm4-9b": "glm4_9b",
    "command-r-35b": "command_r_35b",
    "qwen1.5-110b": "qwen1p5_110b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "llama-3.2-vision-90b": "llama32_vision_90b",
    "granite-moe-3b-a800m": "granite_moe_3b",
    "dbrx-132b": "dbrx_132b",
    "musicgen-medium": "musicgen_medium",
}

ARCH_IDS: List[str] = list(_MODULES)


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.SMOKE if smoke else mod.FULL


def applicable_shapes(arch: str) -> List[str]:
    """The assigned shape set, minus rule-based skips (DESIGN.md par.4):
    long_500k only for sub-quadratic (SSM / hybrid) architectures."""
    cfg = get_config(arch)
    out = []
    for name, sh in SHAPES.items():
        if name == "long_500k" and not cfg.is_subquadratic:
            continue
        out.append(name)
    return out


def all_cells():
    """Every (arch, shape) dry-run cell after rule-based skips."""
    return [(a, s) for a in ARCH_IDS for s in applicable_shapes(a)]


def skipped_cells():
    return [(a, s) for a in ARCH_IDS for s in SHAPES
            if s not in applicable_shapes(a)]
