"""Architecture registry: 10 assigned archs, the paper's §5.1 eval model
and the benchmark's Moonlight-16B-A3B (published and one chip's share).

Every module exposes ``CONFIG`` (the exact published config) and ``tiny()``
(a reduced same-family config for CPU smoke tests).
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import ModelConfig, SHAPES, ShapeConfig

_ARCH_MODULES = {
    "qwen2-0.5b": "qwen2_0_5b",
    "minicpm-2b": "minicpm_2b",
    "gemma-2b": "gemma_2b",
    "qwen3-4b": "qwen3_4b",
    "zamba2-2.7b": "zamba2_2_7b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "mixtral-8x22b": "mixtral_8x22b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "mamba2-370m": "mamba2_370m",
    "whisper-base": "whisper_base",
    "llama3.2-1b": "llama3_2_1b",  # paper's overhead-eval model (§5.1)
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "moonlight-16b-a3b-ep8": "moonlight_16b_a3b_ep8",
}

_NOT_ASSIGNED = ("llama3.2-1b", "moonlight-16b-a3b", "moonlight-16b-a3b-ep8")
ASSIGNED_ARCHS: List[str] = [a for a in _ARCH_MODULES
                             if a not in _NOT_ASSIGNED]

# long_500k needs sub-quadratic attention: runs for SSM/hybrid and SWA archs.
_LONG_CONTEXT_OK = {"zamba2-2.7b", "mamba2-370m", "mixtral-8x22b"}


def _module(name: str):
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")


def get(name: str) -> ModelConfig:
    return _module(name).CONFIG


def tiny(name: str) -> ModelConfig:
    return _module(name).tiny()


def list_archs() -> List[str]:
    return list(_ARCH_MODULES)


def shape_applicable(arch: str, shape: str) -> bool:
    """Which (arch x shape) cells lower. 40 assigned cells; 7 documented skips."""
    if shape == "long_500k" and arch not in _LONG_CONTEXT_OK:
        return False  # pure full-attention / enc-dec: skip per DESIGN.md §4
    return True


def cells(arch: str) -> List[ShapeConfig]:
    return [s for k, s in SHAPES.items() if shape_applicable(arch, k)]


def all_cells() -> List[tuple]:
    out = []
    for arch in ASSIGNED_ARCHS:
        for shape_name in SHAPES:
            out.append((arch, shape_name, shape_applicable(arch, shape_name)))
    return out
