"""Architecture config registry: ``--arch <id>`` resolution."""
from __future__ import annotations

import importlib

from repro.configs.shapes import SHAPES, InputShape  # noqa: F401
from repro.models.common import ModelConfig

_MODULES = {
    "whisper-tiny": "repro.configs.whisper_tiny",
    "internlm2-20b": "repro.configs.internlm2_20b",
    "qwen2-moe-a2.7b": "repro.configs.qwen2_moe_a27b",
    "qwen1.5-4b": "repro.configs.qwen15_4b",
    "gemma3-1b": "repro.configs.gemma3_1b",
    "grok-1-314b": "repro.configs.grok1_314b",
    "rwkv6-1.6b": "repro.configs.rwkv6_16b",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "llama-3.2-vision-90b": "repro.configs.llama32_vision_90b",
    "qwen1.5-32b": "repro.configs.qwen15_32b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; one of {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch]).CONFIG


# Archs that legitimately run the 524k-decode shape (sub-quadratic or
# windowed); everything else skips long_500k (see DESIGN.md §5).
LONG_CONTEXT_ARCHS = ("gemma3-1b", "rwkv6-1.6b", "recurrentgemma-2b")


def shape_applies(arch: str, shape_name: str) -> bool:
    if shape_name == "long_500k":
        return arch in LONG_CONTEXT_ARCHS
    return True


def dryrun_matrix() -> list[tuple[str, str]]:
    """All (arch, shape) pairs exercised by the multi-pod dry-run."""
    return [(a, s) for a in ARCH_IDS for s in SHAPES
            if shape_applies(a, s)]
