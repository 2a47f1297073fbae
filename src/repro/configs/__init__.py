"""Architecture + shape registry.  Importing this package registers all
assigned architectures."""

from repro.configs.base import ArchConfig, get_arch, list_archs, reduced, register
from repro.configs.shapes import (SHAPES, ShapeConfig, applicable, cells,
                                  get_shape, skip_reason)

# Register every assigned architecture (import side effect).
from repro.configs import (  # noqa: F401  isort: skip
    musicgen_medium,
    mamba2_780m,
    llama4_scout_17b_a16e,
    granite_moe_3b_a800m,
    gemma2_27b,
    gemma3_4b,
    gemma2_9b,
    qwen3_8b,
    hymba_1_5b,
    llama_3_2_vision_11b,
    nemotron_3_nano_30b_a3b,
)

ARCH_IDS = list_archs()

__all__ = [
    "ArchConfig", "ShapeConfig", "SHAPES", "ARCH_IDS",
    "get_arch", "get_shape", "list_archs", "reduced", "register",
    "applicable", "cells", "skip_reason",
]
