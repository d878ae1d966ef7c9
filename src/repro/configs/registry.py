"""Architecture registry — ``--arch <id>`` resolution."""

from __future__ import annotations

from typing import Dict, List, Tuple

from .base import ModelConfig, SHAPE_CELLS, ShapeCell, get_shape_cell
from . import (
    deepseek_moe_16b,
    deepseek_v2_236b,
    gemma3_12b,
    granite_4_0_h_micro,
    mamba2_370m,
    mistral_nemo_12b,
    paligemma_3b,
    qwen2_5_32b,
    recurrentgemma_2b,
    whisper_large_v3,
    yi_34b,
)

_MODULES = {
    "recurrentgemma-2b": recurrentgemma_2b,
    "yi-34b": yi_34b,
    "gemma3-12b": gemma3_12b,
    "qwen2.5-32b": qwen2_5_32b,
    "mistral-nemo-12b": mistral_nemo_12b,
    "paligemma-3b": paligemma_3b,
    "deepseek-moe-16b": deepseek_moe_16b,
    "deepseek-v2-236b": deepseek_v2_236b,
    "mamba2-370m": mamba2_370m,
    "whisper-large-v3": whisper_large_v3,
    "granite-4.0-h-micro": granite_4_0_h_micro,
}

ARCHS: Tuple[str, ...] = tuple(_MODULES)


def get_config(arch: str) -> ModelConfig:
    try:
        return _MODULES[arch].CONFIG
    except KeyError:
        raise KeyError(f"unknown arch {arch!r}; available: {list(ARCHS)}") from None


def get_smoke_config(arch: str) -> ModelConfig:
    try:
        return _MODULES[arch].SMOKE
    except KeyError:
        raise KeyError(f"unknown arch {arch!r}; available: {list(ARCHS)}") from None


def cell_applicable(cfg: ModelConfig, cell: ShapeCell) -> Tuple[bool, str]:
    """Whether a (arch x shape) cell runs, and the reason when it doesn't.

    long_500k requires sub-quadratic attention (assignment rule): full-
    attention archs skip it, with the skip recorded in DESIGN.md / the
    dry-run report."""
    if cell.name == "long_500k" and not cfg.sub_quadratic:
        return False, "long_500k skipped: full/quadratic attention at 524k context"
    return True, ""


def all_cells() -> List[Tuple[str, str]]:
    """Full 40-cell assignment (including skips)."""
    return [(arch, cell.name) for arch in ARCHS for cell in SHAPE_CELLS]
