"""Config registry: every selectable ``--arch`` id maps to a ModelConfig."""
from __future__ import annotations

from repro.configs.base import (
    ModelConfig,
    ShapeConfig,
    SHAPES,
    TRAIN_4K,
    PREFILL_32K,
    DECODE_32K,
    LONG_500K,
    vocab_pad,
)
from repro.configs import resnet50 as _resnet50
from repro.configs.gpt_models import GPT_117M, GPT_800M, GPT_13B, GPT_175B

from repro.configs.granite_8b import CONFIG as _granite_8b
from repro.configs.qwen2_0_5b import CONFIG as _qwen2_0_5b
from repro.configs.command_r_35b import CONFIG as _command_r_35b
from repro.configs.llama3_2_3b import CONFIG as _llama3_2_3b
from repro.configs.whisper_small import CONFIG as _whisper_small
from repro.configs.llava_next_34b import CONFIG as _llava_next_34b
from repro.configs.jamba_v0_1_52b import CONFIG as _jamba
from repro.configs.mamba2_1_3b import CONFIG as _mamba2
from repro.configs.granite_moe_3b_a800m import CONFIG as _granite_moe
from repro.configs.llama4_maverick_400b_a17b import CONFIG as _llama4
from repro.configs.granite_4_0_h_small import (
    CONFIG as _granite_h_small, CONFIG_EP4 as _granite_h_small_ep4,
)

# The 10 assigned architectures (dry-run + roofline cells).
ASSIGNED: dict[str, ModelConfig] = {
    c.name: c
    for c in (
        _granite_8b,
        _qwen2_0_5b,
        _command_r_35b,
        _llama3_2_3b,
        _whisper_small,
        _llava_next_34b,
        _jamba,
        _mamba2,
        _granite_moe,
        _llama4,
    )
}

# The paper's own models.
PAPER_MODELS: dict[str, ModelConfig] = {
    c.name: c for c in (GPT_117M, GPT_800M, GPT_13B, GPT_175B)
}

# Served on the chip beside them: the published model, and one chip's
# share of its experts under four-way expert parallelism.
SERVED: dict[str, ModelConfig] = {
    c.name: c for c in (_granite_h_small, _granite_h_small_ep4)
}

REGISTRY: dict[str, ModelConfig] = {**ASSIGNED, **PAPER_MODELS, **SERVED}

RESNET_REGISTRY = {
    "resnet50": _resnet50.CONFIG,
    "resnet18": _resnet50.RESNET18,
    "resnet34": _resnet50.RESNET34,
}


def get_config(arch: str) -> ModelConfig:
    try:
        return REGISTRY[arch]
    except KeyError:
        raise KeyError(
            f"unknown arch {arch!r}; available: {sorted(REGISTRY)}"
        ) from None


def cells(archs=None, shapes=None):
    """All (arch, shape) benchmark cells, honoring long_500k applicability."""
    out = []
    for a in archs or ASSIGNED:
        cfg = get_config(a)
        for s in shapes or SHAPES:
            shp = SHAPES[s]
            if shp.name == "long_500k" and not cfg.long_context_ok:
                continue  # quadratic full-attention arch: documented skip
            out.append((cfg, shp))
    return out


def skipped_cells(archs=None):
    out = []
    for a in archs or ASSIGNED:
        cfg = get_config(a)
        if not cfg.long_context_ok:
            out.append((cfg.name, "long_500k", "full quadratic attention"))
    return out


__all__ = [
    "ModelConfig", "ShapeConfig", "SHAPES", "TRAIN_4K", "PREFILL_32K",
    "DECODE_32K", "LONG_500K", "ASSIGNED", "PAPER_MODELS", "REGISTRY",
    "RESNET_REGISTRY", "get_config", "cells", "skipped_cells", "vocab_pad",
]
