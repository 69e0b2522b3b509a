"""Architecture registry: ``--arch <id>`` resolves here.

Each module exposes ``config(ep_degree)`` (the exact published geometry)
and ``smoke_config()`` (a reduced same-family config for CPU tests).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

from repro.models.config import Group, ModelConfig

from . import (deepseek_v3_671b, gemma2_9b, granite_moe_3b, jamba_v01_52b,
               llama3_2_1b, llava_next_mistral_7b, mamba2_130m,
               moonlight_16b_a3b, qwen1_5_110b, qwen3_14b, whisper_base)
from .shapes import SHAPES, ShapeCell, applicable, input_specs

_MODULES = (qwen1_5_110b, llama3_2_1b, qwen3_14b, gemma2_9b, granite_moe_3b,
            deepseek_v3_671b, mamba2_130m, llava_next_mistral_7b,
            jamba_v01_52b, whisper_base, moonlight_16b_a3b)

REGISTRY: Dict[str, Tuple[Callable, Callable]] = {
    m.ARCH: (m.config, m.smoke_config) for m in _MODULES
}

ARCHS = tuple(REGISTRY)


def get_config(arch: str, *, smoke: bool = False, ep_degree: int = 16):
    if arch not in REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(REGISTRY)}")
    full, small = REGISTRY[arch]
    return small() if smoke else full(ep_degree=ep_degree)


def expert_share(cfg: ModelConfig, *, layers: int, experts_held: int,
                 vocab: int) -> ModelConfig:
    """One chip's share of an expert-parallel deployment of ``cfg``:
    the first ``layers`` layers (the rest lie on further pipeline stages),
    the first ``experts_held`` experts of each expert layer (the router
    keeps its full width; the other experts live on the other chips of
    the layer's group) and the first ``vocab`` token ids (the vocabulary
    split over the same chips).  Every width is kept."""
    groups, left = [], layers
    for g in cfg.groups:
        reps = min(g.repeats, left // len(g.blocks))
        if reps:
            groups.append(Group(g.name, g.blocks, reps))
        left -= reps * len(g.blocks)
    if left or not groups:
        raise ValueError(f"{layers} layers do not cut {cfg.name}'s groups")
    moe = cfg.moe and dataclasses.replace(cfg.moe, held=(0, experts_held))
    return dataclasses.replace(cfg, groups=tuple(groups), vocab=vocab,
                               moe=moe)


__all__ = ["REGISTRY", "ARCHS", "get_config", "expert_share", "SHAPES",
           "ShapeCell", "applicable", "input_specs"]
