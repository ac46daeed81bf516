"""Architecture registry: the 10 assigned architectures, Moonlight-16B-A3B
(latent attention and a DeepSeek-V3 mixture of experts) and the paper's own
CLIP ViT-Huge. `get_config(name)` returns the exact published config;
each arch's same-family shrunken config for CPU runs is registered under
its own name, ``<arch>-reduced`` (``get_config("smollm-360m-reduced")``,
or ``get_reduced_config("smollm-360m")``).
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import (CLIPConfig, EncDecConfig, MambaConfig,
                                MLAConfig, ModelConfig, MoEConfig,
                                ParallelConfig, RWKVConfig, TrainConfig)

ARCH_IDS = (
    "qwen3-moe-30b-a3b", "arctic-480b", "rwkv6-1.6b", "internvl2-76b",
    "smollm-360m", "starcoder2-3b", "granite-20b", "minitron-8b",
    "seamless-m4t-large-v2", "jamba-v0.1-52b", "moonlight-16b-a3b",
)
PAPER_ARCH = "clip-vit-huge"
ALL_ARCHS = ARCH_IDS + (PAPER_ARCH,)
REDUCED_SUFFIX = "-reduced"
REDUCED_ARCHS = tuple(a + REDUCED_SUFFIX for a in ALL_ARCHS)

_MODULES = {
    "qwen3-moe-30b-a3b": "qwen3_moe_30b",
    "arctic-480b": "arctic_480b",
    "rwkv6-1.6b": "rwkv6_1_6b",
    "internvl2-76b": "internvl2_76b",
    "smollm-360m": "smollm_360m",
    "starcoder2-3b": "starcoder2_3b",
    "granite-20b": "granite_20b",
    "minitron-8b": "minitron_8b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "clip-vit-huge": "clip_vit_huge",
}


def get_config(name: str):
    """The published config of ``name``, or the reduced config of
    ``<arch>-reduced``."""
    if name in REDUCED_ARCHS:
        return get_reduced_config(name[:-len(REDUCED_SUFFIX)])
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: "
                       f"{sorted(ALL_ARCHS + REDUCED_ARCHS)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG


def get_reduced_config(name: str):
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.REDUCED

