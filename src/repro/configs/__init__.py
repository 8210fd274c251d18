"""Architecture registry: ``get_config('<arch-id>')`` / ``--arch <id>``."""
from __future__ import annotations

import importlib

_MODULES = {
    "deepseek-v2-236b": "deepseek_v2_236b",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "arctic-480b": "arctic_480b",
    "mamba2-2.7b": "mamba2_2p7b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "zamba2-7b": "zamba2_7b",
    "gemma3-12b": "gemma3_12b",
    "stablelm-1.6b": "stablelm_1p6b",
    "yi-6b": "yi_6b",
    "gemma2-2b": "gemma2_2b",
    "phi-3-vision-4.2b": "phi3_vision_4p2b",
}

ALL_ARCHS = tuple(_MODULES)


def get_config(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG.validate()


def reduced_config(name: str, **overrides):
    from repro.models.config import reduced
    return reduced(get_config(name), **overrides)
