"""Architecture registry: 10 assigned archs, the paper's 3 Llama-2-family
experiment models, and the benchmark's further archs.  `get_config(name)` /
`get_smoke_config(name)`."""

from __future__ import annotations

import importlib

from repro.models.config import ModelConfig

# arch id -> module name
_REGISTRY = {
    # assigned architecture pool
    "dbrx-132b": "dbrx_132b",
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "whisper-base": "whisper_base",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "internvl2-1b": "internvl2_1b",
    "gemma2-27b": "gemma2_27b",
    "nemotron-4-15b": "nemotron_4_15b",
    "mamba2-370m": "mamba2_370m",
    "llama3.2-1b": "llama3_2_1b",
    # the paper's own experiment models
    "microllama-300m": "microllama_300m",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "openllama-3b": "openllama_3b",
    # further archs the benchmark runs
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
}

ASSIGNED_ARCHS = ("dbrx-132b", "phi3-mini-3.8b", "whisper-base",
                  "deepseek-v2-236b", "recurrentgemma-9b", "internvl2-1b",
                  "gemma2-27b", "nemotron-4-15b", "mamba2-370m",
                  "llama3.2-1b")
PAPER_ARCHS = ("microllama-300m", "tinyllama-1.1b", "openllama-3b")
ALL_ARCHS = tuple(_REGISTRY)


def _module(name: str):
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return importlib.import_module(f"repro.configs.{_REGISTRY[name]}")


def get_config(name: str) -> ModelConfig:
    return _module(name).CONFIG


def get_smoke_config(name: str) -> ModelConfig:
    return _module(name).smoke_config()
