"""Architecture registry: one module per assigned architecture.

``get_config(name, reduced=False)`` returns the exact published config
(full) or a structure-preserving small config (reduced) for CPU smoke
tests.  ``ARCH_IDS`` is the assignment's architecture pool.
"""
from __future__ import annotations

import importlib

ARCH_IDS = [
    "nemotron-4-15b",
    "llama3.2-3b",
    "granite-8b",
    "llama3-8b",
    "mamba2-1.3b",
    "jamba-1.5-large-398b",
    "deepseek-v2-236b",
    "llama4-scout-17b-a16e",
    "chameleon-34b",
    "seamless-m4t-large-v2",
    "deepseek-v2-lite",
]

_MODULES = {
    "nemotron-4-15b": "nemotron_4_15b",
    "llama3.2-3b": "llama3_2_3b",
    "granite-8b": "granite_8b",
    "llama3-8b": "llama3_8b",
    "mamba2-1.3b": "mamba2_1_3b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "chameleon-34b": "chameleon_34b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def get_config(name: str, reduced: bool = False):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f".{_MODULES[name]}", __package__)
    return mod.reduced() if reduced else mod.full()


from .shapes import SHAPES, shape_applicable  # noqa: E402
