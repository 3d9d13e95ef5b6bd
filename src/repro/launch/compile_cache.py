"""Persistent XLA compilation cache for the launchers and ``chip_smoke.py``.

A fresh process on a fresh machine compiles everything again; a 32-layer
model takes tens of seconds per shape.  So that a second run finds what the
first compiled, the cache lives at one fixed place:
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself), else
``.jax_cache/`` at the root of the checkout.  Tests do not call this.
"""
from __future__ import annotations

import os

import jax

__all__ = ["DEFAULT_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> three levels up is the checkout root
DEFAULT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
