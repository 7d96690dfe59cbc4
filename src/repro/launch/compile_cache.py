"""JAX's persistent compilation cache for the repo's run scripts.

Compiling the kernels and scans for a chip takes longer than running them
at smoke sizes, so every entry point that drives the device turns the
cache on before its first compile.  Nothing turns it on at import time,
and the tests run without it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache(root: Path = CHECKOUT) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
    nothing is set here.  Otherwise the cache lives at ``<root>/.jax_cache``:
    a fixed path, since a cache directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
