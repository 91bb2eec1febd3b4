"""JAX's persistent compilation cache, kept at a fixed path.

A cold chip run compiles every program (tens of seconds for one OPT-125M
chunk). The persistent cache keeps compiled programs on disk so the next
process skips that. Its directory must not move between runs: the path is
part of what makes an entry found again.

    JAX_COMPILATION_CACHE_DIR=/some/dir python chip_smoke.py   # that dir
    python chip_smoke.py                                       # .jax_cache/
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root holding src/
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path.

    Call before the first compile. When `JAX_COMPILATION_CACHE_DIR` is
    set, JAX already reads it and nothing is set here. Otherwise the cache
    goes to `.jax_cache/` at the checkout root.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
