"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (the launchers and ``chip_smoke.py``),
applied before the first compile: when ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing here overrides it; otherwise the
cache lives in the fixed, git-ignored ``.jax_cache/`` at the checkout
root. The path is part of each entry's key, so it is never built from a
temporary name, a pid or the time — a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
