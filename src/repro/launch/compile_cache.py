"""JAX's persistent compilation cache, shared by every entry point.

Each entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/serve_decode.py``) calls
``enable_compile_cache()`` before it compiles anything, so a second run in
the same checkout reloads its XLA programs instead of compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
helper sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``
— a FIXED path (git-ignored), because the directory is part of what a
later run looks up: a temporary or per-process name would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root (this file is <root>/src/repro/launch/compile_cache.py)
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
