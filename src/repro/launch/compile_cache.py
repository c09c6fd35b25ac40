"""JAX's persistent compilation cache, placed for the entry points.

A cold TPU process recompiles every step program (tens of seconds per model
step); the persistent cache lets a later process of the same checkout load
them instead.  The cache directory is part of what makes an entry findable,
so it must not move between runs: it is either what the operator set in
``JAX_COMPILATION_CACHE_DIR`` or one fixed path inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset (``.cache/`` is
#: gitignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".cache" / "jax_compile"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Entry points call this at the start of ``main()``, never at import.
    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    nothing here overrides it.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
