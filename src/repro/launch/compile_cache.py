"""Persistent XLA compilation cache for the entry points.

A cold compile of the 25-layer AtacWorks train step takes on the order of
a minute; the persistent cache lets a later process on the same machine
skip it.  The cache's path is part of its key, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself, so nothing else is set here), otherwise ``.jax_cache/``
at the root of this checkout.  Only the command-line entry points call
this; library code and tests leave the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Switch the persistent compilation cache on; call once at the start
    of an entry point, before the first compile.  Returns its directory."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    import jax

    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
