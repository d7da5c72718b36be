"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``/``train`` and the
benchmark CLIs) call ``enable_compile_cache()`` before their first compile;
importing a library module never turns the cache on.  A cache entry is keyed
by its directory among other things, so the directory must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself),
else ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
