"""JAX's persistent compilation cache for the chip entry points.

Called by ``launch/serve.py`` and ``chip_smoke.py`` before their first
compile — never at library import, so tests and library users keep JAX's
own defaults.  The cache's location is part of its key, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
itself, and nothing here overrides it), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

#: fixed in-checkout location used when the environment names none
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
