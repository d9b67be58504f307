"""JAX's persistent compilation cache, placed where a launcher can find it
again.

The cache key includes the directory, so the directory must not move
between runs: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself, and nothing here overrides it), else the
fixed ``<checkout>/.jax_cache``.  Launchers call :func:`enable_compile_cache`
from their entry point; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``src/repro/launch/compile_cache.py`` -> the checkout root.
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The directory this process's compiles are cached in."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on (in :func:`compile_cache_dir`) and
    return its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
