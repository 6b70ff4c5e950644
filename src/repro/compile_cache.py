"""Where entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself and
nothing here overrides it); otherwise the caller's fixed in-checkout
directory is used. Importing ``repro`` never configures JAX — only entry
points (``chip_smoke.py``, ``benchmarks/run.py``) call this.
"""
from __future__ import annotations

import os


def enable_compile_cache(default_dir) -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``default_dir``."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    path = os.fspath(default_dir)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
