"""One place for JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, the serving launchers, the benchmark
runner) call :func:`use_compile_cache` once, before their first compile;
nothing calls it at import.  The cache directory is part of every cache
key's location, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the cache goes
  there — this module sets no other directory;
* otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``), a fixed
  path never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
