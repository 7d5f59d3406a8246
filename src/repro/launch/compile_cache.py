"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed on the directory it lives in as well as on the
program, so the directory must not move between runs. The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it at start-up and this
  module sets nothing, so whoever runs the program places the cache;
* unset: ``<checkout>/.jax_cache``, one fixed path per checkout (listed in
  ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile. Leaves the directory to JAX when the
    environment names one.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
