"""Where JAX's persistent compilation cache lives.

A process that compiles the same programs as an earlier one (a rerun of the
launcher, the chip smoke test, a benchmark) finds them in this cache instead
of compiling again. The cache directory is part of what JAX keys on, so it
must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing here
    sets another directory;
  * unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored), a fixed
    path that never depends on a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "configure_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def configure_compile_cache() -> str:
    """Points JAX's persistent compilation cache at its directory (see the
    module docstring) and returns that directory. Call before the first
    compile."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
