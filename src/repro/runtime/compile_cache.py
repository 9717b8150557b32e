"""Where JAX keeps its persistent compilation cache.

Every entry point calls ``enable_compile_cache()`` first thing in its
``main``; library imports never do, so tests and embedding programs keep
whatever cache policy their own process chose.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; the helper
  reports it and sets no other directory.
* unset: the cache goes to one fixed directory inside the checkout
  (``<root>/.jax_cache``, git-ignored). The path is part of the cache
  key, so it is never built from a temp name, a pid or the time — a
  second run of any entry point from the same checkout finds the first
  run's kernels.

Either way every compile is cached, not only those over JAX's default
one-second threshold: the counting kernels compile in well under a
second each, but a served run compiles dozens of shapes.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory an entry point uses (no side effects)."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory. Touches no device."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
