"""Where JAX keeps its persistent compilation cache.

Every entry point calls ``configure_compile_cache()`` at the start of
its ``main()`` (never at import time).  A full-width step program takes
seconds to tens of seconds to compile, and the cache lets the next
process on the same machine skip that.

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
    else is configured here.
  * otherwise: ``<repo>/.jax_cache``, a fixed path inside the checkout
    (git ignores it).  The path is part of the cache key, so it is
    never built from a temp dir, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
