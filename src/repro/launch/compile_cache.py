"""Where JAX keeps its persistent compilation cache for the entry points.

A 32-layer prefill or decode program takes tens of seconds to compile, so
every entry point that drives the chip turns on JAX's persistent cache:

  * if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    module sets no other directory;
  * otherwise the cache lives at one fixed path inside the checkout
    (``.jax_cache/``, git-ignored). The path is part of the cache key, so
    it must never be temporary, per-process or timed.

Tests do not call ``enable_compile_cache``.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def compile_cache_dir() -> str:
    """The directory the cache uses: ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else the fixed in-checkout path."""
    return os.environ.get(ENV) or CHECKOUT_CACHE


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
