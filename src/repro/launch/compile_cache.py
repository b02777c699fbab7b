"""JAX's persistent compilation cache for the entry points.

``serve``, ``build_index``, ``eval_quality`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile.  The cache lives
where ``JAX_COMPILATION_CACHE_DIR`` says when it is set (JAX reads the
variable itself, and nothing here overrides it); otherwise at the fixed
path ``<checkout>/.jax_cache``.  The path is part of each entry's key, so
it never depends on a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir` and
    return that directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
