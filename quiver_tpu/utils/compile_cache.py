"""Where JAX's persistent compilation cache lives.

One rule for every entry point of the repo (tests, benches, scripts,
``chip_smoke.py``): where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
itself reads it and nothing here sets a directory; where it is not, the
cache is ``<checkout>/.jax_cache``. The path is part of the cache key,
so it is never derived from a temp dir, a pid or the time.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
