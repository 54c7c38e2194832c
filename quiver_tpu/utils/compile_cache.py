"""Where JAX's persistent compilation cache lives.

One rule for every entry point of the repo (tests, benches, scripts,
``chip_smoke.py``): where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
itself reads it and nothing here sets a directory; where it is not, the
cache is ``<checkout>/.jax_cache``. The path is part of the cache key,
so it is never derived from a temp dir, a pid or the time.

The key includes the program's debug locations, all of them
(``jax_compilation_cache_include_metadata_in_key`` makes JAX skip its
``strip-debuginfo`` pass before it hashes a program): ``op_name``s,
and with them the file path, line number and caller frames of every
traced line, test files included. JAX leaves them out by default, so
two programs that differ only in ``op_name`` (a ``named_scope`` added,
moved or renamed) would share an entry, and whichever compiled first
would lend the other its names: ``chipbench/trace.py`` and XProf read
device time by those names, so here they are part of what a program
is. JAX has no switch for the names alone, and the price is a standing
one: a change that shifts a line of any file a program is traced
through (``parallel/train.py``, ``ops/sample*.py``, ``models/sage.py``,
``serving.py``, the calling test or cell), or a checkout at another
path, finds no entry for ANY program traced through it and compiles
them all again (PERF.md section 6, PR 25, has the seconds).
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
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
