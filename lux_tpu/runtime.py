"""Process-level runtime set-up shared by every entry point.

One job today: decide where JAX keeps its persistent compilation
cache.  ``cli.main``, ``bench.main``, ``serve.main``, ``fleet.main``
and ``chip_smoke.py`` call ``use_compile_cache()`` first thing, before
anything compiles; nothing else in the repo sets a cache directory.
"""

from __future__ import annotations

import os

# the checkout that holds this package: the cache path is part of the
# cache key, so it is derived from the tree's own location and nothing
# that changes between runs (no tempfile, pid or time)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory
    and return it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the
    cache was placed from outside and JAX reads the variable itself —
    nothing is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
