"""Process-level runtime set-up shared by every entry point.

Three jobs: decide where JAX keeps its persistent compilation cache,
decide where the host preparation's products are kept
(``prep_store_dir``, read by ``lux_tpu/prepstore.py`` alone), and
record what JAX compiles.  ``cli.main``, ``bench.main``,
``serve.main``, ``fleet.main`` and ``chip_smoke.py`` call
``use_compile_cache()`` first thing, before anything compiles; nothing
else in the repo sets a cache directory.
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
    ``<checkout>/.jax_cache`` (git-ignored).  Also installs
    ``watch_compiles()``."""
    watch_compiles()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def prep_store_dir() -> str:
    """Where ``lux_tpu.prepstore`` keeps the relabelled graphs, pair
    plans and sparse views it has computed: ``LUX_PREP_STORE_DIR``
    where that is set (placed from outside, as the compile cache is),
    else ``<checkout>/.prep_store`` (git-ignored, beside
    ``.jax_cache``).  Deleting the directory clears the store."""
    return (os.environ.get("LUX_PREP_STORE_DIR")
            or os.path.join(_CHECKOUT, ".prep_store"))


# JAX's own timers (jax.monitoring) -> telemetry ring records: the
# "which step recompiled" trail.  backend_compile covers
# compile-or-load-from-the-persistent-cache, so ANY jit.compile record
# inside a measured window is a program the warm-up missed.
_JIT_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_load",
}
_watching = False


def watch_compiles() -> None:
    """Install (once per process) the ``jax.monitoring`` listener
    that turns JAX's trace / lower / backend-compile / cache-load
    durations into ring records ``jit.trace``, ``jit.lower``,
    ``jit.compile``, ``jit.cache_load`` (count ``fun``: the function's
    name where JAX gives it)."""
    global _watching
    if _watching:
        return
    _watching = True
    from jax import monitoring

    from lux_tpu import telemetry

    def on_duration(event, seconds, **kw):
        name = _JIT_DURATIONS.get(event)
        if name is not None:
            counts = {"fun": kw["fun_name"]} if "fun_name" in kw else {}
            telemetry.mark(name, seconds=seconds, **counts)

    monitoring.register_event_duration_secs_listener(on_duration)
