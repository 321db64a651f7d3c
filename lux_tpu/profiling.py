"""Profiling and tracing.

The reference's observability is wall clocks plus per-part phase
timings printed under -verbose (reference sssp_gpu.cu:513-518,
pagerank.cc:108-118).  The TPU-native equivalents:

- ``trace(dir)``: captures an XLA/TPU profiler trace viewable in
  TensorBoard / Perfetto (the analogue of Legion's prof logs).
- ``annotation``/``step_annotation``: host-side
  ``jax.profiler.TraceAnnotation`` wrappers — the only place in the
  package that touches that class.  ``telemetry.span`` opens
  ``annotation("lux:" + name)`` round every host region the program
  times; the run paths (timing.py, segmented.py, checkpoint.py) put
  them around their iterate / segment / checkpoint regions, so a
  captured trace shows named regions instead of anonymous XLA ops.

Where an iteration's time goes is read from the device side of such a
trace: the engines' traced code carries ``jax.named_scope`` labels
(lux_exchange / lux_gather / lux_reduce / lux_apply, push: lux_relax /
lux_update / lux_dense / lux_sparse, the deliveries' lux_gather_reduce
/ lux_combine / lux_dot_* / lux_gen_exchange) that name the ops of the
program that runs.  ``benchmarks/trace_reduce.py`` sums a trace's
milliseconds by scope, and ``tests/test_scopes.py`` holds every scope
a ``scope_ms`` metric names to the lowered programs.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def trace(log_dir: str | None):
    """Capture a jax.profiler trace into ``log_dir`` (no-op if None)."""
    if not log_dir:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield
    print(f"profiler trace written to {log_dir}")


def annotation(name: str):
    """Host-side named region for profiler traces
    (jax.profiler.TraceAnnotation); a no-op nullcontext when the
    profiler is unavailable.  Costs nothing outside an active trace
    capture."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name)
    except Exception:       # noqa: BLE001 — profiling must never break
        return contextlib.nullcontext()


def step_annotation(name: str, step: int):
    """Per-step named region (jax.profiler.StepTraceAnnotation) —
    segments/repeats show up as numbered steps in the trace viewer."""
    try:
        import jax

        return jax.profiler.StepTraceAnnotation(name, step_num=step)
    except Exception:       # noqa: BLE001
        return contextlib.nullcontext()
