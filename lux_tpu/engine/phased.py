"""Shared scaffolding for per-iteration phase timing (the engines'
``timed_phases`` — the analogue of the reference's per-iteration
per-part loadTime/compTime/updateTime -verbose prints, reference
sssp_gpu.cu:513-518).

Each phase is a SEPARATE compiled program returning (output, scalar
fence); fetching the scalar is the completion fence (it depends on
the whole phase and ships O(1) bytes).  Separate executables deliberately
prevent cross-phase fusion, so the split is honest at the cost of
materializing phase outputs and dispatch overhead — read relative
weights, not GTEPS.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from lux_tpu.parallel.mesh import PARTS_AXIS


def cksum(x):
    """Tiny fence value ([3] float32): depends on the phase output,
    costs nothing (the same first-8-elements convention as
    lux_tpu.timing.fence, wide-int-safe — see timing._cksum)."""
    from lux_tpu.timing import _cksum
    return _cksum(x)


def mesh_wrap(mesh, n_graph_args, parts_spec, repl_spec):
    """Returns wrap(fn, in_specs, out_spec) that shard_maps a phase fn
    over the parts mesh; the fence scalar is pmin-replicated (phase
    fns that need a true global scalar psum it themselves first —
    pmin of identical values is the identity)."""

    def wrap(fn, in_specs, out_spec):
        def inner(*a):
            out, c = fn(*a)
            return out, jax.lax.pmin(c, PARTS_AXIS)

        # check_vma off: the all-gathered flat state is value-
        # replicated but the VMA analysis cannot see it
        return jax.shard_map(
            inner, mesh=mesh, check_vma=False,
            in_specs=in_specs + (parts_spec,) * n_graph_args,
            out_specs=(out_spec, repl_spec))

    return wrap


class PhaseTimer:
    """Runs fenced phase programs, recording wall seconds per name.
    ``last_fence`` keeps the fetched fence scalar (phases may encode a
    useful global value in it, e.g. the new frontier count)."""

    def __init__(self, fetch):
        self._fetch = fetch
        self.t = {}
        self.last_fence = None

    def __call__(self, name, fn, *args):
        from lux_tpu.profiling import annotation
        with annotation(f"lux_phase_{name}"):
            t0 = time.perf_counter()
            out, c = fn(*args)
            self.last_fence = self._fetch(c)
            self.t[name] = time.perf_counter() - t0
        return out
