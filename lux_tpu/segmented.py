"""Shared segmented-execution drivers.

Checkpointing (checkpoint.py), runtime guards (debug.py) and the run
supervisor (resilience.py) all run engines in host-visible segments;
this module is the single copy of that slicing logic so per-segment
behaviors (save, finite checks, stall detection, fault injection,
duration budgeting) compose instead of forking.

Three extensions beyond plain fixed-size slicing:

- ``on_segment`` hooks may RETURN a replacement state to continue
  with (the fault-injection harness corrupts state this way;
  lux_tpu/faults.py) or raise to abort; returning None keeps the
  current state.
- ``segment`` may be an int OR a ``DurationBudget``: each execution
  is then timed (fenced through ``lux_tpu.timing``) and the next
  slice is sized so a single XLA execution stays under the budget —
  the systematic replacement for the ad-hoc ``seg=2`` / small-``ni``
  routing big-scale runs used against a ~55 s per-execution wall
  seen on the earlier installation (PERF_NOTES round 5).
- each driver is a GENERATOR of segments (``each_run_segment``,
  ``each_converge_segment``) that a caller may suspend between two
  segments; ``run_segments`` / ``converge_segments`` are the same
  generators run to their end.
- the two generators offer the serving tier two seams, both unused
  by every batch caller: ``while_running()`` is called between the
  DISPATCH of a slice and the wait for it, so host work that nothing
  in the slice reads runs while the device computes
  (lux_tpu/serve.py: the answers of columns that have left the
  batch); and a caller that changed the state while the driver was
  suspended resumes it with ``send(state)`` instead of ``next()``.

Both drivers are telemetry emitters (lux_tpu/telemetry.py): with an
active handle, every slice emits a ``segment`` event (sizes, fenced
seconds) and budget lock/halve decisions emit ``budget_*`` events;
with iter-stats active the slices run the engines' counter-recording
programs and fetch the per-iteration buffers once per boundary.
"""

from __future__ import annotations

import time
from typing import Callable

import numpy as np


class DurationBudget:
    """Adaptive segment sizing against a per-XLA-execution duration
    budget.  The 45 s default sat safely under a ~55 s worker-crash
    envelope measured on the earlier installation (PERF_NOTES round
    5); whether any such wall exists on this machine is UNVERIFIED
    (ROADMAP Queue 1 item 3 measures it) — bounded segments remain
    what checkpoints, health checks and heartbeats hang on.

    Policy:

    - the first ``warmup`` slices run ``probe_n`` iterations each:
      the FIRST execution of a program includes its compile, so only
      the last warmup slice's measured rate is trusted;
    - the slice size then LOCKS at ``headroom * budget_s / per_iter``
      clamped to [1, max_segment] — sticky, because pull engines
      compile one fused program per distinct slice length and a
      drifting size would recompile every segment;
    - an execution that overruns the budget halves the lock.  With
      ``per_size_compile=True`` (pull engines: one fused program per
      distinct slice length) the first execution at any new size is
      exempt, since it may carry that size's compile; push converge
      is ONE program with the cap as an argument AND reports actual
      relax steps (which vary every segment), so its callers pass
      False — otherwise every overrun would look like a fresh size
      and stay permanently exempt.
    """

    def __init__(self, budget_s: float = 45.0, probe_n: int = 1,
                 warmup: int = 2, max_segment: int = 4096,
                 headroom: float = 0.8, per_size_compile: bool = True):
        if not budget_s > 0:
            raise ValueError(f"budget_s must be > 0, got {budget_s}")
        self.budget_s = float(budget_s)
        self.probe_n = max(1, int(probe_n))
        self.warmup = max(1, int(warmup))
        self.max_segment = max(1, int(max_segment))
        self.headroom = float(headroom)
        self.per_size_compile = bool(per_size_compile)
        self.locked: int | None = None
        self.per_iter: float | None = None
        self._measured = 0
        self._seen: set[int] = set()

    def reset_rate(self, reason: str = "") -> None:
        """Forget the measured rate and re-enter warmup.  Called on a
        TOPOLOGY change (resilience's elastic re-placement): a
        per-iteration rate learned on 8 devices is stale on 4 — the
        locked segment size would roughly double the execution time
        and blow the duration wall on the first post-shrink segment.
        The per-size compile exemptions reset too (every size is a
        fresh compile on the new mesh)."""
        from lux_tpu import telemetry

        telemetry.current().emit("budget_reset", reason=reason,
                                 locked=self.locked,
                                 per_iter_s=(None if self.per_iter is
                                             None else
                                             round(self.per_iter, 6)))
        self.locked = None
        self.per_iter = None
        self._measured = 0
        self._seen.clear()

    def next_n(self, remaining: int) -> int:
        n = self.locked if self.locked is not None else self.probe_n
        return max(1, min(n, remaining, self.max_segment))

    def observe(self, n: int, seconds: float) -> None:
        """Record one fenced execution of ``n`` iterations."""
        from lux_tpu import telemetry

        first_at_size = self.per_size_compile and n not in self._seen
        self._seen.add(n)
        self._measured += 1
        if self.locked is None:
            if self._measured < self.warmup:
                return                      # compile-contaminated
            self.per_iter = max(seconds / max(n, 1), 1e-9)
            self.locked = max(1, min(
                self.max_segment,
                int(self.headroom * self.budget_s / self.per_iter)))
            telemetry.current().emit(
                "budget_lock", n=self.locked,
                per_iter_s=round(self.per_iter, 6),
                budget_s=self.budget_s)
        elif (seconds > self.budget_s and not first_at_size
              and self.locked > 1):
            self.locked = max(1, self.locked // 2)
            telemetry.current().emit(
                "budget_halve", n=self.locked,
                seconds=round(seconds, 3), budget_s=self.budget_s)


def _next_n(segment, remaining: int) -> int:
    if isinstance(segment, DurationBudget):
        return segment.next_n(remaining)
    return min(segment, remaining)


def run_segments(eng, state, num_iters: int, segment,
                 on_segment: Callable | None = None,
                 start_iter: int = 0, mem=None):
    """Run a pull engine in slices to ``num_iters``: every segment of
    ``each_run_segment`` (same arguments), one after another; returns
    the final state."""
    for state in each_run_segment(eng, state, num_iters, segment,
                                  on_segment, start_iter, mem):
        pass
    return state


def each_run_segment(eng, state, num_iters: int, segment,
                     on_segment: Callable | None = None,
                     start_iter: int = 0, mem=None,
                     while_running: Callable | None = None):
    """The pull driver, one slice at a time: a generator that runs ONE
    slice (``segment``: int size or DurationBudget) and its
    ``on_segment(state, done_iters)`` hook — which may return a
    replacement state — per ``next()``, and yields the state it goes
    on with.  Between two ``next()`` calls the driver is suspended
    with its state on the device and its watchdog, budget and counter
    history intact, so a caller may run something else in between
    (lux_tpu/serve.py time-shares one chip among runners this way);
    one that CHANGED the state meanwhile resumes with ``send(state)``.
    ``while_running()``, where given, is called once a slice, after
    the slice's dispatch and before anything waits for it (inside
    ``segment.run``): what it does runs while the device computes.
    ``mem`` is a memwatch.MemoryTrail sampled at every segment
    boundary (the round-22 occupancy trail — O(1) host work, outside
    the fused loop by construction).

    With telemetry active (lux_tpu/telemetry.py): each slice emits a
    ``segment`` event with its fenced seconds, and with iter-stats the
    slice runs ``eng.run_stats`` — the device-side per-iteration
    counters are fetched once per segment boundary (a few KB) and
    accumulated across segments.

    Every slice leaves one ``segment.run`` span (telemetry.span;
    counts ``iters`` = the slice's size).  The driver
    fences only when timed, counted or guarded: where it does not,
    ``segment.run`` ends at DISPATCH and the device's work runs on
    under whatever the caller does next.  No span is held across a
    ``yield``."""
    from lux_tpu import telemetry
    from lux_tpu.profiling import step_annotation

    tel = telemetry.current()
    st = tel.iter_stats
    guarded = getattr(eng, "health", False)
    if st is not None and start_iter == 0:
        st.begin_run()          # a resume keeps accumulating instead
    budget = segment if isinstance(segment, DurationBudget) else None
    timed = budget is not None or tel.events is not None
    done = start_iter
    seg_idx = 0
    watch = None           # threaded across segments: the trailing-
    #                        window checks keep their history even
    #                        when segments are shorter than the window
    while done < num_iters:
        n = _next_n(segment, num_iters - done)
        t0 = time.perf_counter()
        with telemetry.span("segment.run", iters=n), \
                step_annotation("lux_segment", seg_idx):
            if guarded:
                state, _itd, res_b, chg_b, res_p, chg_p, watch = \
                    eng.run_health(state, n, watch)
            elif st is not None:
                state, res_b, chg_b, res_p, chg_p = eng.run_stats(
                    state, n)
            else:
                state = eng.run(state, n)
            if while_running is not None:
                while_running()
            if timed or st is not None or guarded:
                from lux_tpu.timing import fence
                fence(state)   # O(1)-byte fence, not a download
        dt = time.perf_counter() - t0
        if guarded:
            # a tripped watchdog raises BEFORE the segment hook, so a
            # corrupted state can never reach a checkpoint save (the
            # trip iteration is already global: the threaded watch's
            # tick counts across segments; start_iter offsets resumes)
            from lux_tpu import health
            health.ensure_ok(watch, engine="pull",
                             base_iter=start_iter,
                             where=f"pull segment {seg_idx}")
        if budget is not None:
            budget.observe(n, dt)
        done += n
        if timed:
            tel.emit("segment", engine="pull", n=n, done=done,
                     seconds=round(dt, 6))
        seg_idx += 1
        if mem is not None:
            mem.sample(where=f"segment:{done}")
        if on_segment is not None:
            res = on_segment(state, done)
            if res is not None:
                state = res
        # counters land only after the segment hook (checkpoint save)
        # survives: a crash in the save window makes the retry re-run
        # this slice, so appending earlier would double-count it
        if st is not None:
            st.extend_pull(res_b, chg_b, n, res_p, chg_p)
        res = yield state
        if res is not None:
            state = res


def converge_segments(eng, label, active, segment,
                      max_iters: int | None = None,
                      on_segment: Callable | None = None,
                      start_iter: int = 0, mem=None):
    """Run a push engine to convergence in slices: every segment of
    ``each_converge_segment`` (same arguments), one after another.
    Returns (label, active, total_iters)."""
    out = label, active, start_iter
    for out in each_converge_segment(eng, label, active, segment,
                                     max_iters, on_segment,
                                     start_iter, mem):
        pass
    return out


def each_converge_segment(eng, label, active, segment,
                          max_iters: int | None = None,
                          on_segment: Callable | None = None,
                          start_iter: int = 0, mem=None,
                          while_running: Callable | None = None):
    """The push driver, one slice at a time: a generator that runs ONE
    slice (``segment``: int size or DurationBudget) and its hook per
    ``next()``, yields ``(label, active, total_iters)``, and ends when
    the active mask is empty or ``max_iters`` is reached (suspension,
    ``send((label, active))`` and ``while_running`` as in
    ``each_run_segment``: the call lies between the dispatch of
    ``eng.converge*`` and the completion fence).

    ``on_segment(label, active, total_iters, active_count)`` runs after
    each slice (may raise to abort, or return a replacement
    ``(label, active)``).  Convergence is detected from the active
    mask, never from iteration counts (delta-stepping counts relax
    steps only).  ``mem`` is a memwatch.MemoryTrail sampled at every
    boundary (round 22).

    With telemetry active: each slice emits a ``segment`` event, and
    with iter-stats the slice runs ``eng.converge_stats`` — frontier/
    edge counters fetched once per boundary and accumulated across
    segments (a resumed run keeps accumulating).

    Every slice leaves three kinds of span (telemetry.span), each
    opened and closed inside one ``next()``: ``segment.run`` (the
    slice to its completion fence; counts ``iters`` = the fetched
    iteration count), ``segment.count`` (the fetch of the
    active count that follows the fence) and, after a hook that
    replaced the state, ``segment.recount``.
    """
    import jax
    import jax.numpy as jnp

    from lux_tpu import telemetry
    from lux_tpu.profiling import step_annotation

    tel = telemetry.current()
    st = tel.iter_stats
    guarded = getattr(eng, "health", False)
    if st is not None and start_iter == 0:
        st.begin_run()
    budget = segment if isinstance(segment, DurationBudget) else None
    total = start_iter
    seg_idx = 0
    watch = None           # threaded: a stall spanning a segment
    #                        boundary still accumulates
    cap = np.iinfo(np.int32).max if max_iters is None else max_iters
    while total < cap:
        n = _next_n(segment, cap - total)
        t0 = time.perf_counter()
        with telemetry.span("segment.run") as sp, \
                step_annotation("lux_segment", seg_idx):
            if guarded:
                label, active, it, fsz, fed, fszp, fedp, watch = \
                    eng.converge_health(label, active, n, watch)
            elif st is not None:
                label, active, it, fsz, fed, fszp, fedp = \
                    eng.converge_stats(label, active, n)
            else:
                label, active, it = eng.converge(label, active, n)
            if while_running is not None:
                while_running()
            # the scalar fetch depends on the whole while_loop: it is
            # the completion fence (O(1) bytes to the host)
            it = int(np.asarray(jax.device_get(it)))
            sp.count(iters=it)
        dt = time.perf_counter() - t0
        if guarded:
            # raise BEFORE the segment hook: a corrupted/livelocked
            # state never reaches a checkpoint save (trip iterations
            # are global via the threaded watch's tick)
            from lux_tpu import health
            health.ensure_ok(watch, engine="push",
                             base_iter=start_iter,
                             where=f"push segment {seg_idx}")
        if budget is not None and it > 0:
            budget.observe(it, dt)
        total += it
        with telemetry.span("segment.count"):
            cnt = int(np.asarray(jax.device_get(jnp.sum(active))))
        tel.emit("segment", engine="push", iters=it, total=total,
                 active=cnt, seconds=round(dt, 6))
        seg_idx += 1
        if mem is not None:
            mem.sample(where=f"segment:{total}")
        if on_segment is not None:
            res = on_segment(label, active, total, cnt)
            if res is not None:
                label, active = res
                # the hook's replacement may still be on its way to
                # the device (``place`` ends at dispatch): the count
                # is where the host waits for it to arrive
                with telemetry.span("segment.recount"):
                    cnt = int(np.asarray(jax.device_get(
                        jnp.sum(active))))
        # counters land only after the segment hook (checkpoint save)
        # survives: a crash in the save window makes the retry re-run
        # this slice, so appending earlier would double-count it
        if st is not None:
            st.extend_push(fsz, fed, it, fszp, fedp)
        res = yield label, active, total
        if res is not None:
            label, active = res     # changed while suspended: go on
        elif cnt == 0:
            break
