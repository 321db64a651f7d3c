"""Timing utilities with reliable completion fences.

The reference drains its deferred-execution pipeline with an execution
fence + TimingLauncher before reading wall clocks (reference
sssp.cc:132-135).  The TPU analogue: dispatch is asynchronous, so a
timer is read only after a host fetch of a value that depends on the
whole computation — a fetch cannot complete before the device does,
and the fence below keeps it to O(1) bytes.
"""

from __future__ import annotations

import time

import numpy as np


def fetch(x) -> np.ndarray:
    """Force completion of everything ``x`` depends on; returns host
    value."""
    import jax
    return np.asarray(jax.device_get(x))


def _cksum(*leaves):
    """Tiny completion-fence checksum (first 8 elements per leaf).

    float32 carries a 24-bit mantissa: casting INTEGER leaves wider
    than 24 bits (e.g. the packed uint32 pair rows, src<<7|rel)
    through it collapses values differing only above bit 24 into the
    same checksum.  Wide integer leaves therefore sum exactly in
    int32 (wraparound keeps determinism) and ride two separate
    sub-24-bit float channels, each exactly representable — the
    result is a [3] vector, one float channel + the int sum's
    low-12/high-20 bit channels."""
    import jax.numpy as jnp
    f = jnp.float32(0)
    i = jnp.int32(0)
    for leaf in leaves:
        x = leaf.reshape(-1)[:8]
        if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize > 3:
            i = i + jnp.sum(x.astype(jnp.int32))
        else:
            f = f + jnp.sum(x.astype(jnp.float32))
    return jnp.stack([f, (i & 0xFFF).astype(jnp.float32),
                      ((i >> 12) & 0xFFFFF).astype(jnp.float32)])


_cksum_jit = None


def fence(x) -> None:
    """Completion fence that ships O(1) bytes: fetches a tiny
    checksum DEPENDENT on ``x`` instead of ``x`` itself.  A full
    ``fetch`` of a multi-GB state bills its device->host transfer to
    whatever is being timed.

    One module-level jitted checksum: repeat calls with the same leaf
    shapes hit the jit cache, so no compile lands inside a timed
    window after the warmup call."""
    import jax

    global _cksum_jit
    if _cksum_jit is None:
        _cksum_jit = jax.jit(_cksum)
    fetch(_cksum_jit(*jax.tree.leaves(x)))


def loop_bench(step, carry0, k: int, repeats: int = 3,
               clock=time.perf_counter):
    """The trusted microbenchmark recipe (PERF_NOTES rounds 2-3) as a
    library call: ``step(carry) -> (scalar, carry)`` runs ``k`` times
    inside ONE jitted ``fori_loop`` with a loop-DEPENDENT carry and a
    scalar output, so XLA can neither hoist the work out of the loop
    nor dead-code it, and the scalar fetch is the completion fence —
    no multi-MB transfer is ever billed to the timed window.

    Big operands ride the carry (jit ARGUMENTS, never closed-over
    constants — audit.py const-bytes); leave inputs you don't mutate in
    the carry untouched.  One compile happens on the warmup call;
    ``repeats`` timed calls follow on the warm cache.

    ``clock`` is injectable for deterministic tests
    (tests/test_observe.py).  Returns (seconds_per_step list — one
    entry per repeat — and the warmup's scalar output).
    """
    import jax
    import jax.numpy as jnp

    def run(c0):
        def body(_, c):
            acc, cur = c
            sv, cur = step(cur)
            return (acc + sv, cur)
        return jax.lax.fori_loop(0, k, body,
                                 (jnp.float32(0), c0))[0]

    r = jax.jit(run)
    out = float(fetch(r(carry0)))      # compile + warm; fetch = fence
    samples = []
    for _ in range(repeats):
        t0 = clock()
        float(fetch(r(carry0)))
        samples.append((clock() - t0) / k)
    return samples, out


def _trace_ctx(trace_dir):
    from lux_tpu.profiling import trace
    return trace(trace_dir)


def timed_fused_run(eng, num_iters: int, trace_dir: str | None = None,
                    repeats: int = 1):
    """Warm up a pull engine ONCE with the SAME static iteration count
    (num_iters is a static jit arg — a different count would recompile
    inside the timed region), then time ``repeats`` fresh fused runs.
    When trace_dir is set, a profiler trace captures ONLY the timed
    runs (warmup and compilation are excluded).

    With telemetry iter-stats active (telemetry.use(iter_stats=...)),
    every run is the counter-recording variant (eng.run_stats — same
    program warmed and timed) and the LAST timed repeat's counters are
    fetched AFTER its elapsed time is recorded, so the download is
    never billed.  Per-repeat seconds are emitted as ``timed_run``
    events.

    Returns (final_state, [elapsed_seconds per repeat]).
    """
    from lux_tpu import telemetry
    from lux_tpu.profiling import step_annotation

    tel = telemetry.current()
    st = tel.iter_stats
    guarded = getattr(eng, "health", False)

    def one(state):
        if guarded:
            # the watchdog loop variant IS the timed program; the
            # 24-byte word is checked after the elapsed time is
            # recorded, so the check is never billed
            s, _it, rb, cb, rbp, cbp, h = eng.run_health(state,
                                                         num_iters)
            return s, rb, cb, rbp, cbp, h
        if st is not None:
            return (*eng.run_stats(state, num_iters), None)
        return eng.run(state, num_iters), None, None, None, None, None

    state, res_b, chg_b, res_p, chg_p, hvec = one(eng.init_state())
    fence(state)
    elapsed = []
    with _trace_ctx(trace_dir):
        for i in range(repeats):
            state = eng.init_state()
            fence(state)       # H2D upload is async: keep it untimed
            with step_annotation("lux_timed_run", i):
                t0 = time.perf_counter()
                state, res_b, chg_b, res_p, chg_p, hvec = one(state)
                fence(state)   # O(1)-byte fence, not a state download
                elapsed.append(time.perf_counter() - t0)
            tel.emit("timed_run", repeat=i, iters=num_iters,
                     seconds=round(elapsed[-1], 6))
    if guarded:
        from lux_tpu import health
        tel.emit("health", **health.ensure_ok(
            hvec, engine="pull", where="timed pull run"),
            iters=num_iters)
    if st is not None:
        st.begin_run()         # counters describe the LAST timed run
        st.extend_pull(res_b, chg_b, num_iters, res_p, chg_p)
    return state, elapsed


def timed_converge(eng, max_iters=None, verbose: bool = False,
                   trace_dir: str | None = None, repeats: int = 1):
    """Warm up a push engine's converge program ONCE (replaying
    per-iteration frontier sizes from the warmup's device counters
    when verbose), then time ``repeats`` fresh whole-run converges; a
    trace_dir captures only the timed runs.  With telemetry iter-stats
    active the timed program is eng.converge_stats and the last timed
    repeat's counters are fetched after its elapsed time is recorded.
    Returns (labels, iters, [elapsed_seconds per repeat])."""
    from lux_tpu import telemetry
    from lux_tpu.profiling import step_annotation

    tel = telemetry.current()
    st = tel.iter_stats
    guarded = getattr(eng, "health", False)

    def one(label, active):
        if guarded:
            return eng.converge_health(label, active, max_iters)
        if st is not None:
            return (*eng.converge_stats(label, active, max_iters),
                    None)
        l, a, it = eng.converge(label, active, max_iters)
        return l, a, it, None, None, None, None, None

    if verbose and st is None:
        # one extra run purely to replay counters; with an active
        # iter-stats handle the caller replays the TIMED run's
        # counters instead (printing here would double the series)
        eng.run(max_iters=max_iters, verbose=True)
    label, active = eng.init_state()
    l2, a2, _it, _f, _e, _fp, _ep, _h = one(label, active)  # compile
    fence(l2)
    elapsed = []
    with _trace_ctx(trace_dir):
        for i in range(repeats):
            label, active = eng.init_state()
            fence((label, active))   # keep the async upload untimed
            with step_annotation("lux_timed_converge", i):
                t0 = time.perf_counter()
                label, active, it_d, fsz, fed, fszp, fedp, hvec = \
                    one(label, active)
                iters = int(fetch(it_d))
                elapsed.append(time.perf_counter() - t0)
            tel.emit("timed_run", repeat=i, iters=iters,
                     seconds=round(elapsed[-1], 6))
    if guarded:
        from lux_tpu import health
        tel.emit("health", **health.ensure_ok(
            hvec, engine="push", where="timed converge"),
            iters=iters)
    if st is not None:
        st.begin_run()
        st.extend_push(fsz, fed, iters, fszp, fedp)
    return eng.unpad(label), iters, elapsed


def timed_run_until(eng, tol: float, max_iters: int,
                    trace_dir: str | None = None):
    """Warm a pull engine's convergence program with a one-iteration
    call of the SAME executable (tol/max_iters are traced args, so no
    recompile), then time a fresh run-to-convergence; a trace_dir
    captures only the timed run.  With telemetry iter-stats active the
    program is eng.run_until_stats (per-iteration residuals fetched
    after the elapsed time is recorded).  Returns (state, iters,
    residual, elapsed)."""
    from lux_tpu import telemetry

    tel = telemetry.current()
    st = tel.iter_stats
    guarded = getattr(eng, "health", False)

    def one(state, cap):
        if guarded:
            return eng.run_until_health(state, tol, max_iters=cap)
        if st is not None:
            return (*eng.run_until_stats(state, tol, max_iters=cap),
                    None)
        s, it, res = eng.run_until(state, tol, max_iters=cap)
        return s, it, res, None, None, None, None, None

    s0, _it, _res, _rb, _cb, _rp, _cp, _h = one(eng.init_state(), 1)
    fence(s0)
    state0 = eng.init_state()
    fence(state0)              # keep the async upload untimed
    with _trace_ctx(trace_dir):
        t0 = time.perf_counter()
        state, it, res, rb, cb, rbp, cbp, hvec = one(state0,
                                                     max_iters)
        iters = int(fetch(it))
        elapsed = time.perf_counter() - t0
    tel.emit("timed_run", repeat=0, iters=iters,
             seconds=round(elapsed, 6))
    if guarded:
        from lux_tpu import health
        tel.emit("health", **health.ensure_ok(
            hvec, engine="pull", where="timed run_until"),
            iters=iters)
    if st is not None:
        st.begin_run()
        st.extend_pull(rb, cb, iters, rbp, cbp)
    return state, iters, float(fetch(res)), elapsed
