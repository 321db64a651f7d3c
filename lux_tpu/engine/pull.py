"""The pull engine: dense gather-apply iterations.

One iteration (the analogue of one PullAppTask index launch,
reference pull_model.inl:423-470 + pagerank_gpu.cu:104-151):

1. make the full vertex state visible to every part — single device:
   a reshape; mesh: ``lax.all_gather`` over the ``parts`` axis (the
   reference's whole-region READ_ONLY requirement that Legion/GASNet
   materialize remotely, pull_model.inl:454-461);
2. gather each edge's source state by precomputed padded slot;
3. per-edge message (program.edge_value);
4. scatter-free segment reduction to each part's local destinations
   (replacing the CUB BlockScan + atomicAdd CTA pattern, SURVEY.md
   §3.3) — by default via the tiled chunk layout (ops/tiled.py),
   which keeps the hot loop on dense VPU/MXU ops; ``layout="flat"``
   falls back to the XLA scatter path (ops/segment.py), the
   correctness oracle;
5. per-vertex apply epilogue.

Fixed-iteration runs are fused into a single XLA program with
``lax.fori_loop`` — the TPU-native version of the reference's
fire-and-forget launch pipeline (pagerank.cc:109-114), with zero host
round-trips instead of deferred-execution tricks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from lux_tpu import telemetry
from lux_tpu.engine.auditable import AuditableEngine
from lux_tpu.engine.delivery import Delivery
from lux_tpu.engine.program import PartCtx, PullProgram, vmask_of
from lux_tpu.graph import ShardedGraph
from lux_tpu.parallel.mesh import PARTS_AXIS, shard_over_parts


class PullEngine(AuditableEngine):
    """Compiled pull-model iterations for one ShardedGraph + program.

    With ``mesh=None`` everything runs on one device (parts stacked on
    the leading axis, vmapped).  With a mesh, all part-major arrays are
    sharded over the ``parts`` axis and the same per-part computation
    runs under shard_map with an all-gather for remote state.

    Construction leaves the spans ``build.pair_plan`` and
    ``build.dense_layout`` (telemetry.span); on a single device the
    latter also hands each array to the device as it is built (to
    dispatch, not to arrival).
    """

    def __init__(self, sg: ShardedGraph, program: PullProgram, mesh=None,
                 layout: str = "tiled", tile_e: int = 512,
                 use_mxu: bool | str = "auto",
                 reduce_method: str = "auto",
                 pair_threshold: int | None = None,
                 pair_min_fill: int | str | None = None,
                 pair_stream: bool | None = None,
                 stream_msgs: bool | None = None,
                 exchange: str = "auto",
                 gather: str = "flat",
                 owner_tile_e: int | None = None,
                 owner_minmax_fused: bool = False,
                 health: bool = False,
                 audit: str | None = None):
        self.delivery, arrays = Delivery.build(
            sg, program, mesh, layout=layout, tile_e=tile_e,
            use_mxu=use_mxu, reduce_method=reduce_method,
            pair_threshold=pair_threshold, pair_min_fill=pair_min_fill,
            pair_stream=pair_stream, stream_msgs=stream_msgs,
            exchange=exchange, gather=gather,
            owner_tile_e=owner_tile_e,
            owner_minmax_fused=owner_minmax_fused)
        # the graph the dense layout runs on: the pair RESIDUAL when
        # pair delivery is on
        self.sg = sg = self.delivery.sg
        self.program = program
        self.mesh = mesh
        # health=True: run()/segmented drivers use the watchdog loop
        # variants (run_health / run_until_health, compiled lazily);
        # False leaves every watchdog-free program untouched
        self.health = bool(health)
        self.stats_cap = telemetry.DEFAULT_STATS_CAP
        if program.extra_arrays is not None:
            # program-contributed per-part constants (e.g. per-query
            # reset vectors): jit ARGUMENTS like every graph array —
            # the no-closure convention holds for query state too
            dev = jnp.asarray if mesh is None else np.asarray
            for k, v in program.extra_arrays(sg).items():
                arrays[f"prog_{k}"] = dev(np.asarray(v))
        if mesh is not None:
            arrays = shard_over_parts(mesh, arrays, sg.num_parts)
        self.arrays = arrays
        # compiled-variant registry for the static program auditor
        # (lux_tpu/audit.py): name -> (jitted fn, example-args thunk)
        self._audit_variants: dict = {}
        self._step_fn = self._build_step()
        if audit is not None:
            # mode validation lives in audit_engine (typed ValueError
            # on anything but 'warn'/'error')
            from lux_tpu import audit as _audit
            _audit.audit_engine(self, mode=audit)

    # -- state placement ----------------------------------------------

    def init_state(self):
        """Fresh state on the engine's devices.  Where the program has
        ``init_device`` the devices make it, with a compiled program
        over graph arrays they already hold (``_init_program``): no
        host array, no transfer, and the sharding ``shard_over_parts``
        would have given, so ``run`` takes it as its donated argument
        without a reshard.  Any other program, and an audit's stashed
        host init, take the host path: ``program.init`` in NumPy, then
        the transfer.

        Leaves a ``state.init`` span (``bytes``: the state's;
        ``device_bytes``: the same where the devices made it, 0 on the
        host path) whose two children cover it, ``bytes`` on each:
        ``state.init.build`` (what the host does to make the state: on
        the device path, fetching the compiled program) and
        ``state.init.put`` (whatever places the state on the devices,
        the program or the transfer: asynchronous, so it ends at
        dispatch)."""
        with telemetry.span("state.init") as sp:
            with telemetry.span("state.init.build") as build:
                state = self._consume_pending_init()
                make = None if state is not None else self._init_program
                if make is not None:
                    sds = self._audit_state_sds
                    nbytes = sds.size * sds.dtype.itemsize
                else:
                    if state is None:
                        state = self.program.init(self.sg)
                    state = np.asarray(state)
                    nbytes = state.nbytes
                build.count(bytes=nbytes)
            sp.count(bytes=nbytes,
                     device_bytes=0 if make is None else nbytes)
            with telemetry.span("state.init.put", bytes=nbytes):
                if make is not None:
                    program, keys = make
                    return program(*(self.arrays[k] for k in keys))
                if self.mesh is not None:
                    return shard_over_parts(self.mesh, [state],
                                            self.sg.num_parts)[0]
                return jnp.asarray(state)

    @functools.cached_property
    def _init_program(self):
        """(program, keys): the jitted program that makes the fresh
        state on the devices from the graph arrays named ``keys``, a
        vmap of ``program.init_device`` over the local parts (under
        shard_map on a mesh, parts in and out exactly as the step);
        ``None`` where the program has no such hook.  Graph arrays are
        ARGUMENTS, read from ``self.arrays`` at call time like every
        loop's, and only the ones a PartCtx is made of."""
        init = self.program.init_device
        if init is None:
            return None
        keys = [k for k in self._graph_keys
                if k in ("deg", "nvp") or k.startswith("prog_")]

        def core(*gargs):
            return jax.vmap(lambda g: init(self._part_ctx(g)))(
                dict(zip(keys, gargs)))

        if self.mesh is not None:
            P = PartitionSpec
            core = jax.shard_map(
                core, mesh=self.mesh,
                in_specs=(P(PARTS_AXIS),) * len(keys),
                out_specs=P(PARTS_AXIS))
        return jax.jit(core), keys

    def place(self, state):
        """Put a host state pytree on the engine's devices with the
        parts sharding (mirrors init_state's placement; used by
        checkpoint/resilience resume).  This is also the elastic
        RE-PLACEMENT entry point (round 11): the input is the global
        ``[P, vpad, ...]`` view, so the same call re-shards a
        checkpoint written on an 8-device mesh onto this engine's
        4-device one — parts fixed, device mapping changed.
        Leaves a ``state.place`` span (``bytes``); asynchronous, so
        the span ends at dispatch, not at arrival."""
        self._drop_pending_init()     # resume never needs the probe
        leaves, treedef = jax.tree.flatten(state)
        with telemetry.span("state.place",
                            bytes=sum(x.nbytes for x in leaves)):
            if self.mesh is not None:
                leaves = shard_over_parts(
                    self.mesh, [np.asarray(x) for x in leaves],
                    self.sg.num_parts)
            else:
                leaves = [jnp.asarray(x) for x in leaves]
        return jax.tree.unflatten(treedef, leaves)

    def update_program_arrays(self, **arrays):
        """Swap program-contributed per-part arrays
        (``PullProgram.extra_arrays``; key ``<name>`` here maps to
        graph-array key ``prog_<name>``) with SAME-shape/dtype
        replacements — no recompile: every compiled variant reads
        ``self.graph_args`` at call time, so the next step/run sees
        the new arrays.  A host array is placed as the engine places
        its graph arrays; a device array (``jax.Array``) that already
        has the current one's sharding is taken as it is, with no
        transfer.  This is the serving front-end's
        continuous-batching refill path (lux_tpu/serve.py): a retired
        query column's reset vector is replaced in the table where it
        lies on the device, and the updated table handed back here."""
        for k, v in arrays.items():
            key = f"prog_{k}"
            if key not in self.arrays:
                raise KeyError(
                    f"engine has no program array {k!r} "
                    f"(program.extra_arrays supplies "
                    f"{[x[5:] for x in self.arrays if x.startswith('prog_')]})")
            cur = self.arrays[key]
            placed = isinstance(v, jax.Array)
            arr = v if placed else np.asarray(v)
            if (arr.shape != tuple(cur.shape)
                    or np.dtype(arr.dtype) != np.dtype(cur.dtype)):
                raise ValueError(
                    f"program array {k!r} must keep shape "
                    f"{tuple(cur.shape)}/{np.dtype(cur.dtype)} "
                    f"(got {arr.shape}/{arr.dtype}) — shapes are "
                    f"compiled; rebuild the engine to change B")
            if placed:
                if not arr.sharding.is_equivalent_to(cur.sharding,
                                                     arr.ndim):
                    raise ValueError(
                        f"program array {k!r} arrives on the device "
                        f"with sharding {arr.sharding}, the engine "
                        f"holds it as {cur.sharding}")
            elif self.mesh is not None:
                arr = shard_over_parts(self.mesh, [arr],
                                       self.sg.num_parts)[0]
            else:
                arr = jnp.asarray(arr)
            self.arrays[key] = arr
        self.graph_args = tuple(self.arrays[k] for k in self._graph_keys)

    # -- one part's work ----------------------------------------------

    def _part_ctx(self, g) -> PartCtx:
        """What a program callback sees of one part's graph arrays."""
        sg = self.sg
        extra = {k[5:]: g[k] for k in g if k.startswith("prog_")}
        return PartCtx(deg=g["deg"], vmask=vmask_of(g, sg.vpad),
                       nv=sg.nv, ne=sg.ne, extra=extra or None)

    def _apply_epilogue(self, old_p, red, g):
        ctx = self._part_ctx(g)
        vm = ctx.vmask
        new = self.program.apply(old_p, red, ctx)
        keep = vm.reshape(vm.shape + (1,) * (new.ndim - 1))
        return jnp.where(keep, new, old_p)

    def _msg(self, vals, w):
        """The delivery's message function: source-only edge values."""
        return self.program.edge_value(vals, None, w)

    def _part_msgs(self, flat_state, old_p, g):
        """Phase 1 (gather): per-edge source gather + message values."""
        prog, d = self.program, self.delivery
        msg = self._msg
        if prog.needs_dst:
            def msg(vals, w):
                return prog.edge_value(vals, d.dst_values(old_p, g), w)
        msgs = d.messages(flat_state, msg, g)
        if d.tiles is not None and (d.reduce_method == "xla"
                                    or msgs.ndim != 2):
            # Keep the (serial, expensive) gather from being fused
            # into the W-wide broadcast consumer, which re-executes
            # it per output lane — measured 3-5x slower on v5e.
            # The Pallas kernel is an opaque boundary and needs no
            # barrier.
            msgs = jax.lax.optimization_barrier(msgs)
        return msgs

    def _part_step(self, flat_state, old_p, g):
        """g: dict of this part's graph arrays."""
        prog, d = self.program, self.delivery
        if d.dot_path:
            with jax.named_scope("lux_dot_reduce"):
                red = d.reduce_dot(flat_state, prog.edge_value_from_dot,
                                   g, old_p)
        elif d.fused:
            with jax.named_scope("lux_gather_reduce"):
                red = d.reduce_fused(flat_state, self._msg, g)
        else:
            with jax.named_scope("lux_gather"):
                msgs = self._part_msgs(flat_state, old_p, g)
            with jax.named_scope("lux_reduce"):
                red = d.reduce(flat_state, msgs, self._msg, g)
        with jax.named_scope("lux_apply"):
            return self._apply_epilogue(old_p, red, g)

    def _parts_step(self, local_state, full_state, g_local):
        """vmap _part_step over this device's parts."""
        sg = self.sg
        flat = full_state.reshape((sg.num_parts * sg.vpad,) +
                                  full_state.shape[2:])
        return jax.vmap(lambda old, g: self._part_step(flat, old, g))(
            local_state, g_local)

    def _owner_step(self, state, g):
        """One owner-exchange iteration for the locally-held rows
        (single device: all parts; under shard_map: this device's)."""
        d = self.delivery
        with jax.named_scope("lux_gen_exchange"):
            red = d.owner_generate(state, self._msg, g)
        red = d.owner_pairs(red, state, self._msg, g)
        return jax.vmap(self._apply_epilogue)(state, red, g)

    # -- full step over all parts -------------------------------------

    def _build_step(self):
        """Builds self._graph_args and the un-jitted core
        step(state, *graph_args); returns a jitted single-step wrapper.

        Graph arrays are always passed as ARGUMENTS, never closed over:
        closing over them would bake hundreds of MB of edge indices
        into the XLA program as constants.
        """
        keys = sorted(self.arrays)
        self._graph_keys = keys
        self.graph_args = tuple(self.arrays[k] for k in keys)

        owner = self.exchange == "owner"

        def core(state, *gargs):
            g = dict(zip(keys, gargs))
            if owner:       # no state all_gather (delivery.owner_*)
                return self._owner_step(state, g)
            full = state
            if self.mesh is not None:
                # The per-iteration vertex-state exchange over ICI.
                with jax.named_scope("lux_exchange"):
                    full = jax.lax.all_gather(state, PARTS_AXIS,
                                              tiled=True)
            return self._parts_step(state, full, g)

        if self.mesh is not None:
            P = PartitionSpec
            core = jax.shard_map(
                core, mesh=self.mesh,
                in_specs=(P(PARTS_AXIS),) * (1 + len(keys)),
                out_specs=P(PARTS_AXIS))
        if self.program.name:
            core = jax.named_scope(f"lux_{self.program.name}")(core)
        self._step_core = core
        jitted = jax.jit(core, donate_argnums=0)
        self._register_variant(
            "step", jitted,
            lambda: (self._audit_state_sds, *self.graph_args))
        return lambda state: jitted(state, *self.graph_args)

    # -- static-audit surface (engine/auditable.py) --------------------

    # every lazily compiled loop variant, forced (built, not
    # compiled) so the registry is complete for a full audit
    _AUDIT_LAZY = ("_run_fused", "_run_stats_fused", "_run_until",
                   "_run_until_stats", "_run_health_fused",
                   "_run_until_health")

    @functools.cached_property
    def _audit_state_sds(self):
        """Abstract stand-in for the iterated state (shape and dtype,
        no device placement): from ``jax.eval_shape`` of the device
        init program where the program has one, with nothing
        materialized.  Otherwise from the program's host init, which
        is STASHED for the next ``init_state`` call, so an
        audited-then-run engine (bench.py -audit) pays for exactly
        one host init, same as an unaudited one."""
        if self._init_program is not None:
            program, keys = self._init_program
            st = jax.eval_shape(program, *(self.arrays[k] for k in keys))
        else:
            st = self._pending_init = np.asarray(
                self.program.init(self.sg))
        return jax.ShapeDtypeStruct(st.shape, st.dtype)

    # -- public API ---------------------------------------------------

    def pure_step(self, state, *graph_args):
        """Un-jitted step taking the graph arrays as ARGUMENTS (pass
        ``*engine.graph_args``), so embedding jits don't bake hundreds
        of MB of edge indices in as constants (mesh=None engines)."""
        if self.mesh is not None:
            raise ValueError("pure_step is for single-device engines")
        return self._step_core(state, *graph_args)

    def step(self, state):
        """One iteration (compiled)."""
        return self._step_fn(state)

    @functools.cached_property
    def _run_fused(self):
        core = self._step_core

        @functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
        def run(state, num_iters, *gargs):
            return jax.lax.fori_loop(
                0, num_iters, lambda _, s: core(s, *gargs), state)

        self._register_variant(
            "run", run,
            lambda: (self._audit_state_sds, 3, *self.graph_args))
        return lambda state, n: run(state, n, *self.graph_args)

    def run(self, state, num_iters: int, fused: bool = True,
            seg_budget: float | None = None):
        """num_iters iterations; fused=True compiles the whole loop into
        one XLA program (no host round-trips).  seg_budget (seconds)
        instead runs duration-budgeted fused segments
        (segmented.DurationBudget) so each XLA execution stays under
        the budget — the systematic form of the old hand-picked
        small-``ni`` routing."""
        if seg_budget is not None:
            from lux_tpu.segmented import DurationBudget, run_segments
            return run_segments(self, state, num_iters,
                                DurationBudget(seg_budget))
        if fused:
            if self.health:
                from lux_tpu import health as hw
                state, _it, _rb, _cb, _rbp, _cbp, h = \
                    self.run_health(state, num_iters)
                hw.ensure_ok(h, engine="pull", where="pull run")
                return state
            return self._run_fused(state, num_iters)
        for _ in range(num_iters):
            state = self.step(state)
        return state

    def _iter_counters(self, new, old):
        """Per-iteration device-side counters shared by the stats
        loops: (max-abs state change — the residual run_until
        converges on, count of vertices whose state changed), PLUS
        the round-13 per-part split (residual per part [P] float32,
        changed vertices per part [P] uint32).  The scalars are
        derived FROM the per-part rows (max of maxes / sum of sums),
        so max-over-parts and sum-over-parts are bitwise-exact by
        construction.  Computed on the sharded global arrays like
        _run_until's residual; O(state), tiny next to the O(edges)
        gather — and NO gathers at all (audit gather-budget holds)."""
        d = jnp.abs(new.astype(jnp.float32) - old.astype(jnp.float32))
        res_p = jnp.max(d.reshape(d.shape[0], -1), axis=1)     # [P]
        if d.ndim > 2:                        # K-vector payloads
            d = d.reshape(d.shape[0], d.shape[1], -1).max(axis=-1)
        chg_p = jnp.sum((d > 0).astype(jnp.uint32), axis=1)    # [P]
        return jnp.max(res_p), jnp.sum(chg_p), res_p, chg_p

    def _stats_bufs(self):
        cap, P = self.stats_cap, self.sg.num_parts
        return (jnp.zeros((cap,), jnp.float32),
                jnp.zeros((cap,), jnp.uint32),
                jnp.zeros((cap, P), jnp.float32),
                jnp.zeros((cap, P), jnp.uint32))

    @functools.cached_property
    def _run_stats_fused(self):
        core = self._step_core

        @functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
        def run(state, num_iters, *gargs):
            def body(i, c):
                s, res, chg, resp, chgp = c
                new = core(s, *gargs)
                r, cnt, rp, cp = self._iter_counters(new, s)
                return (new, res.at[i].set(r, mode="drop"),
                        chg.at[i].set(cnt, mode="drop"),
                        resp.at[i].set(rp, mode="drop"),
                        chgp.at[i].set(cp, mode="drop"))

            return jax.lax.fori_loop(
                0, num_iters, body, (state, *self._stats_bufs()))

        self._register_variant(
            "run_stats", run,
            lambda: (self._audit_state_sds, 3, *self.graph_args))
        return lambda state, n: run(state, n, *self.graph_args)

    def run_stats(self, state, num_iters: int):
        """``run(fused=True)`` + device-side iteration counters
        accumulated inside the fori_loop: returns (state, residual
        float32 [stats_cap], changed uint32 [stats_cap], residual
        per part float32 [stats_cap, P], changed per part uint32
        [stats_cap, P]) where residual[i] is iteration i's max-abs
        state change and changed[i] its changed-vertex count (see
        lux_tpu/telemetry.py; writes past stats_cap drop).  The
        per-part counters are the imbalance-attribution signal:
        scalar = max/sum over the per-part row, bitwise
        (tests/test_telemetry.py holds the NumPy per-part oracle).
        Fetch the buffers once per run/segment — a few KB,
        independent of graph size."""
        return self._run_stats_fused(state, num_iters)

    @functools.cached_property
    def _run_until(self):
        core = self._step_core

        @functools.partial(jax.jit, donate_argnums=0)
        def run(state, tol, max_iters, *gargs):
            def cond(c):
                it, s, res = c
                # NOT (res <= tol), never (res > tol): a NaN residual
                # compares False BOTH ways, and the latter would exit
                # the loop reporting convergence on a garbage state
                # (round-9 tentpole).  Non-finite residuals keep
                # iterating until max_iters; run_until_health trips
                # the watchdog on them immediately.
                return jnp.logical_not(res <= tol) & (it < max_iters)

            def body(c):
                it, s, _ = c
                new = core(s, *gargs)
                res = jnp.max(jnp.abs(new.astype(jnp.float32) -
                                      s.astype(jnp.float32)))
                return it + 1, new, res

            it, s, res = jax.lax.while_loop(
                cond, body, (jnp.int32(0), state, jnp.float32(jnp.inf)))
            return s, it, res

        self._register_variant(
            "run_until", run,
            lambda: (self._audit_state_sds,
                     jax.ShapeDtypeStruct((), jnp.float32),
                     jax.ShapeDtypeStruct((), jnp.int32),
                     *self.graph_args))
        return run

    @functools.cached_property
    def _run_until_stats(self):
        core = self._step_core

        @functools.partial(jax.jit, donate_argnums=0)
        def run(state, tol, max_iters, *gargs):
            def cond(c):
                it, s, res = c[:3]
                # non-finite-safe, see _run_until's cond
                return jnp.logical_not(res <= tol) & (it < max_iters)

            def body(c):
                it, s, _res, rb, cb, rbp, cbp = c
                new = core(s, *gargs)
                r, cnt, rp, cp = self._iter_counters(new, s)
                return (it + 1, new, r,
                        rb.at[it].set(r, mode="drop"),
                        cb.at[it].set(cnt, mode="drop"),
                        rbp.at[it].set(rp, mode="drop"),
                        cbp.at[it].set(cp, mode="drop"))

            it, s, res, rb, cb, rbp, cbp = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), state, jnp.float32(jnp.inf),
                 *self._stats_bufs()))
            return s, it, res, rb, cb, rbp, cbp

        self._register_variant(
            "run_until_stats", run,
            lambda: (self._audit_state_sds,
                     jax.ShapeDtypeStruct((), jnp.float32),
                     jax.ShapeDtypeStruct((), jnp.int32),
                     *self.graph_args))
        return run

    def run_until_stats(self, state, tol: float,
                        max_iters: int = np.iinfo(np.int32).max):
        """``run_until`` + the per-iteration residual/changed counters
        of ``run_stats`` (per-part counters included, same oracle
        contract) — closing the 'pull residuals are invisible inside
        run_until' observability hole.  Returns (state, it, residual,
        residual_buf, changed_buf, residual_parts, changed_parts)."""
        return self._run_until_stats(state, jnp.float32(tol),
                                     jnp.int32(max_iters),
                                     *self.graph_args)

    def run_until(self, state, tol: float,
                  max_iters: int = np.iinfo(np.int32).max):
        """Iterate until the max-abs change of the STATE (whatever
        the program iterates — e.g. pagerank's degree-scaled ranks)
        falls to ``tol``, or max_iters, entirely inside one XLA
        program — convergence-driven runs the reference lacks (fixed
        -ni only, reference pagerank.cc:109-114).  Returns
        (state, iterations, final_residual) as device scalars."""
        return self._run_until(state, jnp.float32(tol),
                               jnp.int32(max_iters), *self.graph_args)

    # -- health-watchdog loop variants (lux_tpu/health.py) -------------

    @functools.cached_property
    def _run_health_fused(self):
        """run_stats + the in-loop health word: a while_loop (num_iters
        is a traced argument — one compiled program for every segment
        size) whose condition ALSO exits the iteration after a check
        trips, so a diverging run stops burning device time the moment
        the watchdog sees it."""
        from lux_tpu import health as hw
        core = self._step_core

        @functools.partial(jax.jit, donate_argnums=0)
        def run(state, num_iters, h0, win0, *gargs):
            def cond(c):
                it, h = c[0], c[6]
                return (it < num_iters) & (h[0] == 0)

            def body(c):
                it, s, rb, cb, rbp, cbp, h, win = c
                new = core(s, *gargs)
                r, cnt, rp, cp = self._iter_counters(new, s)
                h, win = hw.pull_update(h, win, new, r)
                return (it + 1, new, rb.at[it].set(r, mode="drop"),
                        cb.at[it].set(cnt, mode="drop"),
                        rbp.at[it].set(rp, mode="drop"),
                        cbp.at[it].set(cp, mode="drop"), h, win)

            it, s, rb, cb, rbp, cbp, h, win = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), state, *self._stats_bufs(), h0, win0))
            return s, it, rb, cb, rbp, cbp, h, win

        def call(state, n, watch=None):
            if watch is None:
                watch = (hw.init_word(), hw.init_window())
            s, it, rb, cb, rbp, cbp, h, win = run(
                state, jnp.int32(n), *watch, *self.graph_args)
            return s, it, rb, cb, rbp, cbp, (h, win)

        self._register_variant(
            "run_health", run,
            lambda: (self._audit_state_sds,
                     jax.ShapeDtypeStruct((), jnp.int32),
                     hw.init_word(), hw.init_window(),
                     *self.graph_args))
        return call

    def run_health(self, state, num_iters: int, watch=None):
        """``run_stats`` under the device-side health watchdog
        (per-part counters included, same oracle contract): returns
        (state, iters_executed, residual_buf, changed_buf,
        residual_parts, changed_parts, watch) where watch = (health
        int32[6], residual window).  The
        loop EXITS the iteration a check trips (iters_executed <
        num_iters then); fetch + decode the word once per run/segment
        with ``health.ensure_ok(watch)`` — 24 bytes, no in-loop host
        syncs.  Pass the previous segment's ``watch`` back in so the
        trailing-window checks keep their history across segment
        boundaries.  Compiled lazily; the watchdog-free programs are
        untouched."""
        return self._run_health_fused(state, num_iters, watch)

    @functools.cached_property
    def _run_until_health(self):
        from lux_tpu import health as hw
        core = self._step_core

        @functools.partial(jax.jit, donate_argnums=0)
        def run(state, tol, max_iters, *gargs):
            def cond(c):
                it, res, h = c[0], c[2], c[7]
                return (jnp.logical_not(res <= tol)
                        & (it < max_iters) & (h[0] == 0))

            def body(c):
                it, s, _res, rb, cb, rbp, cbp, h, win = c
                new = core(s, *gargs)
                r, cnt, rp, cp = self._iter_counters(new, s)
                h, win = hw.pull_update(h, win, new, r)
                return (it + 1, new, r,
                        rb.at[it].set(r, mode="drop"),
                        cb.at[it].set(cnt, mode="drop"),
                        rbp.at[it].set(rp, mode="drop"),
                        cbp.at[it].set(cp, mode="drop"), h, win)

            it, s, res, rb, cb, rbp, cbp, h, win = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), state, jnp.float32(jnp.inf),
                 *self._stats_bufs(), hw.init_word(),
                 hw.init_window()))
            return s, it, res, rb, cb, rbp, cbp, h, win

        self._register_variant(
            "run_until_health", run,
            lambda: (self._audit_state_sds,
                     jax.ShapeDtypeStruct((), jnp.float32),
                     jax.ShapeDtypeStruct((), jnp.int32),
                     *self.graph_args))
        return run

    def run_until_health(self, state, tol: float,
                         max_iters: int = np.iinfo(np.int32).max):
        """``run_until_stats`` under the health watchdog (per-part
        counters included, same oracle contract): returns (state, it,
        residual, residual_buf, changed_buf, residual_parts,
        changed_parts, watch) with watch = (health int32[6], residual
        window).  The non-finite-safe predicate means a NaN residual
        can never report convergence; the watchdog additionally stops
        the loop at the tripping iteration instead of spinning to
        max_iters."""
        s, it, res, rb, cb, rbp, cbp, h, win = self._run_until_health(
            state, jnp.float32(tol), jnp.int32(max_iters),
            *self.graph_args)
        return s, it, res, rb, cb, rbp, cbp, (h, win)

    def unpad(self, state) -> np.ndarray:
        """Padded device state -> [nv, ...] user order (host).
        Multi-host runs gather remote shards over the process group.
        Leaves a ``state.fetch`` span (``bytes``: what came to the
        host) whose two children cover it: ``state.fetch.get`` (device
        to host, the same ``bytes``) and ``state.fetch.unpad``
        (``from_padded``; ``bytes`` of its result)."""
        from lux_tpu.parallel.multihost import fetch_global
        with telemetry.span("state.fetch") as sp:
            with telemetry.span("state.fetch.get") as get:
                host = fetch_global(state)
                get.count(bytes=host.nbytes)
            sp.count(bytes=host.nbytes)
            with telemetry.span("state.fetch.unpad") as unpad:
                out = self.sg.from_padded(host)
                unpad.count(bytes=out.nbytes)
            return out


def _check_local_parts(sg, mesh, pair_threshold):
    """Validate a local-parts (multi-host) ShardedGraph against the
    mesh: the materialized rows must be exactly the rows this process's
    devices hold under the parts sharding."""
    if sg.local_parts is None:
        return
    if mesh is None:
        raise ValueError(
            "a ShardedGraph built with parts= (multi-host local rows) "
            "requires a mesh")
    # pair_threshold IS supported with local-parts builds: the pair
    # planner lays each process's rows out against a process-group-
    # allreduced common depth profile (plan_sharded_pairs)
    del pair_threshold
    from lux_tpu.parallel.mesh import local_part_rows
    expect = local_part_rows(mesh, sg.num_parts)
    got = list(np.asarray(sg.local_parts))
    if got != expect:
        raise ValueError(
            f"local_parts {got} != this process's sharding rows "
            f"{expect}; build with parts=multihost.process_parts(P)")
