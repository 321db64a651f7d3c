"""The push engine: frontier-driven label propagation to convergence.

The reference's push model (reference core/push_model.inl,
sssp_gpu.cu:335-522) keeps per-partition frontier queues with
dense-bitmap/sparse-queue representations, exchanges them through
zero-copy memory each iteration, pipelines SLIDING_WINDOW=4 launches,
and halts when every part's future reports an empty frontier
(sssp.cc:115-129).

The TPU-native design:

- The CANONICAL frontier is a dense boolean mask in the padded
  part-major vertex layout — a shape-stable array that all-gathers
  trivially over ICI (SURVEY.md §7 "sparse frontiers" hard part).
- Each iteration picks one of two execution strategies with a real
  ``lax.cond`` branch (the analogue of the reference's adaptive
  pull/push switch on ``frontier > nv/16``, sssp_gpu.cu:414-421):
  * DENSE: masked pull over every edge — inactive sources contribute
    the reduction identity (tiled scatter-free segment reduction).
  * SPARSE: compact the mask into capacity-bounded padded queues of
    (vertex, label) pairs, exchange the queues (all-gather over ICI —
    O(queue) bytes, not O(nv)), and relax only the frontier's
    out-edges through the src-sorted CSR view (engine/frontier.py).
  The cond predicate is replicated (a psum), so the branch stays a
  branch — it is deliberately hoisted OUTSIDE the per-part vmap,
  where it would decay into select-both-sides.
- The sparse branch has a third outcome, BOTTOM-UP (Beamer's
  direction-optimizing BFS): a frontier too wide for the queue still
  runs on it where the UNREACHED vertices fit instead.  The queue
  then holds those, and each looks at its own neighbours for a
  reached one: the budget stage takes a slot's candidate from the
  neighbour's label and reduces it into the queue item that owns the
  slot, where top-down it takes the item's label and scatters into
  the neighbour.  Same stages, same compiled ladder, the direction a
  replicated flag.  It is chosen only where the choice was DENSE and
  three conditions hold, all observed by the loop (``_choose``), none
  an option:
  * the src-sorted view is also every vertex's in-edge list: the
    graph is SYMMETRIC (decided once on the host,
    ``ShardedGraph.edges_symmetric``; any other graph builds no such
    step and keeps its program);
  * no relaxation into a REACHED vertex can improve it: the best
    candidate the frontier can offer (its best label, relaxed over
    the view's extreme weights) is not better than the worst label a
    reached vertex holds (hops: ``max_reached <= min_active + 1``).
    Then everything the frontier can still improve is unreached, an
    unreached vertex's extent holds the mirror of every edge into it,
    and a reached neighbour that is not active was expanded with the
    label it has, so pulling from every neighbour finds exactly what
    pushing from the active ones would;
  * the unreached vertices that have an edge fit WITHOUT truncation:
    the largest part's count of them the top queue rung, their edges
    the top budget rung.  A truncated pull would leave an item with
    a label from some of its neighbours and nobody to revisit it.
  Components (no label ever at the identity) and weighted SSSP away
  from level-like fronts never meet them; the delta schedule builds
  no step (a bucket's front leaves reached vertices unexpanded behind
  it).
- A sparse iteration costs per SLOT of its two static shapes (queue
  and edge budget), filled or not, so both are the top of a short
  ladder of rungs and each iteration runs on the smallest rungs that
  hold its frontier's count and out-edge total — quantities the loop
  observes, no option (engine/frontier.py).  Lower rungs never
  truncate: the iteration sequence is the top rung's.  How FULL the
  slots were is counted beside ``sparse_iters`` / ``low_rung_iters``
  / ``pull_iters`` in the loops' carry and left on the
  ``push.converge`` mark: over a call's sparse iterations
  ``queue_items`` (vertices the queue stage compacted, all parts) and
  ``queue_slots`` (the queue rungs they ran on x parts),
  ``budget_edges`` (edges the budget stage expanded: a part's
  out-edge total, or the budget where it truncated) and
  ``budget_slots`` (the budget rungs x parts).  The carry keeps each
  in two uint32 scalar words (``frontier.wide_add``: hundreds of
  iterations on a 12 M-slot rung pass 2^32) and the mark folds them
  into ONE number when the ring is read (``frontier.Folded``).  Engines
  without a ladder (batched, ``enable_sparse=False``) carry none and
  mark zeros.
- Under ``delta`` the loop is the bucket schedule (delta-stepping): a
  trip relaxes the current bucket's front, or, where that is empty,
  only raises the bucket bound.  A relax trip chooses its branch by
  the front's OUT-EDGES as well as its vertex count: the bound keeps
  the front's count under the queue's limit while the few vertices
  under it may be hubs, and a front whose out-edges pass the top
  budget rung would be relaxed a truncated prefix a trip, trip after
  trip, each at a dense trip's price; it takes the dense branch,
  whole, once (the plain loop keeps ``_choose``'s count alone: there
  a truncated level is a cheap head start and the next front runs
  dense anyway; PERF.md section 6, PR 44).  The front mask, its
  count, its out-edge total, the active minimum and the advance lie
  under the scope ``lux_bucket``, and the mark carries ``advances``
  (trips that relaxed nothing: ``iters + advances`` = trips),
  ``front_edges`` (the edges the relax trips relaxed, summed: a
  dense trip its front's out-edges, a sparse one what its budget
  stage expanded; two words, as the fills), ``front_vertices`` (the
  vertices of those fronts, summed the same way: what a relax trip
  holds, against the static shapes it pays), ``graph_edges`` (the
  stored edges, once a call): how often the schedule re-relaxes an
  edge, against the trips it pays; and ``edge_dense_iters`` (relax
  trips whose front fit the queue by vertex count and ran dense for
  its out-edges: ``iters`` = ``sparse_iters`` + dense by count +
  these).
  An engine without ``delta`` carries none of them and marks zeros.
- Sparse overflow safety: when a frontier's out-edges exceed the
  static edge budget, the un-expanded queue suffix simply STAYS
  ACTIVE (the globally-agreed processed prefix is cleared via a
  pmin), so truncation degrades performance, never correctness —
  the reference instead re-densifies on queue overflow
  (sssp_gpu.cu:485-490).
- The ENTIRE convergence run is one XLA program: ``lax.while_loop``
  whose predicate is a ``psum`` of active counts.  There is no
  device->host sync per iteration at all, so the reference's
  SLIDING_WINDOW=4 latency-hiding trick is unnecessary by
  construction.
"""

from __future__ import annotations

import dataclasses
import functools

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from lux_tpu import telemetry
from lux_tpu.engine import frontier as fr
from lux_tpu.engine.auditable import AuditableEngine
from lux_tpu.engine.delivery import Delivery
from lux_tpu.engine.program import vmask_of
from lux_tpu.graph import ShardedGraph
from lux_tpu.parallel.mesh import PARTS_AXIS, shard_over_parts
from lux_tpu.partition import frontier_capacity


# The sparse iteration's ladder (engine/frontier.py): the lower rungs
# of the queue and of the edge budget, as divisors of the top one.
# One lower rung each: a rung more is a compiled copy more of its
# stage for every rung of the other shape (PERF.md, PR 29).
QUEUE_RUNG_DIVISORS = (8,)
BUDGET_RUNG_DIVISORS = (16,)


@dataclasses.dataclass(frozen=True)
class PushProgram:
    """Monotone label-propagation program.

    reduce    'min' (SSSP/BFS) or 'max' (components) — the atomicMin/
              atomicMax of the reference's process_edge (sssp_gpu.cu:
              48-82, components_gpu.cu:57-59).
    relax     (src_label [epad], weight [epad]|None) -> candidate label
              offered to the edge's destination.
    identity  scalar no-op candidate (+inf for min, -inf/0 for max).
    init      (sharded_graph) -> (label0 [num_parts, vpad],
              active0 bool [num_parts, vpad]) numpy.
    name      optional app label; engines scope their traced step in
              ``jax.named_scope(f"lux_{name}")`` so profiler captures
              (profiling.trace) attribute device ops to the app.
    batch     query-batch width B when labels/active carry a trailing
              query axis ``[vpad, B]`` (None = single-query).  Each
              column is one independent query: its active mask is its
              frontier, a retired (converged) column is all-inactive
              and contributes the reduce identity through the same
              pre-gather mask as any inactive source — ONE label
              gather serves all B queries (audit gather-budget).
              Batched engines run every iteration DENSE (per-query
              sparse queues are not implemented) and reject
              delta-stepping and pair-lane delivery.
    """
    reduce: str
    relax: Callable
    identity: Any
    init: Callable
    name: str | None = None
    batch: int | None = None

    def better(self, cand, old):
        return cand < old if self.reduce == "min" else cand > old


class PushEngine(AuditableEngine):
    """Compiled frontier iterations for one ShardedGraph + PushProgram.

    Construction leaves the spans ``build.pair_plan`` (counts
    pair_edges, residual_edges), ``build.dense_layout`` and
    ``build.sparse_view`` (telemetry.span); on a single device
    ``build.dense_layout`` also hands each array to the device as it
    is built (to dispatch, not to arrival)."""

    def __init__(self, sg: ShardedGraph, program: PushProgram, mesh=None,
                 layout: str = "tiled", tile_e: int = 512,
                 use_mxu: bool | str = "auto",
                 enable_sparse: bool = True,
                 sparse_threshold: int = 16,
                 edge_budget: int | None = None,
                 delta: float | None = None,
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
        # query-batched labels [vpad, B] (program.batch = B): dense
        # masked iterations only — columns retire independently
        # through their own active masks; sparse queues, delta
        # buckets and pair rows are single-query machinery
        self.batch = getattr(program, "batch", None)
        if self.batch is not None:
            if delta is not None:
                raise ValueError(
                    "delta-stepping is single-query (one scalar "
                    "bucket bound); build batched engines with "
                    "delta=None")
            enable_sparse = False
        if delta is not None:
            if program.reduce != "min":
                raise ValueError("delta-stepping requires a 'min' program")
            # validate in the LABEL dtype: a fractional delta truncates
            # to 0 on int32 hop labels and would spin the bucket loop
            # forever without progress
            ldt = np.asarray(program.identity).dtype
            if not float(np.asarray(delta, ldt)) > 0:
                raise ValueError(
                    f"delta-stepping bucket width {delta!r} is not > 0 "
                    f"in label dtype {ldt}")
        # The DENSE iterations' delivery (engine/delivery.py).  Pair
        # rows and paged plans serve those only: the SPARSE path below
        # keeps the FULL graph's src-sorted view — frontier expansion
        # must see every edge — and the MXU flag rides its CSR-expand
        # too (fr.expand_frontier use_mxu).
        self.delivery, arrays = Delivery.build(
            sg, program, mesh, layout=layout, tile_e=tile_e,
            use_mxu=use_mxu, reduce_method=reduce_method,
            pair_threshold=pair_threshold, pair_min_fill=pair_min_fill,
            pair_stream=pair_stream, stream_msgs=stream_msgs,
            exchange=exchange, gather=gather,
            owner_tile_e=owner_tile_e,
            owner_minmax_fused=owner_minmax_fused)
        self.sg = sg
        self.program = program
        self.mesh = mesh
        self.delta = delta
        # health=True: run()/segmented drivers use the watchdog loop
        # variant (converge_health, compiled lazily); False leaves
        # every watchdog-free program untouched
        self.health = bool(health)
        self.stats_cap = telemetry.DEFAULT_STATS_CAP
        self.sparse_threshold = sparse_threshold
        dev = jnp.asarray if mesh is None else np.asarray
        self.enable_sparse = enable_sparse
        # whether the bottom-up step is built (decided below, where
        # the sparse view is: _sparse_mode), and the view's extreme
        # weights for its guard (unweighted: relax takes None)
        self.pull = False
        self._weight_ends = (None,)
        if enable_sparse:
            # The compressed source index's pad size is a compiled
            # SHAPE: on multi-host runs agree on the max across every
            # process's parts.
            with telemetry.span("build.sparse_view"):
                s_pad = sg.src_unique_max()
                if jax.process_count() > 1:
                    from jax.experimental import multihost_utils
                    s_pad = int(np.max(
                        multihost_utils.process_allgather(
                            np.asarray([s_pad]))))
                ss = sg.src_sorted(s_pad=s_pad)
                # The bottom-up step walks this view as every
                # vertex's IN-edge list, which it is on a symmetric
                # graph only; any other graph keeps the program it
                # had.  Off under the delta schedule: a bucket's front
                # leaves reached vertices unexpanded behind it, so the
                # step's guard (module docstring) would seldom hold.
                # The out-edge total the loop sums is uint32.
                self.pull = (delta is None and sg.ne < 2 ** 32
                             and program.reduce in ("min", "max")
                             and sg.edges_symmetric())
            # Reference queue sizing rule (push_model.inl:393-397).
            self.queue_cap = frontier_capacity(sg.vpad, sparse_threshold)
            # The edge budget must cover any single vertex's out-edges
            # within one part, or a truncated hub could make zero
            # progress forever (see module docstring).  It is a STATIC
            # shape, so on local-parts (multi-host) builds it must not
            # depend on which parts this process holds — bound it by
            # the global max out-degree instead.
            if sg.local_parts is not None:
                max_deg = int(sg.max_out_degree) or 1
            else:
                max_deg = sg.max_in_deg() or 1
            default_eb = max(1024, sg.epad // sparse_threshold)
            self.edge_budget = int(edge_budget if edge_budget is not None
                                   else max(default_eb, max_deg + 128))
            # Both are the TOP of a ladder of static shapes; an
            # iteration runs on the smallest rungs that hold its
            # frontier (_sparse_parts).  Only the top rung truncates,
            # so only it must cover a hub.
            self.queue_rungs = fr.rungs(self.queue_cap,
                                        QUEUE_RUNG_DIVISORS)
            self.budget_rungs = fr.rungs(self.edge_budget,
                                         BUDGET_RUNG_DIVISORS)
            arrays = dict(arrays,
                          # (as the row search's tree, built once)
                          src_ids=dev(np.stack(
                              [fr.row_table(ids)
                               for ids in ss["src_ids"]])),
                          src_off=dev(ss["src_off"]),
                          ss_dst=dev(ss["ss_dst"]),
                          part_start=dev(
                              sg.starts[sg.part_ids()].astype(
                                  np.int32)[:, None]))
            if ss["ss_weight"] is not None:
                arrays["ss_weight"] = dev(ss["ss_weight"])
                if self.pull:
                    # the view's extreme weights, for the step's guard
                    real = [w[:int(n)] for w, n in zip(
                        ss["ss_weight"], sg.ne_part[sg.part_ids()])]
                    self._weight_ends = (
                        min(w.min(initial=np.inf) for w in real),
                        max(w.max(initial=-np.inf) for w in real))
        if mesh is not None:
            arrays = shard_over_parts(mesh, arrays, sg.num_parts)
        self.arrays = arrays
        # compiled-variant registry for the static program auditor
        # (lux_tpu/audit.py): name -> (jitted fn, example-args thunk)
        self._audit_variants: dict = {}
        self._step_fn = self._build(converge=False)
        self._converge_fn = self._build(converge=True)
        if audit is not None:
            # mode validation lives in audit_engine (typed ValueError
            # on anything but 'warn'/'error')
            from lux_tpu import audit as _audit
            _audit.audit_engine(self, mode=audit)

    # ------------------------------------------------------------------

    def init_state(self):
        """Fresh (label, active) on the engine's devices, under a
        ``state.init`` span (``bytes``) whose two children cover it:
        ``state.init.build`` (the host makes the arrays) and
        ``state.init.put`` (ends at dispatch), ``bytes`` on each."""
        with telemetry.span("state.init") as sp:
            with telemetry.span("state.init.build") as build:
                pending = self._consume_pending_init()
                if pending is not None:
                    label0, active0 = pending
                else:
                    label0, active0 = self.program.init(self.sg)
                nbytes = label0.nbytes + active0.nbytes
                build.count(bytes=nbytes)
            sp.count(bytes=nbytes)
            with telemetry.span("state.init.put", bytes=nbytes):
                return self._place(label0, active0)

    def place(self, label, active):
        """Put host (or replicated) state arrays on the engine's
        devices with the parts sharding (used by checkpoint resume).
        Like PullEngine.place, this is the elastic re-placement entry
        point: the global ``[P, vpad]`` label/active views re-shard
        onto whatever mesh THIS engine was built over (round 11).
        Leaves a ``state.place`` span (``bytes``); the transfer is
        asynchronous, so the span ends at dispatch, not at arrival."""
        with telemetry.span("state.place",
                            bytes=label.nbytes + active.nbytes):
            return self._place(label, active)

    def _place(self, label, active):
        self._drop_pending_init()     # resume never needs the probe
        if self.mesh is not None:
            return tuple(shard_over_parts(
                self.mesh, [np.asarray(label), np.asarray(active)],
                self.sg.num_parts))
        return jnp.asarray(label), jnp.asarray(active)

    # -- dense iteration over this device's parts ----------------------

    def _dense_flat(self, full_label, full_active):
        """Phase 1 (exchange): mask inactive sources to the identity
        BEFORE the per-edge gather — one gather instead of two (the
        gather is ~90% of a dense iteration, PERF_NOTES.md), with
        identical semantics: relax(identity) stays absorbing for
        min/max programs.  Batched labels [.., vpad, B] keep their
        query axis: the flat table is [P*vpad, B] and the SAME single
        gather fetches all B columns per edge (a retired column is
        all-inactive, so it contributes the identity here exactly
        like any masked source — the sentinel convention per query)."""
        ident_l = jnp.asarray(self.program.identity, full_label.dtype)
        masked = jnp.where(full_active, full_label, ident_l)
        return masked.reshape((-1,) + masked.shape[2:])

    def _msg(self, label_dtype):
        """The delivery's message function: relax, with masked
        (identity) sources mapped back to the identity so they stay
        absorbing whatever relax does to them."""
        prog = self.program
        ident_l = jnp.asarray(prog.identity, label_dtype)

        def msg(vals, w):
            c = prog.relax(vals, w)
            return jnp.where(vals == ident_l,
                             jnp.asarray(prog.identity, c.dtype), c)

        return msg

    def _dense_cand(self, flat_l, g):
        """Phase 2 (relax): per-edge source gather + candidates."""
        cand = self.delivery.messages(flat_l, self._msg(flat_l.dtype),
                                      g)
        return jax.lax.optimization_barrier(cand)

    def _dense_red(self, flat_l, cand, g):
        """Phase 3 (reduce) -> [vpad, ...].  cand=None: the delivery
        fuses gather+relax+reduce (streamed chunk blocks, the
        billion-edge memory mode, PERF_NOTES ledger; paged rows)."""
        d, msg = self.delivery, self._msg(flat_l.dtype)
        if cand is None:
            return d.reduce_fused(flat_l, msg, g)
        return d.reduce(flat_l, cand, msg, g)

    def _dense_update(self, old, red, g):
        """Phase 4 (update): keep improvements, flag the new frontier
        (per query on batched labels — the [vpad] vertex mask
        broadcasts over the trailing query axis)."""
        vm = vmask_of(g, self.sg.vpad)
        vm = vm.reshape(vm.shape + (1,) * (red.ndim - 1))
        improved = self.program.better(red, old) & vm
        return jnp.where(improved, red, old), improved

    def _dense_g(self, g):
        """The dense iteration's arrays (the delivery's) out of the
        step's: the sparse view's never ride its vmap."""
        return {k: g[k] for k in self.delivery.keys}

    def _dense_parts(self, label, active, full_label, full_active, g):
        with jax.named_scope("lux_exchange"):
            flat_l = self._dense_flat(full_label, full_active)
        fused = self.delivery.fused

        def one(old, g):
            with jax.named_scope("lux_relax"):
                cand = None if fused else self._dense_cand(flat_l, g)
            with jax.named_scope("lux_reduce"):
                red = self._dense_red(flat_l, cand, g)
            with jax.named_scope("lux_update"):
                return self._dense_update(old, red, g)

        return jax.vmap(one)(label, self._dense_g(g))

    def _dense_parts_owner(self, label, active, g):
        """One dense iteration with owner-side message generation
        (delivery.owner_generate): each LOCAL source part masks its own
        label shard (inactive -> identity, exactly _dense_flat's
        one-gather trick applied per shard) — no label/active
        all_gather at all (except for pair rows)."""
        d = self.delivery
        ident_l = jnp.asarray(self.program.identity, label.dtype)
        masked = jnp.where(active, label, ident_l)
        msg = self._msg(label.dtype)
        with jax.named_scope("lux_gen_exchange"):
            red = d.owner_generate(masked, msg, g)
        red = d.owner_pairs(red, masked, msg, g)
        return jax.vmap(self._dense_update)(label, red,
                                            self._dense_g(g))

    # -- sparse iteration ----------------------------------------------

    def _sparse_parts(self, label, active, need, g, gather_fn,
                      pmin_fn, pmax_fn, psum_fn, pull=None,
                      unreached=None):
        """One frontier-queue iteration over this device's parts, on
        the smallest static shapes that hold the queue (the ladder,
        engine/frontier.py) -> (label, active, 1 if the edge budget
        was a lower rung else 0, fill): fill = four uint32 scalars,
        how full the iteration's slots were: (queue_items, queue_slots,
        budget_edges, budget_slots), the vertices compacted and the
        edges expanded (a part's out-edge total, or the budget where
        it truncated) summed over ALL parts, beside the rungs they ran
        on x parts.

        need is what the queue must hold: the frontier's global size
        (it fits the top queue: _sparse_mode).  gather_fn concatenates
        per-part queue arrays across the whole mesh (identity +
        reshape on a single device); pmin_fn / pmax_fn / psum_fn reduce
        across the mesh.  Both rung indices are replicated scalars picked
        OUTSIDE the per-part vmap, where a switch would decay into
        select-every-branch; the branches hold the collectives, so
        every device takes the same.

        pull (engines with the bottom-up step, else None) is the
        replicated flag of _choose: where it is set the queue holds
        ``unreached`` instead of ``active``, need is the largest
        part's count of them, and the budget stage runs the other way
        round on the same slots: a slot's candidate comes from its
        NEIGHBOUR's label and is reduced into the queue ITEM that owns
        the slot (the [P_total * Q] queue behind the labels, combined
        across parts under ``lux_pull`` and written into the items'
        own labels).  One gather and one scatter a slot either way,
        and one scatter of the queue back onto the vertices, selected
        by the flag: the ladder is compiled once.
        """
        sg, prog = self.sg, self.program
        nv = sg.nv
        ssw = g.get("ss_weight")
        pidx = self._part_index()
        queued = active if pull is None else \
            jnp.where(pull, unreached, active)
        rows, cnts = jax.vmap(fr.mask_ranks)(queued)

        def exchanged(fn, x):
            # the sparse branch's collectives under one scope of a
            # trace; on one device fn is an identity and the scope
            # names nothing
            with jax.named_scope("lux_sparse_exchange"):
                return fn(x)

        def on_queue(Q):
            # 1. compact each local part's mask into a (global id,
            #    label) queue.
            def compact(rows, lab, start):
                ids, vals = fr.pick_queue(rows, lab, Q)
                gids = jnp.where(ids < sg.vpad, start[0] + ids, nv)
                return gids.astype(jnp.int32), vals

            gids, vals = jax.vmap(compact)(rows, label,
                                           g["part_start"])

            # 2. exchange queues: [P_total * Q] flat, part-major order
            #    (identical on every device).
            all_gids = exchanged(gather_fn, gids).reshape(-1)
            all_vals = exchanged(gather_fn, vals).reshape(-1)

            # 3. where the gathered queue's edges lie in each part's
            #    compressed src-sorted view, and how many they are:
            #    the most any part holds picks the budget rung.
            begin, off, total = jax.vmap(
                lambda sids, soff: fr.frontier_extents(
                    all_gids, sids, soff, nv))(
                g["src_ids"], g["src_off"])
            most = jnp.max(total)
            eb_rung = fr.rung_index(exchanged(pmax_fn, most),
                                    self.budget_rungs)
            # how full this iteration's slots are (the loops' ``fill``
            # carry): every part's items and expanded edges, one psum.
            # With one local part (every cell) its total IS ``most``:
            # ``total`` keeps the one reader it had
            eb_size = fr.rung_size(eb_rung, self.budget_rungs)
            if total.shape[0] == 1:
                local = [jnp.minimum(cnts[0], Q),
                         jnp.minimum(most, eb_size)]
            else:
                local = [jnp.sum(jnp.minimum(cnts, Q)),
                         jnp.sum(jnp.minimum(total, eb_size))]
            used = exchanged(psum_fn,
                             jnp.stack(local).astype(jnp.uint32))
            fill = (used[0], jnp.uint32(Q * sg.num_parts), used[1],
                    eb_size.astype(jnp.uint32)
                    * jnp.uint32(sg.num_parts))

            # 4. each part relaxes the queue's edges that land in its
            #    partition.  Where the bottom-up step is built the
            #    labels carry the gathered queue's behind them: one
            #    array is table and target whichever way a slot runs.
            #    Top-down a slot reads its item's label there and
            #    reduces into the neighbour; bottom-up it reads the
            #    neighbour (an unreached one offers nothing) and
            #    reduces into its item's slot, which holds the
            #    identity an unreached vertex has.
            wide = label if pull is None else jax.vmap(
                lambda lab: jnp.concatenate([lab, all_vals]))(label)

            def on_budget(EB):
                def relax_part(lab, begin, off, ssd, ssw):
                    # (the two-way slot reads its label off the wide
                    # array, whichever way it runs: none rides along)
                    edge_idx, src_val, in_range, owner = \
                        fr.expand_extents(
                            all_vals if pull is None else None,
                            begin, off, EB, use_mxu=self.use_mxu)
                    dst = jnp.take(ssd, edge_idx, axis=0)
                    w = jnp.take(ssw, edge_idx, axis=0) \
                        if ssw is not None else None
                    if pull is None:
                        cand = prog.relax(src_val, w)
                        cand = jnp.where(
                            in_range & (dst < sg.vpad), cand,
                            jnp.asarray(prog.identity, cand.dtype))
                        dst = jnp.where(in_range, dst, sg.vpad - 1)
                    else:
                        ok = in_range & (dst < sg.vpad)
                        nbr = jnp.where(ok, dst, sg.vpad - 1)
                        item = sg.vpad + owner
                        src_val = jnp.take(
                            lab, jnp.where(pull, nbr, item), axis=0)
                        ok = ok & (src_val != jnp.asarray(
                            prog.identity, lab.dtype))
                        cand = prog.relax(src_val, w)
                        cand = jnp.where(
                            ok, cand,
                            jnp.asarray(prog.identity, cand.dtype))
                        dst = jnp.where(pull, item, nbr)
                    new = fr.scatter_reduce(lab, dst, cand, prog.reduce)
                    # (behind a queue the improvement is read off
                    # the labels' part, after the switch)
                    improved = () if pull is not None else \
                        (prog.better(new, lab),)
                    # number of fully-expanded queue items (flat
                    # prefix): all of them on a lower rung
                    done = jnp.searchsorted(
                        off, jnp.asarray(EB, off.dtype), side="right",
                        method=fr.SEARCH)
                    return new, *improved, done.astype(jnp.int32)

                if ssw is None:
                    return jax.vmap(
                        lambda lab, begin, off, ssd: relax_part(
                            lab, begin, off, ssd, None))(
                        wide, begin, off, g["ss_dst"])
                return jax.vmap(relax_part)(wide, begin, off,
                                            g["ss_dst"], ssw)

            new_label, *improved, done = jax.lax.switch(
                eb_rung, [jax.named_scope(f"lux_eb{i}")(
                    functools.partial(on_budget, EB))
                    for i, EB in enumerate(self.budget_rungs)])
            if pull is None:
                improved, = improved
            else:
                new_label, pulled = (new_label[:, :sg.vpad],
                                     new_label[:, sg.vpad:])
                improved = prog.better(new_label, label)

            # 5. clear the globally-agreed processed prefix of the
            #    queue; everything else stays active (truncation
            #    safety).
            if pull is None:
                improved = improved & vmask_of(g, sg.vpad)
            done_min = exchanged(pmin_fn, jnp.min(done))

            def processed_local(gid, cnt, pidx):
                pos = jnp.arange(Q, dtype=jnp.int32)
                return (pidx * Q + pos < done_min) & (pos < cnt) & \
                    (gid < nv)

            if pull is None:
                # ids are global; convert back to local slots for
                # clearing
                def clear_local(mask, gid, cnt, start, pidx):
                    processed = processed_local(gid, cnt, pidx)
                    loc = jnp.clip(gid - start[0], 0, sg.vpad - 1)
                    upd = jnp.zeros((sg.vpad,), bool).at[loc].max(
                        processed, mode="drop")
                    return mask & ~upd

                cleared = jax.vmap(clear_local)(active, gids, cnts,
                                                g["part_start"], pidx)
                low = eb_rung < len(self.budget_rungs) - 1
                return (new_label, improved | cleared,
                        low.astype(jnp.int32), fill)

            # 5'. with the bottom-up step ONE scatter takes the queue
            #     back onto the vertices whichever way the slots ran
            #     (a cond round two tails compiled to 6 MB more code):
            #     top-down it marks the processed prefix, bottom-up it
            #     carries each item's result, every part's share of it
            #     combined (never truncated, so there is no prefix to
            #     agree on).  Bottom-up the items that got a label are
            #     the new frontier, and every vertex that was active is
            #     spent: _choose's guard says it could improve none
            #     but these.
            ident = jnp.asarray(prog.identity, label.dtype)
            across, over_parts, of_two = (
                (pmin_fn, jnp.min, jnp.minimum) if prog.reduce == "min"
                else (pmax_fn, jnp.max, jnp.maximum))
            with jax.named_scope("lux_pull"):
                mine = exchanged(across, over_parts(pulled, axis=0)) \
                    .reshape(-1, Q)[pidx]

            def onto_vertices(gid, cnt, start, pidx, val):
                # a mark is any label that wins the reduce against
                # the identity; a slot past the queue's count holds no
                # item and brings the identity, a no-op wherever it
                # lands
                val = jnp.where(
                    pull, jnp.where(gid < nv, val, ident),
                    jnp.where(processed_local(gid, cnt, pidx),
                              self._guard_floor(label.dtype), ident))
                return fr.scatter_reduce(
                    jnp.full((sg.vpad,), ident), gid - start[0], val,
                    prog.reduce)

            back = jax.vmap(onto_vertices)(gids, cnts, g["part_start"],
                                           pidx, mine)
            got = of_two(new_label, back)
            kept = (improved & vmask_of(g, sg.vpad)) | \
                (active & (back == ident))
            low = eb_rung < len(self.budget_rungs) - 1
            return (jnp.where(pull, got, new_label),
                    jnp.where(pull, prog.better(got, new_label), kept),
                    low.astype(jnp.int32), fill)

        return jax.lax.switch(
            fr.rung_index(need, self.queue_rungs),
            [jax.named_scope(f"lux_q{i}")(functools.partial(on_queue, Q))
             for i, Q in enumerate(self.queue_rungs)])

    def _guard_floor(self, dtype):
        """The label that wins the program's reduce against every
        other: the least of ``dtype`` for a min program, the most for
        a max one."""
        info = (jnp.finfo if jnp.issubdtype(dtype, jnp.inexact)
                else jnp.iinfo)(dtype)
        return jnp.asarray(info.min if self.program.reduce == "min"
                           else info.max, dtype)

    def _choose(self, label, active, count, g):
        """The per-iteration choice, traced -> (sparse, pull, need,
        unreached): replicated booleans (run the sparse branch; run it
        bottom-up), what its queue must hold, and the mask it then
        compacts.  pull and unreached are None where the step is not
        built (_sparse_mode).  Every reduction here is [P, vpad]-sized
        or a scalar across the mesh."""
        _usable, limit, pull_built = self._sparse_mode()
        q_fits = count <= jnp.int32(limit)
        if not pull_built:
            return q_fits, None, count, None
        prog, on_mesh = self.program, self.mesh is not None

        def across(fn, x):
            return fn(x, PARTS_AXIS) if on_mesh else x

        ident = jnp.asarray(prog.identity, label.dtype)
        reached = (label != ident) & vmask_of(g, self.sg.vpad)
        # T: unreached vertices with an edge.  (deg is the out-degree,
        # on a symmetric graph also the in-degree; 0 on padding.)
        T = (label == ident) & (g["deg"] != 0) & ~active
        t_most = across(jax.lax.pmax,
                        jnp.max(jnp.sum(T.astype(jnp.int32), axis=1)))
        t_edges = across(jax.lax.psum, jnp.sum(
            jnp.where(T, g["deg"], 0).astype(jnp.uint32)))
        # the frontier's best label and the worst label a reached
        # vertex holds, whichever way the program reduces: (of an
        # array, across the mesh)
        lower, upper = (jnp.min, jax.lax.pmin), (jnp.max, jax.lax.pmax)
        best, worst = (lower, upper) if prog.reduce == "min" \
            else (upper, lower)
        bottom = self._guard_floor(label.dtype)
        best_active = across(best[1], best[0](
            jnp.where(active, label, ident)))[None]
        worst_reached = across(worst[1], worst[0](
            jnp.where(reached, label, bottom)))
        # no relaxation into a REACHED vertex can improve it: the best
        # candidate the frontier can offer (its best label over the
        # view's extreme weights; relax is monotone) is not better
        # than the worst label a reached vertex holds
        offers = [prog.relax(best_active,
                             None if w is None
                             else jnp.full((1,), w, jnp.float32))[0]
                  for w in self._weight_ends]
        guard = functools.reduce(
            jnp.logical_and,
            [~prog.better(c, worst_reached.astype(c.dtype))
             for c in offers])
        fits = ((t_most > 0)
                & (t_most <= jnp.int32(self.queue_rungs[-1]))
                & (t_edges <= jnp.uint32(self.budget_rungs[-1])))
        pull = ~q_fits & fits & guard
        return (q_fits | pull, pull, jnp.where(pull, t_most, count), T)

    def _spills(self, edges):
        """The bucket loop's half of the choice, traced -> replicated
        bool: a front of ``edges`` out-edges (uint32, summed over the
        mesh) passes the top budget rung, the size at which
        _sparse_parts starts to truncate, and runs DENSE whatever its
        vertex count.  The bucket bound keeps a front's count under
        the queue's limit while the few vertices under it are hubs;
        relaxed a truncated prefix a trip, at a dense trip's price,
        the rest meets the same bound on the next trip and truncates
        again (25 trips in a row from one root of Graph500 kernel 3:
        PERF.md section 6, PR 44), where one dense trip relaxes the
        front whole.  The plain loop does not ask: there a truncated
        level is a cheap head start and the next front runs dense
        anyway (PERF.md section 7, "BFS after PR 33").

        On one part the total is what frontier_extents sums, so the
        test is exactly "this trip would truncate".  On a mesh a part
        expands only the front's edges that land in it: a global total
        of at most the top rung means no part truncates, a larger one
        may send a front dense that no part would have truncated, so
        the estimate errs towards DENSE.  Under it no relax trip of
        the bucket loop truncates (short of a total that wraps uint32,
        on a graph of 2^32 edges: then a trip may truncate after all).
        The answer is the same either way: the fixed point of the
        monotone reduce is unique whatever the schedule."""
        return edges > jnp.uint32(self.budget_rungs[-1])

    def _part_index(self):
        """Global part index of this device's parts [P_local] int32."""
        P_local = self.sg.num_parts if self.mesh is None else \
            self.sg.num_parts // self.mesh.devices.size
        base = jnp.int32(0)
        if self.mesh is not None:
            base = jax.lax.axis_index(PARTS_AXIS) * P_local
        return base + jnp.arange(P_local, dtype=jnp.int32)

    # -- compiled whole-run / single-step ------------------------------

    def _build(self, converge: bool, stats: bool = False,
               health: bool = False):
        """stats=True (converge only) additionally accumulates
        device-side per-iteration counters INSIDE the while_loop into
        fixed [stats_cap] buffers: frontier size (int32) and frontier
        out-edges relaxed (uint32) per iteration — see
        lux_tpu/telemetry.py for the exact semantics.  Out-degrees
        come from the FULL graph (self.sg, pair rows included), passed
        as one extra sharded argument so the counter-free program
        never carries them.  Round 13: the same variant ALSO records
        the per-part split into [stats_cap, P] buffers (frontier and
        out-edges per part; the scalar entries are the SUMS of the
        per-part rows, so sum-over-parts is bitwise-exact by
        construction) — per-part values are reduced per local part
        and replicated over the mesh (P ints per iteration over
        ICI), adding NO state-table gathers (audit gather-budget
        stays at the same budget).

        health=True (implies stats) additionally accumulates the O(1)
        health word (lux_tpu/health.py: NaN labels — +Inf stays the
        legitimate unreached sentinel — and the truncation-livelock
        frontier stall) and EXITS the while_loop the iteration a check
        trips, so a livelocked run stops instead of spinning to
        max_iters."""
        assert not stats or converge
        assert not health or stats
        keys = sorted(self.arrays)
        graph_args = tuple(self.arrays[k] for k in keys)
        on_mesh = self.mesh is not None
        sg, prog = self.sg, self.program
        use_sparse, limit, pull_built = self._sparse_mode()
        # the loops' counter carry, last: (took, fill).  took: int32
        # sparse_iters, low_rung_iters and, where the bottom-up step
        # is built, pull_iters.  fill (engines with a ladder, else
        # None: no leaf, the program they had): the sparse iterations'
        # queue_items, queue_slots, budget_edges, budget_slots, each
        # a (low word, high word) pair of uint32 SCALARS (hundreds of
        # iterations on a 12 M-slot rung pass 2^32; scalars, so the
        # loop does scalar arithmetic on them and nothing else),
        # stacked into one uint32 [4, 2] behind the loop
        n_took = 2 + int(pull_built)
        n_counts = n_took + int(use_sparse)

        def tally(ctr, took, fill):
            return (ctr[0] + took,
                    None if fill is None else tuple(
                        fr.wide_add(low, high, x)
                        for (low, high), x in zip(ctr[1], fill)))

        def tally0():
            zero = jnp.uint32(0)
            return (jnp.zeros((n_took,), jnp.int32),
                    ((zero, zero),) * 4 if use_sparse else None)

        def counts_out(ctr):
            # -> sparse_iters, low_rung_iters[, pull_iters][, fill]
            took, fill = ctr
            if fill is None:
                return tuple(took)
            return (*took, jnp.stack([jnp.stack(w) for w in fill]))
        cap_n = self.stats_cap

        def global_sum(x):
            s = jnp.sum(x)
            if on_mesh:
                s = jax.lax.psum(s, PARTS_AXIS)
            return s

        def gather_fn(x):
            if on_mesh:
                return jax.lax.all_gather(x, PARTS_AXIS, tiled=True)
            return x

        def pmin_fn(x):
            if on_mesh:
                return jax.lax.pmin(x, PARTS_AXIS)
            return x

        def pmax_fn(x):
            if on_mesh:
                return jax.lax.pmax(x, PARTS_AXIS)
            return x

        def psum_fn(x):
            if on_mesh:
                return jax.lax.psum(x, PARTS_AXIS)
            return x

        def replicate_parts(x):
            """Per-local-part counters [P_local] -> the full [P] row,
            IDENTICAL on every device.  A psum of each device's rows
            placed at its own offset, not an all_gather: all_gather's
            result is typed device-varying under shard_map, and the
            counter buffers are replicated carries and outputs."""
            if not on_mesh:
                return x
            rows = jnp.zeros((self.mesh.devices.size,) + x.shape,
                             x.dtype)
            rows = rows.at[jax.lax.axis_index(PARTS_AXIS)].set(x)
            return jax.lax.psum(rows, PARTS_AXIS).reshape(-1)

        if health:
            from lux_tpu import health as hw
            P_local = (sg.num_parts if not on_mesh
                       else sg.num_parts // self.mesh.devices.size)
            _BIG = jnp.int32(np.iinfo(np.int32).max)

            def health_step(h, stall, old_l, new_l, old_cnt,
                            new_cnt):
                """One relax iteration's health update (runs INSIDE
                shard_map — everything psum/pmin'd so the word is
                identical on every device)."""
                badp = hw.nan_parts(new_l)          # [P_local] int32
                nf = global_sum(badp)
                chg = global_sum((new_l != old_l).astype(jnp.int32))
                base = jnp.int32(0)
                if on_mesh:
                    base = (jax.lax.axis_index(PARTS_AXIS)
                            * jnp.int32(P_local))
                loc = hw.first_bad_part(badp)
                cand = pmin_fn(jnp.where(loc >= 0, base + loc, _BIG))
                part = jnp.where(cand == _BIG, -1,
                                 cand).astype(jnp.int32)
                # truncation livelock: non-empty frontier, identical
                # active count, bit-identical labels — for STALL_N
                # consecutive relax steps (a zero-progress step that
                # SHRINKS the active set is legitimate and resets)
                stalled = ((chg == 0) & (new_cnt > 0)
                           & (new_cnt == old_cnt))
                stall = jnp.where(stalled, stall + jnp.int32(1),
                                  jnp.int32(0))
                flags = ((nf > 0) * hw.NONFINITE_STATE
                         + (stall >= hw.STALL_N) * hw.FRONTIER_STALL)
                return hw.record(h, flags, part, nf, new_cnt), stall

        def dense_body(label, active, g):
            if self.exchange == "owner":
                return self._dense_parts_owner(label, active, g)
            if on_mesh:
                full_l = jax.lax.all_gather(label, PARTS_AXIS, tiled=True)
                full_a = jax.lax.all_gather(active, PARTS_AXIS, tiled=True)
            else:
                full_l, full_a = label, active
            return self._dense_parts(label, active, full_l, full_a, g)

        def body(label, active, count, g, spills=None):
            """-> (label, active, took, fill): took = int32 [n_took],
            1 if the SPARSE branch ran, 1 if it ran below the top edge
            budget and (engines with the bottom-up step) 1 if it ran
            bottom-up — what the loops sum into their ``sparse_iters``
            / ``low_rung_iters`` / ``pull_iters`` carry (the
            device-side counters telemetry reads); fill = the sparse
            branch's four uint32 scalars (_sparse_parts; zeros from
            the dense one), None on an engine without a ladder.
            spills (the bucket loop's alone, else None: no op) is its
            replicated flag that the front's out-edges pass the top
            budget rung: such a front runs dense."""
            if not use_sparse:
                return (*dense_body(label, active, g),
                        jnp.zeros((2,), jnp.int32), None)

            # Reference heuristic: frontier > nv/16 -> dense/pull mode
            # (sssp_gpu.cu:414), and the queue must fit; a frontier
            # too wide for it still takes the sparse branch, bottom-up,
            # where the UNREACHED vertices fit instead (_choose).
            sparse, pull, need, unreached = self._choose(
                label, active, count, g)
            if spills is not None:
                sparse = sparse & ~spills

            def sparse_branch():
                with jax.named_scope("lux_sparse"):
                    return self._sparse_parts(label, active, need, g,
                                              gather_fn, pmin_fn,
                                              pmax_fn, psum_fn, pull,
                                              unreached)

            def dense_branch():
                with jax.named_scope("lux_dense"):
                    return (*dense_body(label, active, g), jnp.int32(0),
                            (jnp.uint32(0),) * 4)

            nl, na, low, fill = jax.lax.cond(sparse, sparse_branch,
                                             dense_branch)
            took = [sparse.astype(jnp.int32), low]
            if pull is not None:
                took.append(pull.astype(jnp.int32))
            return nl, na, jnp.stack(took), fill

        use_delta = converge and self.delta is not None
        # the delta loop's three outputs behind the shared counts
        n_counts += 3 * int(use_delta)

        def inner(label, active, max_iters, *gargs):
            if health:
                # previous segment's watchdog carry (word + stall
                # counter) — threaded so a stall spanning a segment
                # boundary still accumulates
                h0, stall0, gargs = gargs[0], gargs[1], gargs[2:]
            if stats:
                deg_full, gargs = gargs[0], gargs[1:]
            g = dict(zip(keys, gargs))

            def esum_parts(act):
                # out-edges of the frontier ``act`` PER PART [P] —
                # the relax work each part contributes this iteration
                # (replicated on a mesh: P ints per iteration over
                # ICI, no state-table gathers).
                # uint32: a full 2^31+-edge frontier must not wrap
                # int32; the scalar counter is the SUM of this row,
                # so sum-over-parts is bitwise-exact by construction.
                # Batched labels: the dense iteration gathers each
                # edge ONCE for all B queries, so the work counter is
                # the out-edges of the UNION frontier over the query
                # axis (any column active at the vertex).
                if act.ndim > 2:
                    act = jnp.any(act, axis=-1)
                return replicate_parts(
                    jnp.sum(jnp.where(act, deg_full, 0)
                            .astype(jnp.uint32), axis=1))

            def fcount_parts(act):
                # active count per part [P] int32 (sums to the psum'd
                # scalar frontier count exactly — integer addition);
                # batched: active (vertex, query) PAIRS, matching the
                # scalar global_sum the convergence predicate uses
                return replicate_parts(
                    jnp.sum(act.astype(jnp.int32),
                            axis=tuple(range(1, act.ndim))))

            if not converge:
                cnt0 = global_sum(active)
                new_label, new_active, _, _ = body(label, active, cnt0,
                                                   g)
                return new_label, new_active, global_sum(new_active)

            if use_delta:
                # Delta-stepping (Meyer & Sanders): relax only the
                # current distance bucket [*, B) to (near-)settlement
                # before advancing B — fewer wasted re-relaxations of
                # far vertices than plain Bellman-Ford frontiers.  One
                # XLA while_loop; bucket advance is a pmin'd scalar.
                ident = jnp.asarray(prog.identity, label.dtype)
                delta = jnp.asarray(self.delta, label.dtype)

                def active_min(lbl, act):
                    m = jnp.min(jnp.where(act, lbl, ident))
                    if on_mesh:
                        m = jax.lax.pmin(m, PARTS_AXIS)
                    return m

                # `it` counts RELAX iterations only (what max_iters
                # caps and what GTEPS reporting uses); bucket advances
                # relax nothing and are not iterations.  Advance-only
                # stretches terminate on their own: while any vertex is
                # active, raising B eventually makes the frontier
                # non-empty.
                # carry: (it, lbl, act, B, cnt, [4 stats buffers],
                # [health word, stall], counters) — the counters
                # ((took, fill): tally) ride LAST so every index
                # before it stands
                def cond(c):
                    it, lbl, act, B, cnt = c[:5]
                    ok = (cnt > 0) & (it < max_iters)
                    if health:        # exit the loop on a tripped word
                        ok = ok & (c[9][0] == 0)
                    return ok

                # the schedule's own counters ride with the shared
                # ones in the LAST carry element, (tally, bucket):
                # bucket = (advances int32: trips that relaxed nothing,
                # front: the edges each relax trip relaxed and the
                # vertices of its front, each summed in two uint32
                # words as the fills, edge_dense int32: relax trips
                # whose front fit the queue by vertex count and ran
                # dense for its out-edges: `relax` below)
                def wbody(c):
                    it, lbl, act, B, cnt = c[:5]
                    buf = c[5:]
                    with jax.named_scope("lux_bucket"):
                        front = act & (lbl < B)
                        nf = global_sum(front)

                    def relax(it, lbl, act, B, *buf):
                        # the front's out-edge total, once: the
                        # choice of the branch reads it (_spills) and
                        # so does the front_edges count
                        ctr, (adv, (fe, fv), edge_dense) = buf[-1]
                        with jax.named_scope("lux_bucket"):
                            edges = global_sum(
                                jnp.where(front, g["deg"], 0)
                                .astype(jnp.uint32))
                            spills = None
                            if use_sparse:
                                spills = self._spills(edges)
                                edge_dense = edge_dense + (
                                    spills & (nf <= jnp.int32(limit))
                                ).astype(jnp.int32)
                        if stats:
                            # counters record the bucket front ENTERING
                            # this relax; advances relax nothing
                            # and write no entry.  The scalar
                            # edges entry is the sum of the per-part
                            # row (bitwise, uint32 either way).
                            fsz, fed, fszp, fedp = buf[:4]
                            ep = esum_parts(front)
                            buf = (fsz.at[it].set(nf, mode="drop"),
                                   fed.at[it].set(jnp.sum(ep),
                                                  mode="drop"),
                                   fszp.at[it].set(fcount_parts(front),
                                                   mode="drop"),
                                   fedp.at[it].set(ep, mode="drop")) \
                                + buf[4:]
                        nl, na, took, fill = body(lbl, front, nf, g,
                                                  spills)
                        # the edges this trip relaxed are the front's
                        # out-edges: a sparse trip's budget stage
                        # expands them all (_spills: none truncates)
                        fe = fr.wide_add(*fe, edges)
                        fv = fr.wide_add(*fv, nf.astype(jnp.uint32))
                        merged = (act & ~front) | na
                        if health:
                            # the watchdog watches relax steps only:
                            # advances relax nothing and terminate on
                            # their own (see `advance` below)
                            h, stall = health_step(
                                buf[4], buf[5], lbl, nl, cnt,
                                global_sum(merged))
                            buf = buf[:4] + (h, stall) + buf[6:]
                        return (it + 1, nl, merged, B, *buf[:-1],
                                (tally(ctr, took, fill),
                                 (adv, (fe, fv), edge_dense)))

                    @jax.named_scope("lux_bucket")
                    def advance(it, lbl, act, B, *buf):
                        # Strict progress: with float labels a delta
                        # below one ulp at the current magnitude makes
                        # active_min + delta round back to active_min
                        # and the advance loop livelocks (frontier
                        # stays empty forever).  Raising B strictly
                        # above active_min guarantees the argmin active
                        # vertex enters the next frontier.
                        am = active_min(lbl, act)
                        nb = am + delta
                        if jnp.issubdtype(label.dtype, jnp.inexact):
                            nb = jnp.maximum(
                                nb, jnp.nextafter(
                                    am, jnp.asarray(jnp.inf, am.dtype)))
                        ctr, (adv, *rest) = buf[-1]
                        return (it, lbl, act, nb, *buf[:-1],
                                (ctr, (adv + 1, *rest)))

                    out = jax.lax.cond(
                        nf > 0, relax, advance, it, lbl, act, B, *buf)
                    it, lbl, act, B = out[:4]
                    return (it, lbl, act, B, global_sum(act), *out[4:])

                with jax.named_scope("lux_bucket"):
                    B0 = active_min(label, active) + delta
                init = (jnp.int32(0), label, active, B0,
                        global_sum(active))
                if stats:
                    init = init + (
                        jnp.zeros((cap_n,), jnp.int32),
                        jnp.zeros((cap_n,), jnp.uint32),
                        jnp.zeros((cap_n, sg.num_parts), jnp.int32),
                        jnp.zeros((cap_n, sg.num_parts), jnp.uint32))
                if health:
                    init = init + (h0, stall0)
                zero = jnp.uint32(0)
                out = jax.lax.while_loop(
                    cond, wbody,
                    init + ((tally0(), (jnp.int32(0),
                                        ((zero, zero),) * 2,
                                        jnp.int32(0))),))
                # (lbl, act, it, [stats], [health], *counts_out,
                # advances, the words of front_edges and
                # front_vertices [2, 2], edge_dense)
                ctr, (adv, front, edge_dense) = out[-1]
                return (out[1], out[2], out[0], *out[5:-1],
                        *counts_out(ctr), adv,
                        jnp.stack([jnp.stack(w) for w in front]),
                        edge_dense)

            # carry: (it, lbl, act, cnt, [4 stats buffers], [health
            # word, stall], counters) — the counters ride LAST
            def cond(c):
                it, lbl, act, cnt = c[:4]
                ok = (cnt > 0) & (it < max_iters)
                if health:            # exit the loop on a tripped word
                    ok = ok & (c[8][0] == 0)
                return ok

            def wbody(c):
                it, lbl, act, cnt = c[:4]
                if stats:
                    fsz, fed, fszp, fedp = c[4:8]
                    # edges relaxed by THIS iteration: out-edges of
                    # the frontier entering it, per part; the scalar
                    # is the row's sum (bitwise-exact, uint32)
                    ep = esum_parts(act)
                    fed = fed.at[it].set(jnp.sum(ep), mode="drop")
                    fedp = fedp.at[it].set(ep, mode="drop")
                nl, na, took, fill = body(lbl, act, cnt, g)
                ncnt = global_sum(na)
                ns = tally(c[-1], took, fill)
                if stats:
                    # frontier AFTER the iteration — exactly the
                    # series the stepwise -verbose path printed
                    fsz = fsz.at[it].set(ncnt, mode="drop")
                    fszp = fszp.at[it].set(fcount_parts(na),
                                           mode="drop")
                    if health:
                        h, stall = health_step(c[8], c[9], lbl,
                                               nl, cnt, ncnt)
                        return (it + 1, nl, na, ncnt, fsz, fed, fszp,
                                fedp, h, stall, ns)
                    return (it + 1, nl, na, ncnt, fsz, fed, fszp,
                            fedp, ns)
                return it + 1, nl, na, ncnt, ns

            it0 = jnp.int32(0)
            cnt0 = global_sum(active)
            init = (it0, label, active, cnt0)
            if stats:
                init = init + (
                    jnp.zeros((cap_n,), jnp.int32),
                    jnp.zeros((cap_n,), jnp.uint32),
                    jnp.zeros((cap_n, sg.num_parts), jnp.int32),
                    jnp.zeros((cap_n, sg.num_parts), jnp.uint32))
            if health:
                init = init + (h0, stall0)
            out = jax.lax.while_loop(cond, wbody, init + (tally0(),))
            # (lbl, act, it, [stats], [health], *counts_out)
            return (out[1], out[2], out[0], *out[4:-1],
                    *counts_out(out[-1]))

        if prog.name:
            inner = jax.named_scope(f"lux_{prog.name}")(inner)
        if on_mesh:
            P = PartitionSpec
            out_specs = (P(PARTS_AXIS), P(PARTS_AXIS), P())
            if stats:
                # counters are psum-replicated values written into
                # replicated buffers (scalar pair + the
                # per-part [cap, P] pair)
                out_specs = out_specs + (P(), P(), P(), P())
            if health:
                # the health word + stall counter are built from
                # psum/pmin'd scalars, identical on every device
                out_specs = out_specs + (P(), P())
            if converge:
                # the counters sum predicates of the psum'd count,
                # the pmax'd out-edge total and _choose's scalars
                out_specs = out_specs + (P(),) * n_counts
            in_specs = (P(PARTS_AXIS), P(PARTS_AXIS), P())
            if health:
                in_specs = in_specs + (P(), P())    # h0, stall0
            in_specs = in_specs + \
                (P(PARTS_AXIS),) * (len(keys) + int(stats))
            inner = jax.shard_map(inner, mesh=self.mesh,
                                  in_specs=in_specs,
                                  out_specs=out_specs)

        jitted = jax.jit(inner, donate_argnums=(0, 1))

        extra = ()
        if stats:
            deg_full = np.asarray(self.sg.deg_padded)
            if self.mesh is not None:
                deg_full = shard_over_parts(self.mesh, [deg_full],
                                            self.sg.num_parts)[0]
            else:
                deg_full = jnp.asarray(deg_full)
            extra = (deg_full,)

        vname = ("converge" if converge else "step") + \
            ("_health" if health else "_stats" if stats else "")

        def _args_thunk():
            lab_sds, act_sds = self._audit_state_sds
            watch = ()
            if health:
                from lux_tpu import health as _hw0
                watch = (_hw0.init_word(), jnp.int32(0))
            return (lab_sds, act_sds,
                    jax.ShapeDtypeStruct((), jnp.int32),
                    *watch, *extra, *graph_args)

        self._register_variant(vname, jitted, _args_thunk)

        def mark(it, counts):
            # the bucket schedule's counts (0 on an engine without
            # delta): advances = loop trips that relaxed nothing (so
            # iters + advances = trips), front_edges = the edges the
            # relax trips relaxed, folded as the fills, front_vertices
            # = the vertices of their fronts, graph_edges = the
            # stored edges, once a call, edge_dense_iters = the
            # relax trips whose front fit the queue by vertex count
            # and ran dense because its out-edges pass the top budget
            # rung (iters = sparse_iters + dense by count + these)
            bucket = {"advances": 0, "front_edges": 0,
                      "front_vertices": 0, "graph_edges": 0,
                      "edge_dense_iters": 0}
            if use_delta:
                counts, (adv, words, edge_dense) = \
                    counts[:-3], counts[-3:]
                bucket = {"advances": adv,
                          "front_edges": fr.Folded(words, 0),
                          "front_vertices": fr.Folded(words, 1),
                          "graph_edges": int(sg.ne),
                          "edge_dense_iters": edge_dense}
            # pull_iters is 0 where the step is not built; the four
            # fill counts are 0 on an engine without a ladder, else
            # each ONE number folded from the carry's two words when
            # the ring is read (fr.Folded: no fetch here)
            took, fill = counts[:n_took], counts[n_took:]
            names = ("queue_items", "queue_slots", "budget_edges",
                     "budget_slots")
            telemetry.mark(
                "push.converge", iters=it,
                **dict(zip(("sparse_iters", "low_rung_iters",
                            "pull_iters"), (*took, 0))),
                **{n: fr.Folded(fill[0], i) if fill else 0
                   for i, n in enumerate(names)},
                **bucket)

        if health:
            from lux_tpu import health as _hw

            def call(label, active, max_iters=np.iinfo(np.int32).max,
                     watch=None):
                if watch is None:
                    watch = (_hw.init_word(), jnp.int32(0))
                (l, a, it, fsz, fed, fszp, fedp, h, stall,
                 *counts) = jitted(
                    label, active, jnp.int32(max_iters), *watch,
                    *extra, *graph_args)
                mark(it, counts)
                return l, a, it, fsz, fed, fszp, fedp, (h, stall)

            return call

        def call(label, active, max_iters=np.iinfo(np.int32).max):
            """One dispatch; stays asynchronous.  A converge variant
            leaves a ``push.converge`` mark whose ``iters`` /
            ``sparse_iters`` / ``low_rung_iters`` (the sparse
            iterations whose edge budget was a lower rung) /
            ``pull_iters`` (those that ran bottom-up) are the
            un-fetched device scalars (fetched at
            ``telemetry.spans()``, never here), and so are
            ``queue_items`` / ``queue_slots`` / ``budget_edges`` /
            ``budget_slots``: over the call's sparse iterations the
            vertices compacted and the edges expanded, summed over
            the parts, beside the rungs they ran on x parts.  A
            delta engine's ``advances``, ``front_edges``,
            ``front_vertices`` and ``edge_dense_iters`` likewise; its
            ``graph_edges`` is the host's."""
            out = jitted(label, active, jnp.int32(max_iters), *extra,
                         *graph_args)
            if not converge:
                return out
            out, counts = out[:-n_counts], out[-n_counts:]
            mark(out[2], counts)
            return tuple(out)

        return call

    # -- static-audit surface (engine/auditable.py) --------------------

    _AUDIT_LAZY = ("_converge_stats_fn", "_converge_health_fn")

    @functools.cached_property
    def _audit_state_sds(self):
        """Abstract (label, active) stand-ins — init runs ONCE per
        engine, not once per audited variant, and the materialized
        arrays are stashed for the next ``init_state`` call so an
        audited-then-run engine pays for exactly one host init."""
        lab0, act0 = self.program.init(self.sg)
        lab0, act0 = np.asarray(lab0), np.asarray(act0)
        self._pending_init = (lab0, act0)
        return (jax.ShapeDtypeStruct(lab0.shape, lab0.dtype),
                jax.ShapeDtypeStruct(act0.shape, act0.dtype))

    # -- public API ----------------------------------------------------

    def step(self, label, active):
        """One compiled iteration -> (label, active, global active count
        as a device scalar)."""
        return self._step_fn(label, active)

    def converge(self, label, active, max_iters: int | None = None):
        """Run to an empty frontier inside ONE XLA program.
        Returns (label, active, iterations_executed)."""
        cap = np.iinfo(np.int32).max if max_iters is None else max_iters
        return self._converge_fn(label, active, cap)

    @functools.cached_property
    def _converge_stats_fn(self):
        return self._build(converge=True, stats=True)

    def converge_stats(self, label, active,
                       max_iters: int | None = None):
        """``converge`` + device-side iteration counters accumulated
        INSIDE the fused while_loop (compiled lazily on first use —
        the counter-free program is untouched).  Returns (label,
        active, iters, frontier int32 [stats_cap], edges uint32
        [stats_cap], frontier_parts int32 [stats_cap, P], edges_parts
        uint32 [stats_cap, P]): classic engines record the
        post-iteration frontier size (the stepwise -verbose series)
        and the entering frontier's out-edge count; delta engines
        record each relax step's bucket-front size and out-edges (see
        lux_tpu/telemetry.py).  The per-part counters are the round-13
        imbalance-attribution signal: each scalar entry is the SUM of
        its per-part row, bitwise (tests/test_telemetry.py holds the
        NumPy per-part oracle).  Writes past ``stats_cap`` drop;
        entries past ``iters`` are zero.  Fetch the buffers once per
        run/segment (a few KB) — never inside a timed region's hot
        loop."""
        cap = np.iinfo(np.int32).max if max_iters is None else max_iters
        return self._converge_stats_fn(label, active, cap)

    @functools.cached_property
    def _converge_health_fn(self):
        return self._build(converge=True, stats=True, health=True)

    def converge_health(self, label, active,
                        max_iters: int | None = None, watch=None):
        """``converge_stats`` under the device-side health watchdog
        (lux_tpu/health.py): returns (label, active, iters, frontier
        buf, edges buf, frontier-parts buf, edges-parts buf, watch)
        with watch = (health int32[6], stall counter) — the per-part
        counters ride this variant too, same oracle contract as
        ``converge_stats``.  The while_loop EXITS the iteration a check trips
        (NaN labels; the truncation-livelock frontier stall), so
        ``iters`` then counts only the completed healthy iterations;
        fetch + decode the word once per run/segment with
        ``health.ensure_ok(watch)``, and pass the previous segment's
        ``watch`` back in so a stall spanning a boundary still
        accumulates.  Compiled lazily — the watchdog-free programs
        are untouched."""
        cap = np.iinfo(np.int32).max if max_iters is None else max_iters
        return self._converge_health_fn(label, active, cap, watch)

    def run(self, max_iters: int | None = None, verbose: bool = False,
            seg_budget: float | None = None):
        """init -> converge -> host label array [nv]; returns
        (labels, num_iters).  verbose=True REPLAYS per-iteration
        frontier sizes from the fused run's device-side counters
        (``converge_stats``) — the old stepwise slow path is gone, and
        delta engines replay their ACTUAL bucket schedule's relax
        steps.  seg_budget (seconds) converges in duration-budgeted
        while_loop slices (segmented.DurationBudget) so each XLA
        execution stays under the budget — counters then accumulate
        across segments, so seg_budget and verbose compose."""
        import contextlib

        from lux_tpu import telemetry
        label, active = self.init_state()
        tel = telemetry.current()
        st = tel.iter_stats
        ctx = contextlib.nullcontext()
        if verbose and st is None:
            st = telemetry.IterStats()
            ctx = telemetry.use(events=tel.events, iter_stats=st)
        with ctx:
            if seg_budget is not None:
                from lux_tpu.segmented import DurationBudget, \
                    converge_segments
                label, active, it = converge_segments(
                    self, label, active,
                    DurationBudget(seg_budget, per_size_compile=False),
                    max_iters)
            elif self.health:
                from lux_tpu import health as hw
                label, active, itd, fsz, fed, fszp, fedp, h = \
                    self.converge_health(label, active, max_iters)
                it = int(jax.device_get(itd))
                if st is not None:
                    st.begin_run()
                    st.extend_push(fsz, fed, it, fszp, fedp)
                hw.ensure_ok(h, engine="push", where="push converge")
            elif st is not None:
                st.begin_run()
                label, active, itd, fsz, fed, fszp, fedp = \
                    self.converge_stats(label, active, max_iters)
                it = int(jax.device_get(itd))
                st.extend_push(fsz, fed, it, fszp, fedp)
            else:
                label, active, itd = self.converge(label, active,
                                                   max_iters)
                it = int(jax.device_get(itd))
        if verbose:
            for line in st.replay_lines():
                print(line)
        return self.unpad(label), it

    def unpad(self, state) -> np.ndarray:
        """Device state -> host array in vertex order, under a
        ``state.fetch`` span (``bytes``: what came to the host) whose
        two children cover it: ``state.fetch.get`` (device to host,
        the same ``bytes``) and ``state.fetch.unpad`` (``from_padded``;
        ``bytes`` of its result)."""
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

    def _sparse_mode(self):
        """Single source of truth for the per-iteration choice (traced
        inside the compiled step by _choose): returns (usable,
        count_limit, pull) — the reference's frontier > nv/16 pull
        switch (sssp_gpu.cu:414) AND the queue capacity, and whether a
        frontier over that limit may still run sparse, bottom-up
        (_choose's three conditions; built on symmetric graphs
        only)."""
        usable = (self.enable_sparse
                  and self.program.reduce in ("min", "max"))
        limit = min(self.queue_cap,
                    max(1, self.sg.nv // self.sparse_threshold)) \
            if self.enable_sparse else 0
        return usable, limit, self.pull
