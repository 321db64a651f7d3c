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
from lux_tpu.engine.program import PartCtx, PullProgram, vmask_of
from lux_tpu.graph import ShardedGraph
from lux_tpu.ops.segment import segment_reduce
from lux_tpu.ops.tiled import (STREAM_MSG_BYTES, TiledLayout,
                               combine_chunks, combine_op,
                               tiled_segment_reduce)
from lux_tpu.parallel.mesh import PARTS_AXIS, shard_over_parts


# chunks per lax.map block in the dot path: bounds the [B, E, W]
# intermediate (~32 MB at the default tile sizes; 128 measured best
# on v5e, within 3% of every size from 32 up)
DOT_BLOCK_CHUNKS = 128


def _dot_kdim(program) -> int:
    """K of a dot-path program's vector state — feeds the K-aware pair
    economics (min_fill="auto", ops/pairs.resolve_min_fill) and the
    SDDMM streaming budget.  Programs using edge_value_from_dot should
    set state_bytes = 4 * K (colfilter does); unset falls back to
    scalar economics."""
    if getattr(program, "edge_value_from_dot", None) is None:
        return 1
    sb = getattr(program, "state_bytes", None)
    return max(1, (sb or 4) // 4)



def resolve_reduce_method(method: str) -> str:
    """'auto' picks the Pallas kernel on real TPUs and the portable
    XLA formulation elsewhere (including the CPU test mesh);
    'pallas-interpret' forces the kernel in interpreter mode so its
    code path is testable off-TPU."""
    if method == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if method in ("xla", "pallas", "pallas-interpret"):
        return method
    raise ValueError(f"unknown reduce_method {method!r}")


# auto exchange: go owner-side once the flat state table passes this
# many bytes — the measured XLA gather emitter step sits at ~64-128 MB
# (scripts/profile_bigtable.py), so 96 MB splits the band; below it the
# owner layout's chunk padding isn't worth carrying
OWNER_AUTO_BYTES = 96 << 20


def resolve_exchange(exchange: str, sg: ShardedGraph, program,
                     itemsize: int | None = None) -> str:
    """'auto' picks 'owner' when the program qualifies (source-only
    edge values; full AND multi-host local-parts builds both qualify)
    and the state table would pay the big-table gather tax; 'gather'
    otherwise.

    itemsize: bytes per VERTEX for the table estimate (itemsize x
    trailing dims).  Default: the program's ``state_bytes`` (pull) or
    its ``identity`` dtype's itemsize (push); 4 when neither exists."""
    if exchange == "auto":
        if itemsize is None:
            itemsize = getattr(program, "state_bytes", None)
        if itemsize is None:
            ident = getattr(program, "identity", None)
            itemsize = (np.asarray(ident).dtype.itemsize
                        if ident is not None else 4)
        # works for Pull AND Push programs (push has no dst/dot hooks)
        eligible = (not getattr(program, "needs_dst", False)
                    and getattr(program, "edge_value_from_dot",
                                None) is None)
        big = sg.num_parts * sg.vpad * itemsize > OWNER_AUTO_BYTES
        return "owner" if (eligible and big) else "gather"
    if exchange not in ("gather", "owner"):
        raise ValueError(f"unknown exchange {exchange!r}")
    return exchange


def mxu_wide_of(program) -> int:
    """K x B payload width of a program's state — the free MXU minor
    dimension the round-23 one-hot reduce amortizes its toll over
    (scalemodel.mxu_break_even_wide).  K from state_bytes (itemsize x
    trailing dims, the _dot_kdim convention), B from the query batch;
    both multiply."""
    sb = getattr(program, "state_bytes", None)
    if sb is not None:
        # state_bytes covers the FULL trailing row — colfilter's 4*K,
        # batched pagerank's itemsize*B — so it already is K x B
        return max(1, sb // 4)
    return int(getattr(program, "batch", None) or 1)


def resolve_use_mxu(use_mxu, program) -> bool:
    """``use_mxu="auto"`` (engine default) engages the MXU one-hot
    reduce when the program's K x B payload width amortizes the
    one-hot materialization toll (scalemodel.resolve_use_mxu: sum
    engages at width >= 2 — ppr's B=8 batch and colfilter's K=20 do,
    scalar f32 flagships stay on the fused VPU path bit-for-bit;
    min/max never auto-engage, the tournament is for the measured
    A/B).  True/False force the path for A/B benches and tests."""
    if isinstance(use_mxu, bool):
        return use_mxu
    if use_mxu != "auto":
        raise ValueError(f"unknown use_mxu {use_mxu!r}")
    from lux_tpu import scalemodel
    kind = getattr(program, "reduce", "sum")
    return scalemodel.resolve_use_mxu(kind, mxu_wide_of(program))


def common_graph_arrays(sg: ShardedGraph, dev):
    """deg + nvp, the apply-epilogue arrays every layout needs.  The
    valid-vertex mask is DERIVED on device from the per-part counts
    (iota < nvp, see program.vmask_of's [rows, 1] int32 convention)
    instead of shipping a [rows, vpad] bool array — 68 MB of the
    RMAT26 single-chip fit (PERF_NOTES)."""
    return dict(deg=dev(sg.deg_padded),
                nvp=dev(sg.nv_part[sg.part_ids()].astype(
                    np.int32)[:, None]))


def _owner_edge_arrays(owner, dev):
    """The owner layout's per-slot arrays: packed (uint32 src<<7|rel
    + uint16 live-lane counts) or classic (int32 src + int8 rel) —
    see ops/owner.OwnerLayout's packed encoding note."""
    if owner.packed:
        return dict(own_sr=dev(owner.src_rel),
                    own_nv=dev(owner.n_valid))
    return dict(own_src=dev(owner.src_local),
                own_rel=dev(owner.rel_dst))


def build_graph_arrays(sg: ShardedGraph, layout: str, needs_dst: bool,
                       tile_w: int, tile_e: int, device: bool = True):
    """Per-part graph arrays (all leading dim num_parts) for either
    edge layout; returns (arrays dict, TiledLayout|None).

    device=False keeps them as host numpy — mesh engines place them
    with ``shard_over_parts`` directly (one H2D per shard), instead of
    staging everything through the default device first."""
    dev = jnp.asarray if device else np.asarray
    common = common_graph_arrays(sg, dev)
    if layout == "flat":
        arrays = dict(src_slot=dev(sg.src_slot),
                      dst_local=dev(sg.dst_local), **common)
        if sg.weighted:
            arrays["weight"] = dev(sg.edge_weight)
        return arrays, None
    if layout != "tiled":
        raise ValueError(f"unknown layout {layout!r}")
    lay = TiledLayout.build(
        sg.row_ptr_local, sg.dst_local, sg.vpad, W=tile_w, E=tile_e,
        sizing_row_ptr=(None if sg.local_parts is None
                        else sg.sizing_row_ptr()))
    arrays = dict(src_slot=dev(lay.chunk(sg.src_slot)),
                  rel_dst=dev(lay.rel_dst),
                  chunk_start=dev(lay.chunk_start),
                  last_chunk=dev(lay.last_chunk), **common)
    if sg.weighted:
        arrays["weight"] = dev(lay.chunk(sg.edge_weight))
    if needs_dst:
        arrays["chunk_tile"] = dev(lay.chunk_tile)
    return arrays, lay


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
                 layout: str = "tiled", tile_w: int = 128,
                 tile_e: int = 512, use_mxu: bool | str = "auto",
                 reduce_method: str = "auto",
                 pair_threshold: int | None = None,
                 pair_min_fill: int | str | None = None,
                 pair_stream: bool | None = None,
                 stream_msgs: bool | None = None,
                 exchange: str = "auto",
                 gather: str = "flat",
                 owner_tile_e: int | None = None,
                 owner_minmax_fused: bool = False,
                 stats_cap: int | None = None,
                 health: bool = False,
                 audit: str | None = None):
        if mesh is not None and sg.num_parts % mesh.devices.size != 0:
            raise ValueError(
                f"num_parts={sg.num_parts} not divisible by mesh size "
                f"{mesh.devices.size}")
        exchange = resolve_exchange(exchange, sg, program)
        if exchange == "owner" and (
                program.needs_dst
                or program.edge_value_from_dot is not None):
            raise ValueError(
                "exchange='owner' supports programs whose edge_value "
                "depends only on the source state (owner-side parts "
                "hold no destination state)")
        _check_local_parts(sg, mesh, pair_threshold)
        self.exchange = exchange
        # psum_scatter-style fused min/max owner exchange (ring
        # reduce-scatter, ops/owner.py) — opt-in until measured on a
        # real mesh
        self.owner_minmax_fused = bool(owner_minmax_fused)
        self.pairs = None
        # paged two-level gather (ops/pagegather.py): replaces the
        # per-edge state-table gather with a page-binned row fetch +
        # Pallas lane shuffle; an alternative row-delivery layout to
        # the pair plan, so the two never compose
        self.page_plan = None
        self.gather = "flat"
        if gather != "flat":
            if gather in ("paged", "pagemajor") \
                    and pair_threshold is not None:
                raise ValueError(
                    f"gather={gather!r} subsumes pair delivery (both "
                    f"are row-granular layouts); build without "
                    f"pair_threshold")
            if pair_threshold is None:
                self._setup_paged(sg, gather, program, exchange)
        if pair_threshold is not None:
            sg = self._setup_pairs(sg, pair_threshold, mesh, layout,
                                   program, pair_min_fill)
        from lux_tpu.ops.pairs import (resolve_pair_dot_stream,
                                       resolve_pair_stream)
        self.pair_stream = resolve_pair_stream(pair_stream, self.pairs)
        # the SDDMM (K-dim) pair path streams by the shared 1 GB
        # budget (ops/tiled.STREAM_MSG_BYTES) instead of always: under
        # it the monolithic lax.map measured best; past it the stacked
        # per-row partials are the 67.7 GB NetFlix compile allocation
        self.pair_dot_stream = resolve_pair_dot_stream(
            pair_stream, self.pairs, len(sg.part_ids()),
            _dot_kdim(program))
        # auto: stream once the [rows, C, E] f32 message temporary
        # passes the budget — vmap materializes EVERY materialized
        # part's messages together (sg here is the pair residual when
        # pairs are on; mesh devices hold rows/ndev of this, so the
        # estimate is conservative there)
        rows = len(sg.part_ids())
        self.stream_chunks = (rows * sg.epad * 4 > STREAM_MSG_BYTES
                              if stream_msgs is None
                              else bool(stream_msgs))
        if program.edge_value_from_dot is not None:
            if program.reduce != "sum":
                raise ValueError(
                    "edge_value_from_dot requires reduce='sum' (the "
                    "mask-matmul partial reduction is a sum)")
            if not sg.weighted:
                raise ValueError(
                    "edge_value_from_dot requires a weighted graph "
                    "(the dot path passes per-edge weights)")
        self.sg = sg
        self.program = program
        self.mesh = mesh
        self.use_mxu = resolve_use_mxu(use_mxu, program)
        # health=True: run()/segmented drivers use the watchdog loop
        # variants (run_health / run_until_health, compiled lazily);
        # False leaves every watchdog-free program untouched
        self.health = bool(health)
        from lux_tpu.telemetry import DEFAULT_STATS_CAP
        self.stats_cap = int(stats_cap or DEFAULT_STATS_CAP)
        self.reduce_method = resolve_reduce_method(reduce_method)
        dev = jnp.asarray if mesh is None else np.asarray
        with telemetry.span("build.dense_layout"):
            arrays = self._dense_layout(dev, layout, tile_w, tile_e,
                                        owner_tile_e)
        if program.extra_arrays is not None:
            # program-contributed per-part constants (e.g. per-query
            # reset vectors): jit ARGUMENTS like every graph array —
            # the no-closure convention holds for query state too
            for k, v in program.extra_arrays(sg).items():
                arrays[f"prog_{k}"] = dev(np.asarray(v))
        if self.pairs is not None:
            arrays["pair_rowbind"] = dev(self.pairs.rowbind)
            arrays["pair_rel"] = dev(self.pairs.rel_dst)
            arrays["pair_tile_pos"] = dev(self.pairs.tile_pos)
            if self.pairs.weight is not None:
                arrays["pair_weight"] = dev(self.pairs.weight)
            if program.edge_value_from_dot is not None:
                # the SDDMM pair path also fetches each row's dst tile
                arrays["pair_row_tile"] = dev(self.pairs.row_tile)
                arrays["pair_tile0"] = dev(
                    (np.arange(sg.num_parts) *
                     (sg.vpad // 128)).astype(np.int32)[:, None])
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

    def _dense_layout(self, dev, layout, tile_w, tile_e,
                      owner_tile_e) -> dict:
        """Arrays of the edge layout (paged plan, owner chunks or
        tiled chunks), each through ``dev``; sets ``self.owner`` /
        ``self.tiles``."""
        sg, program = self.sg, self.program
        if self.page_plan is not None:
            # the paged plan IS the edge layout: neither the tiled
            # chunk arrays nor the owner chunk layout is built
            self.owner = None
            self.tiles = None
            return dict(common_graph_arrays(sg, dev),
                        **self._paged_arrays(dev, program))
        if self.exchange == "owner":
            from lux_tpu.ops.owner import OwnerLayout
            self.owner = OwnerLayout.build(sg, E=owner_tile_e or 256)
            self.tiles = None
            arrays = dict(
                **common_graph_arrays(sg, dev),
                **_owner_edge_arrays(self.owner, dev),
                own_cs=dev(self.owner.chunk_start),
                own_lc=dev(self.owner.last_chunk))
            if self.owner.weight is not None:
                arrays["own_w"] = dev(self.owner.weight)
            if self.owner.streams():
                # fused streamed combine: never materializes [C, W]
                ep, et = self.owner.extract_plan()
                arrays["own_ep"] = dev(ep)
                arrays["own_et"] = dev(et)
            return arrays
        self.owner = None
        arrays, self.tiles = build_graph_arrays(
            sg, layout,
            program.needs_dst
            or program.edge_value_from_dot is not None,
            tile_w, tile_e, device=self.mesh is None)
        return arrays

    # -- pair-lane fast path (ops/pairs.py) ----------------------------

    def _setup_pairs(self, sg: ShardedGraph, threshold: int, mesh,
                     layout, program, min_fill=None):
        """Split dense (src-tile, dst-tile) pair edges out of the
        regular gather path (see ops/pairs.py): gather cost is per ROW
        fetched, so pair rows fetch a 128-wide source state row once
        and deliver positionally.  Works for any num_parts, with or
        without a mesh, and on weighted graphs (per-lane weights).
        Returns the RESIDUAL ShardedGraph the normal machinery should
        run on."""
        from lux_tpu.ops.pairs import plan_sharded_pairs

        if layout != "tiled":
            raise ValueError("pair_threshold requires the tiled layout")
        if getattr(program, "batch", None) is not None:
            raise ValueError(
                "pair_threshold does not support query-batched "
                "programs: pair delivery reads scalar vertex state "
                "(ops/pairs.pair_partial); run batched engines "
                "without pairs")
        if program.needs_dst and program.edge_value_from_dot is None:
            raise ValueError("pair_threshold supports programs whose "
                             "edge_value depends only on the source "
                             "state, or on <src, dst> via "
                             "edge_value_from_dot")
        sp, residual = plan_sharded_pairs(sg, threshold,
                                          min_fill=min_fill,
                                          kdim=_dot_kdim(program))
        self.pairs = sp                      # None if nothing dense
        return residual

    def _pair_red(self, flat_state, g):
        """Pair-lane delivery + reduce for one part -> [vpad] partial
        (identity where pairs contribute nothing)."""
        from lux_tpu.ops.pairs import pair_partial, pair_partial_streamed

        prog = self.program
        fn = pair_partial_streamed if self.pair_stream else pair_partial
        red = fn(
            self.pairs, flat_state, g["pair_rowbind"], g["pair_rel"],
            g.get("pair_weight"), g["pair_tile_pos"], prog.reduce,
            lambda vals, w: prog.edge_value(vals, None, w),
            reduce_method=self.reduce_method)
        return red[:self.sg.vpad]

    # -- paged two-level gather (ops/pagegather.py) --------------------

    def _setup_paged(self, sg: ShardedGraph, gather: str, program,
                     exchange: str):
        """Build the page-binned delivery plan and resolve
        ``gather="auto"`` by the scalemodel break-even on its MEASURED
        unique-page ratio / row fill (scalemodel.page_gather_ns) —
        ops/pagegather.engine_page_plan holds the shared rule."""
        from lux_tpu.ops.pagegather import engine_page_plan

        self.page_plan = engine_page_plan(sg, gather, program, exchange)
        if self.page_plan is not None:
            self.gather = self.page_plan.mode

    def _paged_arrays(self, dev, program):
        """The paged plan's graph arrays
        (ops/pagegather.plan_graph_arrays)."""
        from lux_tpu.ops.pagegather import plan_graph_arrays
        return plan_graph_arrays(
            self.page_plan, dev, owner=self.exchange == "owner",
            dot=getattr(program, "edge_value_from_dot", None)
            is not None,
            num_parts=self.sg.num_parts, vpad=self.sg.vpad)

    def _paged_red(self, flat_state, g):
        """Paged delivery + reduce for one part -> [vpad, ...] (total
        coverage: the plan serves EVERY edge, no residual).  The
        page-major plan rides the same call: ``pg_vrs`` binds each
        virtual reduce row to its full-fill gather row
        (ops/pagegather.PagedPlan mode="pagemajor")."""
        from lux_tpu.ops.pagegather import paged_partial

        prog = self.program
        red = paged_partial(
            self.page_plan, flat_state, g["pg_ids"], g["pg_sl"],
            g["pg_rel"], g.get("pg_w"), g["pg_tp"], prog.reduce,
            lambda vals, w: prog.edge_value(vals, None, w),
            reduce_method=self.reduce_method,
            vrow_src=g.get("pg_vrs"))
        return red[:self.sg.vpad]

    def _paged_dot_red(self, flat_state, g):
        """Paged SDDMM delivery (ops/pagegather.paged_partial_dot) —
        pair_partial_dot's MXU pipeline plus the one-hot lane-shuffle
        contraction."""
        from lux_tpu.ops.pagegather import paged_partial_dot

        red = paged_partial_dot(
            self.page_plan, flat_state, g["pg_ids"], g["pg_sl"],
            g["pg_rel"], g["pg_w"], g["pg_rt"], g["pg_tp"],
            g["pg_t0"][0], self.program.edge_value_from_dot)
        return red[:self.sg.vpad]

    def _part_step_paged(self, flat_state, old_p, g):
        with jax.named_scope("lux_gather_reduce"):
            red = self._paged_red(flat_state, g)
        with jax.named_scope("lux_apply"):
            return self._apply_epilogue(old_p, red, g)

    def _part_step_paged_dot(self, flat_state, old_p, g):
        with jax.named_scope("lux_dot_reduce"):
            red = self._paged_dot_red(flat_state, g)
        with jax.named_scope("lux_apply"):
            return self._apply_epilogue(old_p, red, g)

    # -- state placement ----------------------------------------------

    def init_state(self):
        """Fresh state on the engine's devices, under a ``state.init``
        span (``bytes``; the transfer is asynchronous, so the span
        ends at dispatch)."""
        with telemetry.span("state.init") as sp:
            state = self._consume_pending_init()
            if state is None:
                state = self.program.init(self.sg)
            state = np.asarray(state)
            sp.count(bytes=state.nbytes)
            if self.mesh is not None:
                return shard_over_parts(self.mesh, [state],
                                        self.sg.num_parts)[0]
            return jnp.asarray(state)

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

    def _apply_epilogue(self, old_p, red, g):
        sg, prog = self.sg, self.program
        vm = vmask_of(g, sg.vpad)
        extra = {k[5:]: g[k] for k in g if k.startswith("prog_")}
        ctx = PartCtx(deg=g["deg"], vmask=vm, nv=sg.nv, ne=sg.ne,
                      extra=extra or None)
        new = prog.apply(old_p, red, ctx)
        keep = vm.reshape(vm.shape + (1,) * (new.ndim - 1))
        return jnp.where(keep, new, old_p)

    def _part_msgs(self, flat_state, old_p, g):
        """Phase 1 (gather): per-edge source gather + message values."""
        prog, sg, lay = self.program, self.sg, self.tiles
        src_vals = jnp.take(flat_state, g["src_slot"], axis=0)
        if prog.needs_dst:
            if lay is None:
                dst_idx = jnp.minimum(g["dst_local"], sg.vpad - 1)
            else:
                # pad lanes carry rel -1 (int8 marker): clip keeps the
                # garbage gather in range; the reduce masks it anyway
                dst_idx = jnp.clip(
                    g["chunk_tile"][:, None] * lay.W + g["rel_dst"],
                    0, sg.vpad - 1)
            dst_vals = jnp.take(old_p, dst_idx, axis=0)
        else:
            dst_vals = None
        msgs = prog.edge_value(src_vals, dst_vals, g.get("weight"))
        if lay is not None and (self.reduce_method == "xla"
                                or msgs.ndim != 2):
            # Keep the (serial, expensive) gather from being fused
            # into the W-wide broadcast consumer, which re-executes
            # it per output lane — measured 3-5x slower on v5e.
            # The Pallas kernel is an opaque boundary and needs no
            # barrier.
            msgs = jax.lax.optimization_barrier(msgs)
        return msgs

    def _part_reduce(self, flat_state, msgs, g):
        """Phase 2 (reduce): scatter-free segment reduction (+ the
        pair-lane delivery, which fetches and reduces in one go)."""
        prog, sg, lay = self.program, self.sg, self.tiles
        if lay is None:
            red = segment_reduce(msgs, g["dst_local"], sg.vpad + 1,
                                 prog.reduce)[:sg.vpad]
        else:
            red = tiled_segment_reduce(
                msgs, lay, g["chunk_start"], g["last_chunk"],
                g["rel_dst"], sg.vpad, prog.reduce, use_mxu=self.use_mxu,
                method=("xla" if msgs.ndim != 2 else
                        "pallas" if self.reduce_method.startswith("pallas")
                        else "xla"),
                interpret=self.reduce_method == "pallas-interpret")
        return self._combine_pairs(flat_state, red, g)

    def _combine_pairs(self, flat_state, red, g):
        if self.pairs is not None:
            red = combine_op(self.program.reduce)(
                red, self._pair_red(flat_state, g))
        return red

    @property
    def _streams(self) -> bool:
        return (self.stream_chunks and self.tiles is not None
                and not self.program.needs_dst)

    def _part_red_streamed(self, flat_state, g):
        """Gather + message + partials in chunk blocks (ops/tiled.
        streamed_chunk_partials), combined to [vpad] with the pair
        contribution — the billion-edge form of gather+reduce."""
        from lux_tpu.ops.tiled import (combine_partials,
                                       streamed_chunk_partials)
        prog, sg, lay = self.program, self.sg, self.tiles
        partials = streamed_chunk_partials(
            flat_state, g["src_slot"], g["rel_dst"], g.get("weight"),
            lay, prog.reduce,
            lambda vals, w: prog.edge_value(vals, None, w),
            self.reduce_method, use_mxu=self.use_mxu)
        red = combine_partials(partials, lay, g["chunk_start"],
                               g["last_chunk"], sg.vpad, prog.reduce,
                               use_mxu=self.use_mxu)
        return self._combine_pairs(flat_state, red, g)

    def _part_step(self, flat_state, old_p, g):
        """g: dict of this part's graph arrays."""
        if self._streams:
            with jax.named_scope("lux_gather_reduce"):
                red = self._part_red_streamed(flat_state, g)
            with jax.named_scope("lux_apply"):
                return self._apply_epilogue(old_p, red, g)
        with jax.named_scope("lux_gather"):
            msgs = self._part_msgs(flat_state, old_p, g)
        with jax.named_scope("lux_reduce"):
            red = self._part_reduce(flat_state, msgs, g)
        with jax.named_scope("lux_apply"):
            return self._apply_epilogue(old_p, red, g)

    def _part_step_dot(self, flat_state, old_p, g):
        red = self._part_dot_red(flat_state, old_p, g)
        return self._apply_epilogue(old_p, red, g)

    def _part_dot_red(self, flat_state, old_p, g):
        """Tiled-layout reduction for programs whose dst dependence is
        only the inner product <src, dst> (program.edge_value_from_dot).

        The dst row-gather (~9 ns/edge, 75% of a colfilter iteration)
        is replaced by MXU matmuls against the chunk's destination
        TILE: per chunk, D = src @ tile^T gives every (edge, dst-lane)
        dot; a lane-compare selects each edge's own dot; and the
        message reduction is a one-hot mask matmul — the SGD gradient
        as two batched matmuls (the TPU answer to the reference's
        shared-memory gradient staging, colfilter_gpu.cu:41-102).
        Chunks are processed in lax.map blocks so the [B, E, W]
        intermediates stay small.
        """
        sg, lay, prog = self.sg, self.tiles, self.program
        W, E = lay.W, lay.E
        C = lay.n_chunks
        Kdim = old_p.shape[-1]

        n_tiles = lay.n_tiles
        old_pad = jnp.pad(old_p, ((0, n_tiles * W - sg.vpad), (0, 0)))
        tiles = old_pad.reshape(n_tiles, W, Kdim)
        rel = g["rel_dst"]
        wgt = g.get("weight")

        B = max(1, min(DOT_BLOCK_CHUNKS, C))
        nB = (C + B - 1) // B
        Cp = nB * B

        def pad_c(x):
            return jnp.pad(x, ((0, Cp - C),) + ((0, 0),) * (x.ndim - 1))

        lanes = jnp.arange(W, dtype=rel.dtype)

        def block(args):
            # BOTH gathers happen per block: materializing the [C, E,
            # K] source values / [C, W, K] tile rows whole-graph is
            # ~15 GB at the NetFlix shape (measured OOM, round 5) —
            # the block bound must cover the gather outputs, not just
            # the [B, E, W] dot intermediate
            slot_b, ct_b, r, w = args
            s = jnp.take(flat_state, slot_b, axis=0)       # [B, E, K]
            s = jax.lax.optimization_barrier(s)
            t = jnp.take(tiles, jnp.minimum(ct_b, n_tiles - 1),
                         axis=0)                           # [B, W, K]
            D = jnp.einsum("bek,bwk->bew", s, t,
                           preferred_element_type=s.dtype)
            mask = r[..., None] == lanes                   # [B, E, W]
            dot = jnp.sum(jnp.where(mask, D, 0), axis=-1)  # [B, E]
            msgs = prog.edge_value_from_dot(s, dot, w)     # [B, E, K]
            return jnp.einsum("bew,bek->bwk", mask.astype(s.dtype),
                              msgs)                        # [B, W, K]

        args = (pad_c(g["src_slot"]).reshape(nB, B, E),
                pad_c(g["chunk_tile"]).reshape(nB, B),
                pad_c(rel).reshape(nB, B, E),
                pad_c(wgt).reshape(nB, B, E))
        partials = jax.lax.map(block, args).reshape(Cp, W, Kdim)[:C]
        red = combine_chunks(partials, lay, g["chunk_start"],
                             g["last_chunk"], prog.reduce,
                             use_mxu=self.use_mxu)
        red = red.reshape(n_tiles * W, Kdim)[:sg.vpad]
        if self.pairs is not None:
            from lux_tpu.ops.pairs import (pair_partial_dot,
                                           pair_partial_dot_streamed)
            fn = (pair_partial_dot_streamed if self.pair_dot_stream
                  else pair_partial_dot)
            pred = fn(
                self.pairs, flat_state, g["pair_rowbind"],
                g["pair_rel"], g["pair_weight"], g["pair_row_tile"],
                g["pair_tile_pos"], g["pair_tile0"][0],
                prog.edge_value_from_dot)
            red = red + pred[:sg.vpad]
        return red

    def _parts_step(self, local_state, full_state, g_local):
        """vmap _part_step over this device's parts."""
        sg = self.sg
        flat = full_state.reshape((sg.num_parts * sg.vpad,) +
                                  full_state.shape[2:])
        use_dot = self.program.edge_value_from_dot is not None
        if self.page_plan is not None:
            step = (self._part_step_paged_dot if use_dot
                    else self._part_step_paged)
        else:
            step = (self._part_step_dot
                    if use_dot and self.tiles is not None
                    else self._part_step)
        return jax.vmap(lambda old, g: step(flat, old, g))(
            local_state, g_local)

    # -- owner-side exchange (ops/owner.py) ---------------------------

    def _msg_dtype(self, state):
        """Message dtype without running edge_value (abstract eval)."""
        probe_w = (jax.ShapeDtypeStruct((1, 1), jnp.float32)
                   if self.sg.weighted else None)
        probe_s = jax.ShapeDtypeStruct((1, 1) + state.shape[2:],
                                       state.dtype)
        return jax.eval_shape(
            lambda s, w: self.program.edge_value(s, None, w),
            probe_s, probe_w).dtype

    def _owner_contribs(self, state_rows, g):
        """Per-source-part contributions (ops/owner.owner_contribs;
        paged engines run the page-binned shard delivery under the
        same generation scan, ops/pagegather.paged_owner_contribs)."""
        prog = self.program
        if self.page_plan is not None:
            from lux_tpu.ops.pagegather import paged_owner_contribs
            return paged_owner_contribs(
                self.page_plan, state_rows, g, prog.reduce,
                lambda vals, wt: prog.edge_value(vals, None, wt),
                self._msg_dtype(state_rows), self.sg.num_parts,
                self.reduce_method)
        from lux_tpu.ops.owner import owner_contribs

        return owner_contribs(
            self.owner, state_rows, g,
            prog.reduce,
            lambda vals, wt: prog.edge_value(vals, None, wt),
            self._msg_dtype(state_rows), self.sg.num_parts,
            self.reduce_method, use_mxu=self.use_mxu)

    def _owner_exchange(self, acc):
        """Reduce-scatter of contributions (ops/owner.owner_exchange)."""
        from lux_tpu.ops.owner import owner_exchange

        return owner_exchange(
            acc, self.program.reduce,
            axis=None if self.mesh is None else PARTS_AXIS,
            ndev=1 if self.mesh is None else self.mesh.devices.size,
            minmax_fused=self.owner_minmax_fused)

    def _owner_apply(self, state_rows, red_rows, flat_state, g):
        """Pair contribution + apply epilogue, vmapped over the local
        destination parts.  flat_state (full [P*vpad, ...] table) is
        None when no pair delivery needs it."""

        def per_part(old_p, red_p, gp):
            if flat_state is not None:
                red_p = self._combine_pairs(flat_state, red_p, gp)
            return self._apply_epilogue(old_p, red_p, gp)

        return jax.vmap(per_part)(state_rows, red_rows, g)

    def _owner_step(self, state, g):
        """One owner-exchange iteration for the locally-held rows
        (single device: all parts; under shard_map: this device's)."""
        sg = self.sg
        with jax.named_scope("lux_gen_exchange"):
            if (self.page_plan is not None
                    and self.page_plan.mode == "pagemajor"):
                # page-major routing: full message rows all_to_all to
                # their destination parts, reduced receiver-side — no
                # per-tile partials, no separate owner exchange
                # (ops/pagegather.pagemajor_owner_deliver)
                from lux_tpu.ops.pagegather import \
                    pagemajor_owner_deliver
                prog = self.program
                red = pagemajor_owner_deliver(
                    self.page_plan, state, g, prog.reduce,
                    lambda vals, wt: prog.edge_value(vals, None, wt),
                    self._msg_dtype(state), sg.num_parts,
                    self.reduce_method,
                    axis=None if self.mesh is None
                    else PARTS_AXIS)[:, :sg.vpad]
                return self._owner_apply(state, red, None, g)
            acc = self._owner_contribs(state, g)
            red = self._owner_exchange(acc)[:, :sg.vpad]
        flat = None
        if self.pairs is not None:
            # pair rows are fetched from the FULL table (row-granular
            # fetches, not subject to the element-gather big-table
            # tax); on the mesh the all_gather exists only for them
            full = (state if self.mesh is None else
                    jax.lax.all_gather(state, PARTS_AXIS, tiled=True))
            flat = full.reshape((sg.num_parts * sg.vpad,) +
                                full.shape[2:])
        return self._owner_apply(state, red, flat, g)

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

        if self.exchange == "owner":
            if self.mesh is None:
                def core(state, *gargs):
                    return self._owner_step(state,
                                            dict(zip(keys, gargs)))
            else:
                P = PartitionSpec

                @functools.partial(
                    jax.shard_map, mesh=self.mesh,
                    in_specs=(P(PARTS_AXIS),) * (1 + len(keys)),
                    out_specs=P(PARTS_AXIS))
                def core(state, *gargs):
                    return self._owner_step(state,
                                            dict(zip(keys, gargs)))

            if self.program.name:
                core = jax.named_scope(
                    f"lux_{self.program.name}")(core)
            self._step_core = core
            jitted = jax.jit(core, donate_argnums=0)
            self._register_variant(
                "step", jitted,
                lambda: (self._audit_state_sds, *self.graph_args))
            return lambda state: jitted(state, *self.graph_args)

        if self.mesh is None:
            def core(state, *gargs):
                g = dict(zip(keys, gargs))
                return self._parts_step(state, state, g)
        else:
            P = PartitionSpec

            @functools.partial(jax.shard_map, mesh=self.mesh,
                               in_specs=(P(PARTS_AXIS),) * (1 + len(keys)),
                               out_specs=P(PARTS_AXIS))
            def core(state, *gargs):
                g = dict(zip(keys, gargs))
                # The per-iteration vertex-state exchange over ICI.
                with jax.named_scope("lux_exchange"):
                    full = jax.lax.all_gather(state, PARTS_AXIS,
                                              tiled=True)
                return self._parts_step(state, full, g)

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

    # timed_phases phases whose measured seconds CONTAIN the step's
    # collectives — the comm observatory's attribution anchor
    # (lux_tpu/comms.py; observe._comm_attribution grades the wire
    # lower bound against exactly these phases)
    COMM_PHASES = ("exchange", "gen_exchange")

    @functools.cached_property
    def _audit_state_sds(self):
        """Abstract stand-in for the iterated state (shape/dtype from
        the program's init, no device placement).  The materialized
        init is STASHED for the next ``init_state`` call, so an
        audited-then-run engine (bench.py -audit) pays for exactly
        one host init, same as an unaudited one."""
        st = np.asarray(self.program.init(self.sg))
        self._pending_init = st
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
        host)."""
        from lux_tpu.parallel.multihost import fetch_global
        with telemetry.span("state.fetch") as sp:
            host = fetch_global(state)
            sp.count(bytes=host.nbytes)
            return self.sg.from_padded(host)

    # -- per-iteration phase observability ----------------------------

    @functools.cached_property
    def _phase_jits(self):
        """One compiled program per phase (exchange / gather / reduce /
        apply), each returning (output, scalar checksum) — the scalar
        fetch is the O(1)-byte completion fence.  Separate
        executables deliberately prevent cross-phase fusion, so the
        split is honest at the cost of materializing phase outputs."""
        from lux_tpu.engine.phased import cksum, mesh_wrap

        keys = self._graph_keys
        sg = self.sg

        if (self.program.edge_value_from_dot is not None
                and (self.tiles is not None
                     or self.page_plan is not None)):
            # dot-path programs (colfilter): the src gather, MXU tile
            # dots and one-hot reduction are one lax.map pipeline by
            # design, so they time as ONE 'dot_reduce' phase — closing
            # the round-2 hole where this raised NotImplementedError
            # (paged engines time their page-fetch + shuffle + SDDMM
            # pipeline under the same phase name)
            def dot_exchange(state, *gargs):
                full = state
                if self.mesh is not None:
                    full = jax.lax.all_gather(state, PARTS_AXIS,
                                              tiled=True)
                flat = full.reshape((sg.num_parts * sg.vpad,) +
                                    full.shape[2:])
                return flat, cksum(flat)

            def dot_reduce(flat, state, *gargs):
                g = dict(zip(keys, gargs))
                if self.page_plan is not None:
                    red = jax.vmap(
                        lambda old, gp: self._paged_dot_red(flat, gp))(
                        state, g)
                else:
                    red = jax.vmap(
                        lambda old, gp: self._part_dot_red(
                            flat, old, gp))(state, g)
                return red, cksum(red)

            def dot_apply(state, red, *gargs):
                g = dict(zip(keys, gargs))
                new = jax.vmap(self._apply_epilogue)(state, red, g)
                return new, cksum(new)

            fns = dict(exchange=dot_exchange, dot_reduce=dot_reduce,
                       apply=dot_apply)
            if self.mesh is not None:
                P = PartitionSpec
                S, R = P(PARTS_AXIS), P()
                wrap = mesh_wrap(self.mesh, len(keys), S, R)
                fns = dict(exchange=wrap(dot_exchange, (S,), R),
                           dot_reduce=wrap(dot_reduce, (R, S), S),
                           apply=wrap(dot_apply, (S, S), S))
            return {k: jax.jit(f) for k, f in fns.items()}
        # dot-path programs on the FLAT layout never take the dot
        # shortcut (it requires tiles, see use_dot in _parts_step), so
        # their compiled step IS the generic gather/reduce pipeline
        # below — time it with the generic phases (closes the last
        # round-4 stub, VERDICT weak #6)

        if self.exchange == "owner":
            # owner mode has no separable gather: generation (scan
            # over source parts, small-shard gathers) and the
            # reduce_scatter exchange are one fused phase by design
            def gen_exchange(state, *gargs):
                g = dict(zip(keys, gargs))
                acc = self._owner_contribs(state, g)
                red = self._owner_exchange(acc)[:, :sg.vpad]
                return red, cksum(red)

            def owner_apply(state, red, *gargs):
                g = dict(zip(keys, gargs))
                flat = None
                if self.pairs is not None:
                    full = (state if self.mesh is None else
                            jax.lax.all_gather(state, PARTS_AXIS,
                                               tiled=True))
                    flat = full.reshape((sg.num_parts * sg.vpad,) +
                                        full.shape[2:])
                new = self._owner_apply(state, red, flat, g)
                return new, cksum(new)

            fns = dict(gen_exchange=gen_exchange, apply=owner_apply)
            if self.mesh is not None:
                P = PartitionSpec
                S, R = P(PARTS_AXIS), P()
                wrap = mesh_wrap(self.mesh, len(keys), S, R)
                fns = dict(gen_exchange=wrap(gen_exchange, (S,), S),
                           apply=wrap(owner_apply, (S, S), S))
            return {k: jax.jit(f) for k, f in fns.items()}

        def exchange(state, *gargs):
            full = state
            if self.mesh is not None:
                full = jax.lax.all_gather(state, PARTS_AXIS, tiled=True)
            flat = full.reshape((sg.num_parts * sg.vpad,) +
                                full.shape[2:])
            return flat, cksum(flat)

        def gather(flat, state, *gargs):
            g = dict(zip(keys, gargs))
            msgs = jax.vmap(
                lambda old, gp: self._part_msgs(flat, old, gp))(state, g)
            return msgs, cksum(msgs)

        def reduce(flat, msgs, *gargs):
            g = dict(zip(keys, gargs))
            red = jax.vmap(
                lambda m, gp: self._part_reduce(flat, m, gp))(msgs, g)
            return red, cksum(red)

        def gather_reduce(flat, state, *gargs):
            # the streamed step fuses gather+message+reduce per chunk
            # block — instrument it as ONE phase so the report reflects
            # what the compiled step actually runs (and stays within
            # the memory bound streaming exists for).  Paged engines
            # fuse page-fetch + lane shuffle + reduce the same way.
            g = dict(zip(keys, gargs))
            if self.page_plan is not None:
                red = jax.vmap(lambda gp: self._paged_red(flat, gp))(g)
            else:
                red = jax.vmap(
                    lambda gp: self._part_red_streamed(flat, gp))(g)
            return red, cksum(red)

        def apply(state, red, *gargs):
            g = dict(zip(keys, gargs))
            new = jax.vmap(self._apply_epilogue)(state, red, g)
            return new, cksum(new)

        if self._streams or self.page_plan is not None:
            fns = dict(exchange=exchange, gather_reduce=gather_reduce,
                       apply=apply)
            specs = dict(exchange=((0,), 1), gather_reduce=((1, 0), 0),
                         apply=((0, 0), 0))
        else:
            fns = dict(exchange=exchange, gather=gather, reduce=reduce,
                       apply=apply)
            specs = dict(exchange=((0,), 1), gather=((1, 0), 0),
                         reduce=((1, 0), 0), apply=((0, 0), 0))
        if self.mesh is not None:
            P = PartitionSpec
            S, R = P(PARTS_AXIS), P()
            wrap = mesh_wrap(self.mesh, len(keys), S, R)
            fns = {name: wrap(fn,
                              tuple(R if r else S
                                    for r in specs[name][0]),
                              R if specs[name][1] else S)
                   for name, fn in fns.items()}
        return {k: jax.jit(f) for k, f in fns.items()}

    def timed_phases(self, state, iters: int = 1):
        """Instrumented stepwise iterations -> (state, [{phase: s}]).

        The analogue of the reference's per-iteration per-part
        loadTime/compTime/updateTime -verbose prints (reference
        sssp_gpu.cu:513-518).  Phases run as SEPARATE fenced programs
        (engine/phased.py), so absolute times carry dispatch overhead
        the fused run does not; read them for relative weight, not for
        GTEPS."""
        from lux_tpu.engine.phased import PhaseTimer
        from lux_tpu.timing import fetch
        jits = self._phase_jits
        gargs = self.graph_args
        report = []
        for _ in range(iters):
            pt = PhaseTimer(fetch)
            if "gen_exchange" in jits:    # owner exchange: two phases
                red = pt("gen_exchange", jits["gen_exchange"], state,
                         *gargs)
                state = pt("apply", jits["apply"], state, red, *gargs)
                report.append(pt.t)
                continue
            flat = pt("exchange", jits["exchange"], state, *gargs)
            if "dot_reduce" in jits:      # dot path: one reduce phase
                red = pt("dot_reduce", jits["dot_reduce"], flat,
                         state, *gargs)
            elif "gather_reduce" in jits:  # streamed step: one phase
                red = pt("gather_reduce", jits["gather_reduce"], flat,
                         state, *gargs)
            else:
                msgs = pt("gather", jits["gather"], flat, state, *gargs)
                red = pt("reduce", jits["reduce"], flat, msgs, *gargs)
            state = pt("apply", jits["apply"], state, red, *gargs)
            report.append(pt.t)
        return state, report


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
