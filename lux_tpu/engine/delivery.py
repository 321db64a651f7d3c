"""The delivery layer: how each edge's message reaches its destination.

Both engines iterate ``new = apply(old, reduce over in-edges of
msg(source state))`` — the reference's per-CTA gather of source
values, CUB BlockScan and atomicAdd / atomicMin into the destination
(pull_model.inl:423-470, pagerank_gpu.cu:104-151,
sssp_gpu.cu:48-82).  WHICH arrays hold the edges and HOW the
per-edge fetch and the segment reduction run is the delivery, and
this module is the only place under ``lux_tpu/engine/`` that knows the
layouts that exist:

  flat       edge-order gather + XLA scatter (ops/segment.py; the
             correctness oracle)
  tiled      128-vertex tiles of E-edge chunks, scatter-free
             (ops/tiled.py), optionally streamed in chunk blocks
  pair-lane  dense (src-tile, dst-tile) pairs leave the per-edge
             gather for row fetches (ops/pairs.py); the residual
             stays tiled
  paged /    page-binned row fetch + lane shuffle, total coverage
  pagemajor  (ops/pagegather.py)
  owner      per-source-part generation under a scan + a
             reduce-scatter in place of the state all_gather
             (ops/owner.py), over chunks or over a paged plan

``Delivery.build`` resolves the shared options from the program's
facts, validates their combinations (one copy of each error), builds
the plans and the arrays once, and its methods are the dense per-part
reduction of each layout.  The only thing a caller supplies at call
time is the message function ``msg(vals, w)``: the pull engine's
``edge_value(vals, None, w)``, the push engine's relax + identity
mask.  The engines keep the loops, the state's placement and
``all_gather``, apply / update, the sparse frontier path and their
``named_scope``s.

Imports from ``ops/``, ``parallel/``, ``graph``, ``scalemodel`` and
``telemetry`` only — never from an engine, an app or ``serve``
(tests/test_delivery.py holds the direction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu import scalemodel, telemetry
from lux_tpu.graph import ShardedGraph
from lux_tpu.ops import owner as owner_ops
from lux_tpu.ops import pagegather, pairs as pair_ops
from lux_tpu.ops.segment import segment_reduce
from lux_tpu.ops.tiled import (STREAM_MSG_BYTES, TiledLayout,
                               combine_chunks, combine_op,
                               combine_partials, method_args,
                               streamed_chunk_partials,
                               tiled_segment_reduce)
from lux_tpu.parallel.mesh import PARTS_AXIS, local_part_rows


# chunks per lax.map block in the dot path: bounds the [B, E, W]
# intermediate (~32 MB at the default tile sizes; 128 measured best
# on v5e, within 3% of every size from 32 up)
DOT_BLOCK_CHUNKS = 128

# auto exchange: go owner-side once the flat state table passes this
# many bytes — the measured XLA gather emitter step sits at ~64-128 MB
# (PERF_NOTES.md round 3; the probe script last stood at commit
# 3651475), so 96 MB splits the band; below it the owner layout's
# chunk padding isn't worth carrying
OWNER_AUTO_BYTES = 96 << 20


def sharding_demands(gather: str = "flat",
                     pair_threshold: int | None = None):
    """What a delivery demands of the sharding, for the apps'
    ``ShardedGraph.build`` -> ``(vpad_align, tile_e)``: the page-binned
    layouts need 128-aligned vertex padding (ops/pagegather.py; pair
    delivery aligns through ``ShardedGraph.build(pair_threshold=)``
    itself), and the pull apps' default chunk length is 128 with pair
    delivery (residual edges are sparse; shorter chunks waste far
    fewer padded gather slots), else 512."""
    return (128 if gather != "flat" else 8,
            128 if pair_threshold is not None else 512)


def dot_kdim(program) -> int:
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


def resolve_exchange(exchange: str, sg: ShardedGraph, program,
                     itemsize: int | None = None) -> str:
    """'auto' picks 'owner' when the program qualifies (source-only
    edge values; full AND multi-host local-parts builds both qualify)
    and the state table would pay the big-table gather tax; 'gather'
    otherwise.

    itemsize: bytes per VERTEX for the table estimate (itemsize x
    trailing dims).  Default: the program's ``state_bytes`` (pull), or
    its ``identity`` dtype's itemsize (4 when it has none) times its
    query ``batch`` (push: a B-wide batch is B tables)."""
    if exchange == "auto":
        if itemsize is None:
            itemsize = getattr(program, "state_bytes", None)
        if itemsize is None:
            ident = getattr(program, "identity", None)
            itemsize = (np.asarray(ident).dtype.itemsize
                        if ident is not None else 4)
            itemsize *= getattr(program, "batch", None) or 1
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
    trailing dims, the dot_kdim convention), B from the query batch;
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
    kind = getattr(program, "reduce", "sum")
    return scalemodel.resolve_use_mxu(kind, mxu_wide_of(program))


def check_local_parts(sg: ShardedGraph, mesh) -> None:
    """Validate a local-parts (multi-host) ShardedGraph against the
    mesh: the materialized rows must be exactly the rows this process's
    devices hold under the parts sharding.  (Pair delivery IS supported
    there: the pair planner lays each process's rows out against a
    process-group-allreduced common depth profile.)"""
    if sg.local_parts is None:
        return
    if mesh is None:
        raise ValueError(
            "a ShardedGraph built with parts= (multi-host local rows) "
            "requires a mesh")
    expect = local_part_rows(mesh, sg.num_parts)
    got = list(np.asarray(sg.local_parts))
    if got != expect:
        raise ValueError(
            f"local_parts {got} != this process's sharding rows "
            f"{expect}; build with parts=multihost.process_parts(P)")


def common_graph_arrays(sg: ShardedGraph, dev) -> dict:
    """deg + nvp, the apply-epilogue arrays every layout needs.  The
    valid-vertex mask is DERIVED on device from the per-part counts
    (iota < nvp, see program.vmask_of's [rows, 1] int32 convention)
    instead of shipping a [rows, vpad] bool array — 68 MB of the
    RMAT26 single-chip fit (PERF_NOTES)."""
    return dict(deg=dev(sg.deg_padded),
                nvp=dev(sg.nv_part[sg.part_ids()].astype(
                    np.int32)[:, None]))


def build_graph_arrays(sg: ShardedGraph, layout: str, needs_dst: bool,
                       tile_e: int, dev, aligned: bool = False):
    """Per-part graph arrays (all leading dim num_parts) of the flat or
    the tiled edge layout, each through ``dev``; returns (arrays dict,
    TiledLayout|None).  aligned: the tiled layout's lane-aligned
    placement (ops/tiled.py), which adds ``tile_rank``."""
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
        sg.row_ptr_local, sg.dst_local, sg.vpad, E=tile_e,
        sizing_row_ptr=(None if sg.local_parts is None
                        else sg.sizing_row_ptr()),
        aligned=aligned)
    arrays = dict(src_slot=dev(lay.chunk(sg.src_slot)),
                  rel_dst=dev(lay.rel_dst),
                  chunk_start=dev(lay.chunk_start),
                  last_chunk=dev(lay.last_chunk), **common)
    if aligned:
        arrays["tile_rank"] = dev(lay.tile_rank)
    if sg.weighted:
        arrays["weight"] = dev(lay.chunk(sg.edge_weight))
    if needs_dst:
        arrays["chunk_tile"] = dev(lay.chunk_tile)
    return arrays, lay


class Delivery:
    """The resolved edge-delivery layout of one engine: its plans
    (``pairs``, ``page_plan``, ``owner``, ``tiles``), the resolved
    options (``exchange``, ``gather``, ``use_mxu``, ``reduce_method``,
    ``pair_stream``, ``pair_dot_stream``, ``stream_chunks``,
    ``owner_minmax_fused``, ``aligned``) and the per-part reductions over its
    arrays.  Read-only after ``build``; the arrays themselves belong
    to the engine (``keys`` names the ones built here).

    ``sg`` is the graph the dense layout was built from: the pair
    RESIDUAL when pair delivery is on, else the graph handed in.

    Which form a dense iteration takes:
      ``exchange == "owner"``   owner_generate, then owner_pairs
      ``dot_path``              reduce_dot (SDDMM programs on the
                                tiled or paged layout)
      ``fused``                 reduce_fused (streamed chunks, paged)
      otherwise                 messages, then reduce
    """

    @classmethod
    def build(cls, sg: ShardedGraph, program, mesh, *, layout: str,
              tile_e: int, use_mxu, reduce_method: str,
              pair_threshold: int | None, pair_min_fill,
              pair_stream: bool | None, stream_msgs: bool | None,
              exchange: str, gather: str, owner_tile_e: int | None,
              owner_minmax_fused: bool):
        """-> (delivery, arrays).  ``program`` is read for its facts
        only (``reduce``, ``needs_dst``, ``edge_value_from_dot``,
        ``batch``, ``state_bytes`` / ``identity``).  Leaves the spans
        ``build.pair_plan`` (ops/pairs.plan_sharded_pairs) and
        ``build.dense_layout`` (a tiled layout's ``TiledLayout.counts``);
        on a single device each array is
        dispatched to the device as it is built, on a mesh the arrays
        stay host numpy for the engine's ``shard_over_parts``."""
        self = cls()
        self.mesh = mesh
        self.kind = program.reduce
        self.needs_dst = bool(getattr(program, "needs_dst", False))
        self.dot = getattr(program, "edge_value_from_dot",
                           None) is not None
        self.kdim = dot_kdim(program)
        batch = getattr(program, "batch", None)

        if mesh is not None and sg.num_parts % mesh.devices.size != 0:
            raise ValueError(
                f"num_parts={sg.num_parts} not divisible by mesh size "
                f"{mesh.devices.size}")
        exchange = resolve_exchange(exchange, sg, program)
        if exchange == "owner" and (self.needs_dst or self.dot):
            raise ValueError(
                "exchange='owner' supports programs whose edge_value "
                "depends only on the source state (owner-side parts "
                "hold no destination state)")
        check_local_parts(sg, mesh)
        if self.dot:
            if self.kind != "sum":
                raise ValueError(
                    "edge_value_from_dot requires reduce='sum' (the "
                    "mask-matmul partial reduction is a sum)")
            if not sg.weighted:
                raise ValueError(
                    "edge_value_from_dot requires a weighted graph "
                    "(the dot path passes per-edge weights)")
        self.exchange = exchange
        # psum_scatter-style fused min/max owner exchange (ring
        # reduce-scatter, ops/owner.py).  Measured on the four-chip
        # host (bfs.kron23.mesh4, PR 32; PERF.md section 6): the
        # collectives fall from 0.317 to 0.277 ms an iteration and the
        # dense branch rises from 134.8 to 136.5, ms_per_iter 212.9 ->
        # 214.7: nothing to gain while the exchange is 0.15% of an
        # iteration, so it stays opt-in
        self.owner_minmax_fused = bool(owner_minmax_fused)
        self.use_mxu = resolve_use_mxu(use_mxu, program)
        self.reduce_method = resolve_reduce_method(reduce_method)

        # paged two-level gather (ops/pagegather.py): replaces the
        # per-edge state-table gather with a page-binned row fetch +
        # Pallas lane shuffle; an alternative row-delivery layout to
        # the pair plan, so the two never compose
        if gather not in ("flat", "paged", "pagemajor", "auto"):
            raise ValueError(f"unknown gather {gather!r}")
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
                # gather="auto" resolves by the scalemodel break-even
                # on the plan's MEASURED unique-page ratio / row fill
                self.page_plan = pagegather.engine_page_plan(
                    sg, gather, program, exchange)
                if self.page_plan is not None:
                    self.gather = self.page_plan.mode

        # pair-lane fast path (ops/pairs.py): gather cost is per ROW
        # fetched, so pair rows fetch a 128-wide source state row once
        # and deliver positionally.  Works for any num_parts, with or
        # without a mesh, and on weighted graphs (per-lane weights);
        # the layout below is built on the RESIDUAL graph
        self.pairs = None
        if pair_threshold is not None:
            if layout != "tiled":
                raise ValueError(
                    "pair_threshold requires the tiled layout")
            if batch is not None:
                raise ValueError(
                    "pair_threshold does not support query-batched "
                    "programs: pair delivery reads scalar vertex "
                    "state (ops/pairs.pair_partial); run batched "
                    "engines without pairs")
            if self.needs_dst and not self.dot:
                raise ValueError(
                    "pair_threshold supports programs whose "
                    "edge_value depends only on the source state, or "
                    "on <src, dst> via edge_value_from_dot")
            self.pairs, sg = pair_ops.plan_sharded_pairs(
                sg, pair_threshold, min_fill=pair_min_fill,
                kdim=self.kdim)         # pairs None if nothing dense
        self.sg = sg
        self.pair_stream = pair_ops.resolve_pair_stream(pair_stream,
                                                        self.pairs)
        # the SDDMM (K-dim) pair path streams by the shared 1 GB
        # budget (ops/tiled.STREAM_MSG_BYTES) instead of always: under
        # it the monolithic lax.map measured best; past it the stacked
        # per-row partials are the 67.7 GB NetFlix compile allocation
        rows = len(sg.part_ids())
        self.pair_dot_stream = pair_ops.resolve_pair_dot_stream(
            pair_stream, self.pairs, rows, self.kdim)
        # auto: stream once the [rows, C, E] f32 message temporary
        # passes the budget — vmap materializes EVERY materialized
        # part's messages together (mesh devices hold rows/ndev of
        # this, so the estimate is conservative there)
        self.stream_chunks = (rows * sg.epad * 4 > STREAM_MSG_BYTES
                              if stream_msgs is None
                              else bool(stream_msgs))

        # query-batched programs (no pair rows: the chunks hold EVERY
        # edge, and every iteration is dense) get the tiled layout's
        # lane-aligned placement (ops/tiled.py): the per-chunk reduce
        # of their [.., B] messages is then a fold over depth on all
        # but the hub tiles.  A program that reads its destination
        # through chunk_tile x W + rel_dst (dst_values) keeps the
        # vertex-ordered tiles, and so do chunks that hold no whole
        # depth row
        self.aligned = (batch is not None and tile_e % 128 == 0
                        and not (self.needs_dst or self.dot))
        dev = jnp.asarray if mesh is None else np.asarray
        with telemetry.span("build.dense_layout") as sp:
            arrays = self._dense_layout(dev, layout, tile_e,
                                        owner_tile_e)
            if self.tiles is not None:
                sp.count(**self.tiles.counts())
        if self.pairs is not None:
            arrays["pair_rowbind"] = dev(self.pairs.rowbind)
            arrays["pair_rel"] = dev(self.pairs.rel_dst)
            arrays["pair_tile_pos"] = dev(self.pairs.tile_pos)
            if self.pairs.weight is not None:
                arrays["pair_weight"] = dev(self.pairs.weight)
            if self.dot:
                # the SDDMM pair path also fetches each row's dst tile
                arrays["pair_row_tile"] = dev(self.pairs.row_tile)
                arrays["pair_tile0"] = dev(
                    (np.arange(sg.num_parts) *
                     (sg.vpad // 128)).astype(np.int32)[:, None])
        self.keys = tuple(arrays)
        self.dot_path = self.dot and (self.tiles is not None
                                      or self.page_plan is not None)
        # streamed and paged deliveries fuse gather + message + reduce
        self.fused = self.page_plan is not None or (
            self.stream_chunks and self.tiles is not None
            and not self.needs_dst)
        return self, arrays

    def _dense_layout(self, dev, layout, tile_e, owner_tile_e) -> dict:
        """Arrays of the edge layout (paged plan, owner chunks or
        tiled / flat edges), each through ``dev``; sets ``owner`` /
        ``tiles``."""
        sg = self.sg
        self.owner = self.tiles = None
        if self.page_plan is not None:
            # the paged plan IS the edge layout: neither the tiled
            # chunk arrays nor the owner chunk layout is built
            return dict(
                common_graph_arrays(sg, dev),
                **pagegather.plan_graph_arrays(
                    self.page_plan, dev,
                    owner=self.exchange == "owner", dot=self.dot,
                    num_parts=sg.num_parts, vpad=sg.vpad))
        if self.exchange == "owner":
            # per-source-part small-shard gathers + reduce_scatter
            # replace the state all_gather + big-table gather
            lay = self.owner = owner_ops.OwnerLayout.build(
                sg, E=owner_tile_e or 256)
            arrays = dict(**common_graph_arrays(sg, dev),
                          own_cs=dev(lay.chunk_start),
                          own_lc=dev(lay.last_chunk))
            if lay.packed:
                # uint32 src<<7|rel + uint16 live-lane counts (see
                # ops/owner.OwnerLayout's packed encoding note)
                arrays.update(own_sr=dev(lay.src_rel),
                              own_nv=dev(lay.n_valid))
            else:
                arrays.update(own_src=dev(lay.src_local),
                              own_rel=dev(lay.rel_dst))
            if lay.weight is not None:
                arrays["own_w"] = dev(lay.weight)
            if lay.streams():
                # fused streamed combine: never materializes [C, W]
                ep, et = lay.extract_plan()
                arrays["own_ep"] = dev(ep)
                arrays["own_et"] = dev(et)
            return arrays
        arrays, self.tiles = build_graph_arrays(
            sg, layout, self.needs_dst or self.dot, tile_e, dev,
            aligned=self.aligned)
        return arrays

    # -- the two-step form: messages, then reduce -----------------------

    def messages(self, flat_table, msg, g):
        """Per-edge source gather + message values, in the layout's
        edge order (flat ``[epad]`` or tiled ``[C, E]``, trailing
        state dims kept).  ONE gather of the state table (audit
        gather-budget)."""
        return msg(jnp.take(flat_table, g["src_slot"], axis=0),
                   g.get("weight"))

    def dst_values(self, table_p, g):
        """Each edge's DESTINATION state from this part's own rows
        ``table_p [vpad, ...]``, in the same edge order (programs with
        ``needs_dst``)."""
        vpad, lay = self.sg.vpad, self.tiles
        if lay is None:
            dst_idx = jnp.minimum(g["dst_local"], vpad - 1)
        else:
            # pad lanes carry rel -1 (int8 marker): clip keeps the
            # garbage gather in range; the reduce masks it anyway
            dst_idx = jnp.clip(
                g["chunk_tile"][:, None] * lay.W + g["rel_dst"],
                0, vpad - 1)
        return jnp.take(table_p, dst_idx, axis=0)

    def reduce(self, flat_table, msgs, msg, g):
        """Scatter-free segment reduction of materialized ``msgs`` to
        this part's ``[vpad, ...]`` (+ the pair-lane delivery, which
        fetches and reduces in one go)."""
        vpad, lay = self.sg.vpad, self.tiles
        if lay is None:
            red = segment_reduce(msgs, g["dst_local"], vpad + 1,
                                 self.kind)[:vpad]
        else:
            # the Pallas partial kernel takes scalar payloads only
            # (tiled_segment_reduce falls to XLA for the rest); the
            # chunk combine's kernel takes both
            red = tiled_segment_reduce(
                msgs, lay, g["chunk_start"], g["last_chunk"],
                g["rel_dst"], vpad, self.kind, use_mxu=self.use_mxu,
                tile_rank=g.get("tile_rank"),
                **method_args(self.reduce_method))
        return self._with_pairs(red, flat_table, msg, g)

    def _pair_rows(self, flat_table, msg, g):
        """Pair-lane delivery + reduce for one part -> [vpad] partial
        (identity where pairs contribute nothing)."""
        fn = (pair_ops.pair_partial_streamed if self.pair_stream
              else pair_ops.pair_partial)
        return fn(self.pairs, flat_table, g["pair_rowbind"],
                  g["pair_rel"], g.get("pair_weight"),
                  g["pair_tile_pos"], self.kind, msg,
                  reduce_method=self.reduce_method)[:self.sg.vpad]

    def _with_pairs(self, red, flat_table, msg, g):
        if self.pairs is not None:
            red = combine_op(self.kind)(
                red, self._pair_rows(flat_table, msg, g))
        return red

    # -- the fused form -------------------------------------------------

    def reduce_fused(self, flat_table, msg, g):
        """Gather + message + reduce in one delivery -> [vpad, ...]:
        the paged plan (page fetch + lane shuffle + compare-reduce,
        total coverage, no residual; ``pg_vrs`` binds the page-major
        plan's virtual reduce rows to their full-fill gather rows), or
        the tiled layout streamed in chunk blocks (ops/tiled.
        streamed_chunk_partials — the billion-edge form) with the pair
        contribution."""
        vpad = self.sg.vpad
        if self.page_plan is not None:
            return pagegather.paged_partial(
                self.page_plan, flat_table, g["pg_ids"], g["pg_sl"],
                g["pg_rel"], g.get("pg_w"), g["pg_tp"], self.kind,
                msg, reduce_method=self.reduce_method,
                vrow_src=g.get("pg_vrs"))[:vpad]
        lay = self.tiles
        partials = streamed_chunk_partials(
            flat_table, g["src_slot"], g["rel_dst"], g.get("weight"),
            lay, self.kind, msg, self.reduce_method,
            use_mxu=self.use_mxu)
        red = combine_partials(partials, lay, g["chunk_start"],
                               g["last_chunk"], vpad, self.kind,
                               use_mxu=self.use_mxu,
                               tile_rank=g.get("tile_rank"),
                               **method_args(self.reduce_method))
        return self._with_pairs(red, flat_table, msg, g)

    # -- the dot form (SDDMM) -------------------------------------------

    def reduce_dot(self, flat_table, msg_dot, g, table_p):
        """Reduction for programs whose dst dependence is only the
        inner product <src, dst> (``msg_dot(src_vals, dot, w)`` =
        program.edge_value_from_dot); ``table_p [vpad, K]`` is this
        part's own rows.  Paged engines run ops/pagegather.
        paged_partial_dot (pair_partial_dot's MXU pipeline plus the
        one-hot lane-shuffle contraction).

        Tiled: the dst row-gather (~9 ns/edge, 75% of a colfilter
        iteration) is replaced by MXU matmuls against the chunk's
        destination TILE: per chunk, D = src @ tile^T gives every
        (edge, dst-lane) dot; a lane-compare selects each edge's own
        dot; and the message reduction is a one-hot mask matmul — the
        SGD gradient as two batched matmuls (the TPU answer to the
        reference's shared-memory gradient staging,
        colfilter_gpu.cu:41-102).  Chunks are processed in lax.map
        blocks so the [B, E, W] intermediates stay small.
        """
        sg, lay = self.sg, self.tiles
        if self.page_plan is not None:
            return pagegather.paged_partial_dot(
                self.page_plan, flat_table, g["pg_ids"], g["pg_sl"],
                g["pg_rel"], g["pg_w"], g["pg_rt"], g["pg_tp"],
                g["pg_t0"][0], msg_dot)[:sg.vpad]
        with jax.named_scope("lux_dot_residual"):
            red = self._residual_dot(flat_table, msg_dot, g, table_p)
        if self.pairs is not None:
            fn = (pair_ops.pair_partial_dot_streamed
                  if self.pair_dot_stream
                  else pair_ops.pair_partial_dot)
            with jax.named_scope("lux_dot_pairs"):
                pred = fn(
                    self.pairs, flat_table, g["pair_rowbind"],
                    g["pair_rel"], g["pair_weight"],
                    g["pair_row_tile"], g["pair_tile_pos"],
                    g["pair_tile0"][0], msg_dot)
            red = red + pred[:sg.vpad]
        return red

    def _residual_dot(self, flat_table, msg_dot, g, table_p):
        """reduce_dot's chunked SDDMM over the tiled (residual)
        layout -> ``[vpad, K]``."""
        sg, lay = self.sg, self.tiles
        W, E = lay.W, lay.E
        C = lay.n_chunks
        Kdim = table_p.shape[-1]

        n_tiles = lay.n_tiles
        old_pad = jnp.pad(table_p,
                          ((0, n_tiles * W - sg.vpad), (0, 0)))
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
            s = jnp.take(flat_table, slot_b, axis=0)       # [B, E, K]
            s = jax.lax.optimization_barrier(s)
            t = jnp.take(tiles, jnp.minimum(ct_b, n_tiles - 1),
                         axis=0)                           # [B, W, K]
            D = jnp.einsum("bek,bwk->bew", s, t,
                           preferred_element_type=s.dtype,
                           precision=pair_ops.dot_precision(s.dtype))
            mask = r[..., None] == lanes                   # [B, E, W]
            dot = jnp.sum(jnp.where(mask, D, 0), axis=-1)  # [B, E]
            msgs = msg_dot(s, dot, w)                      # [B, E, K]
            return jnp.einsum(
                "bew,bek->bwk", mask.astype(s.dtype), msgs,
                precision=pair_ops.dot_precision(msgs.dtype))

        args = (pad_c(g["src_slot"]).reshape(nB, B, E),
                pad_c(g["chunk_tile"]).reshape(nB, B),
                pad_c(rel).reshape(nB, B, E),
                pad_c(wgt).reshape(nB, B, E))
        partials = jax.lax.map(block, args).reshape(Cp, W, Kdim)[:C]
        red = combine_chunks(partials, lay, g["chunk_start"],
                             g["last_chunk"], self.kind,
                             use_mxu=self.use_mxu,
                             **method_args(self.reduce_method))
        return red.reshape(n_tiles * W, Kdim)[:sg.vpad]

    # -- the owner form (ops/owner.py) ------------------------------------

    def msg_dtype(self, msg, state_rows):
        """Message dtype without running ``msg`` (abstract eval)."""
        probe_w = (jax.ShapeDtypeStruct((1, 1), jnp.float32)
                   if self.sg.weighted else None)
        probe_s = jax.ShapeDtypeStruct(
            (1, 1) + tuple(state_rows.shape[2:]), state_rows.dtype)
        return jax.eval_shape(msg, probe_s, probe_w).dtype

    def owner_generate(self, state_rows, msg, g):
        """Owner-side generation + routing for the locally-held rows
        (single device: all parts; under shard_map: this device's) ->
        ``[rows, vpad, ...]`` reduced at the destination parts: each
        LOCAL source part gathers from its own state shard under a
        lax.scan (ops/owner.owner_contribs; paged engines run the
        page-binned shard delivery under the same scan) and the
        contributions are reduce-scattered (ops/owner.owner_exchange)
        — no state all_gather.  Page-major plans route full message
        rows by all_to_all and reduce receiver-side instead
        (ops/pagegather.pagemajor_owner_deliver): no per-tile
        partials, no separate exchange."""
        sg, pp = self.sg, self.page_plan
        dtype = self.msg_dtype(msg, state_rows)
        axis = None if self.mesh is None else PARTS_AXIS
        if pp is not None and pp.mode == "pagemajor":
            return pagegather.pagemajor_owner_deliver(
                pp, state_rows, g, self.kind, msg, dtype,
                sg.num_parts, self.reduce_method,
                axis=axis)[:, :sg.vpad]
        if pp is not None:
            acc = pagegather.paged_owner_contribs(
                pp, state_rows, g, self.kind, msg, dtype,
                sg.num_parts, self.reduce_method)
        else:
            acc = owner_ops.owner_contribs(
                self.owner, state_rows, g, self.kind, msg, dtype,
                sg.num_parts, self.reduce_method,
                use_mxu=self.use_mxu)
        return owner_ops.owner_exchange(
            acc, self.kind, axis=axis,
            ndev=1 if self.mesh is None else self.mesh.devices.size,
            minmax_fused=self.owner_minmax_fused)[:, :sg.vpad]

    def owner_pairs(self, red_rows, state_rows, msg, g):
        """``red_rows`` combined with the pair rows' contribution.
        Pair rows are fetched from the FULL table (row-granular
        fetches, not subject to the element-gather big-table tax); on
        the mesh the all_gather exists only for them."""
        if self.pairs is None:
            return red_rows
        sg = self.sg
        full = (state_rows if self.mesh is None else
                jax.lax.all_gather(state_rows, PARTS_AXIS, tiled=True))
        flat = full.reshape((sg.num_parts * sg.vpad,) + full.shape[2:])
        pred = jax.vmap(lambda gp: self._pair_rows(flat, msg, gp))(
            {k: g[k] for k in g if k.startswith("pair_")})
        return combine_op(self.kind)(red_rows, pred)
