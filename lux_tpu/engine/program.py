"""Vertex-program abstraction.

The reference specializes its two compute templates per app at compile
time through app.h typedefs + extern task hooks (reference
core/graph.h:146-225).  Here a vertex program is a small bundle of pure
functions over arrays; engines trace them under jit, so specialization
happens at XLA-compile time — the same "zero-cost per-app dispatch"
property, without separate binaries.

All functions see *padded part-local* arrays (see graph.ShardedGraph):
state ``[vpad, ...]``, per-edge values ``[epad, ...]``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass(frozen=True)
class PartCtx:
    """Per-partition context handed to program callbacks.

    deg    int32 [vpad]   out-degrees (the reference's VERTEX_DEGREE)
    vmask  bool  [vpad]   True for real (non-padding) vertex slots
    nv     int            global vertex count (static)
    ne     int            global edge count (static)
    extra  dict | None    this part's rows of the program's
                          ``extra_arrays`` (query-batch arrays like
                          personalized-PageRank reset vectors) —
                          device arrays [vpad, ...], threaded as jit
                          ARGUMENTS by the engine, never closed over
    """
    deg: Any
    vmask: Any
    nv: int
    ne: int
    extra: Any = None


def vmask_of(g, vpad: int):
    """Valid-vertex mask derived from the per-part counts ``nvp``
    graph array ([1] per part under vmap -> [vpad]; [rows, 1] stacked
    -> [rows, vpad]) — shipped as one int32 per part instead of a
    [rows, vpad] bool array (68 MB of the RMAT26 single-chip fit)."""
    import jax.numpy as jnp
    return jnp.arange(vpad, dtype=jnp.int32) < g["nvp"]


@dataclasses.dataclass(frozen=True)
class PullProgram:
    """Dense gather-apply program (the reference's pull model,
    core/pull_model.inl).

    reduce      'sum' | 'min' | 'max' — how edge messages combine per
                destination (replaces atomicAdd/Min/Max).
    edge_value  (src_val [epad,...], dst_val [epad,...], weight
                [epad]|None) -> msg [epad,...]; traced per edge batch.
    apply       (old [vpad,...], reduced [vpad,...], ctx: PartCtx) ->
                new [vpad,...]; the per-vertex epilogue (the reference's
                post-scan code, e.g. pagerank_gpu.cu:97-100).
    init        (sharded_graph) -> initial padded state
                [num_parts, vpad, ...] (numpy).
    init_device optional (ctx: PartCtx) -> this part's rows [vpad,
                ...] of the initial state, pad rows included: a pure
                ``jnp`` function of the same PartCtx ``apply``
                receives.  When set, ``PullEngine.init_state`` makes
                each solve's first state on the devices from arrays
                they already hold, with no host array and no
                transfer.  ``init`` stays (shapes, checkpoints and the
                audit read it) and the two are ONE formula: equal
                bitwise on a CPU backend, within one unit in the last
                place where the device's divide is not IEEE-exact.
    needs_dst   whether edge_value reads dst_val (skips a gather when
                False).
    edge_value_from_dot
                optional (src_val [*,K], dot [*], weight [*]) -> msg;
                for programs whose dst dependence is ONLY through the
                inner product <src, dst> (e.g. colfilter's rating
                error).  When set and the layout is tiled, the engine
                computes the dot on the MXU from the destination TILE
                (dst values are tile-positional, so the ~9 ns/edge dst
                row-gather disappears; see engine/delivery.py reduce_dot).
    state_bytes bytes per VERTEX of the iterated state (itemsize x
                trailing dims), e.g. 80 for colfilter's [vpad, 20]
                f32.  Feeds resolve_exchange's state-table size
                estimate (the big-table gather cliff is in BYTES);
                None -> assume 4 (scalar f32).
    name        optional app label; engines scope their traced step
                in ``jax.named_scope(f"lux_{name}")`` so profiler
                captures (profiling.trace) attribute device ops to
                the app instead of anonymous XLA fusions.
    extra_arrays
                optional (sharded_graph) -> {name: [num_parts, vpad,
                ...] numpy} per-part constants the apply epilogue
                needs beyond deg/vmask (e.g. personalized PageRank's
                per-query reset vectors, the query-batch analogue of
                graph arrays).  The engine ships them as jit
                ARGUMENTS (key ``prog_<name>`` in its graph-array
                dict — the no-closure convention holds at any size)
                and exposes each part's row via ``ctx.extra[name]``;
                ``PullEngine.update_program_arrays`` swaps them
                in-place (same shapes, no recompile) — the serving
                front-end's continuous-batching refill path.
    batch       query-batch width B when the state carries a trailing
                query axis ``[vpad, B]`` (None = single-query).  One
                state-table gather then serves all B queries
                (machine-checked: lux_tpu/audit.py gather-budget).
    """
    reduce: str
    edge_value: Callable
    apply: Callable
    init: Callable
    needs_dst: bool = False
    edge_value_from_dot: Callable | None = None
    state_bytes: int | None = None
    name: str | None = None
    extra_arrays: Callable | None = None
    batch: int | None = None
    init_device: Callable | None = None
