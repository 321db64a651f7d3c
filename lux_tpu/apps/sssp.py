"""Single-source shortest paths (push model, convergence-driven).

Two modes:

- ``hops`` (default): unweighted hop-count distances, candidate =
  dist[src] + 1.  This matches the reference exactly — its "SSSP" never
  loads edge weights and computes BFS levels (reference
  sssp_gpu.cu:122,208,225; weights unread in PushLoadTask,
  push_model.inl:60-75; SURVEY.md §7 quirks).
- ``weighted``: true shortest paths with float edge weights, candidate
  = dist[src] + w — the superset BASELINE.md's config list asks for.

Distances of unreachable vertices stay at INF (the reference seeds
dist = nv as its infinity, sssp_gpu.cu:733-744; we use a large sentinel
and expose ``unreachable`` masks instead of leaking graph-size-dependent
magic values).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from lux_tpu.engine.delivery import sharding_demands
from lux_tpu.engine.push import PushEngine, PushProgram
from lux_tpu.graph import Graph, ShardedGraph

HOP_INF = np.int32(np.iinfo(np.int32).max // 2)   # +1 cannot overflow
DIST_INF = np.float32(np.inf)


def make_program(start_vertex: int, weighted: bool = False) -> PushProgram:
    if weighted:
        def relax(src_label, w):
            return src_label + w
        identity = np.float32(np.inf)
        dtype = np.float32
        inf = DIST_INF
    else:
        def relax(src_label, w):
            return src_label + np.int32(1)
        identity = HOP_INF
        dtype = np.int32
        inf = HOP_INF

    def init(sg: ShardedGraph):
        if not 0 <= start_vertex < sg.nv:
            raise ValueError(
                f"start vertex {start_vertex} out of range [0, {sg.nv})")
        dist = np.full(sg.nv, inf, dtype=dtype)
        dist[start_vertex] = 0
        active = np.zeros(sg.nv, dtype=bool)
        active[start_vertex] = True
        return sg.to_padded(dist), sg.to_padded(active)

    return PushProgram(reduce="min", relax=relax, identity=identity,
                       init=init, name="sssp")


def make_batched_program(sources, weighted: bool = False) -> PushProgram:
    """k-source SSSP: labels carry a query-batch axis ``[vpad, B]``
    with column q the independent single-source run from
    ``sources[q]`` (ROADMAP item 2: ONE label gather per dense
    iteration serves all B queries; columns retire independently
    through their per-query active masks).  Bitwise contract:
    tests/test_batched.py proves each column equals the single-source
    engine's run — min fixed points are unique, so the dense batched
    schedule and the single-query sparse/dense schedule agree
    exactly."""
    sources = [int(s) for s in sources]
    if not sources:
        raise ValueError("sources must name at least one query")
    B = len(sources)
    if weighted:
        def relax(src_label, w):
            # weight [.., E] broadcasts over the trailing query axis
            return src_label + w[..., None]
        identity = np.float32(np.inf)
        dtype = np.float32
        inf = DIST_INF
    else:
        def relax(src_label, w):
            return src_label + np.int32(1)
        identity = HOP_INF
        dtype = np.int32
        inf = HOP_INF

    def init(sg: ShardedGraph):
        for s in sources:
            if not 0 <= s < sg.nv:
                raise ValueError(
                    f"source vertex {s} out of range [0, {sg.nv})")
        dist = np.full((sg.nv, B), inf, dtype=dtype)
        active = np.zeros((sg.nv, B), dtype=bool)
        for q, s in enumerate(sources):
            dist[s, q] = 0
            active[s, q] = True
        return sg.to_padded(dist), sg.to_padded(active)

    return PushProgram(reduce="min", relax=relax, identity=identity,
                       init=init, name="ksssp", batch=B)


def default_delta(g: Graph) -> float:
    """Bucket width of ``delta="auto"``: the LARGEST edge weight (1.0
    where no weight is positive).

    Every iteration of the push engine costs by its static shape, not
    by its front (a dense one all edges, a sparse one its ladder
    rungs), so a narrow bucket saves no time by relaxing fewer edges;
    it only adds loop trips.  MEASURED on float32 weights uniform in
    [0, 1) only (Graph500 kernel 3 on the Kronecker graph of scale 21
    x 16, 8 roots, TPU v5e; ``scripts/sweep_delta.py``, PERF.md
    section 6, PR 43), seconds for the 8 searches with the loop's
    trips (relax iterations + relax-free advances) a search beside
    them: width 0.01 157.6 s (455 trips), 0.031 (the rule this
    replaces, ``max(min positive weight, mean / 16)``) 112.8 s (187),
    0.1 64.9 s (79), 0.25 57.4 s (48), 0.5 54.8 s (35), 1.0 57.3 s
    (33), 2.0 51.1 s (27), plain frontiers 50.8 s (25): seconds
    follow trips all the way.  The maximum is the widest width a
    graph's own weights name: every edge out of a bucket lands in
    that bucket or the next.  On that graph the schedule then all
    but degenerates (2.1 advances a search; twice the width is plain
    frontiers with one advance), and it is NOT the fastest choice:
    plain frontiers are 12.6% faster on the 8 searches.  One root of
    the 8 takes 48 relax iterations (11.9 s) at this width and 31 at
    0.5 (a host replay): its only edge weighs 0.99, so the bound
    ``0 + delta`` cuts every front to a few hubs just behind it,
    under the vertex count that sends a front down the sparse branch,
    with five times the edge budget in out-edges (PERF.md section 6).
    INTEGER weights 1..5 (``bench.py``'s ``sssp-delta`` shape, RMAT21 x
    16 directed, 8 roots, one chip run, PR 43): plain frontiers 7.59 s
    for the 8 (8-9 relax iterations a search), 1.0 (the old rule
    there) 19.26 s (19-28 and 17 advances), this rule's 5.0 8.74 s
    (11-15 and 3): the same order.  No other weight distribution and
    no high-diameter graph has been measured."""
    top = float(np.max(g.weights, initial=0))
    return top if top > 0 else 1.0


def build_engine(g: Graph, start_vertex: int | None = 0,
                 num_parts: int = 1,
                 mesh=None, weighted: bool = False,
                 delta: float | str | None = None,
                 sg: ShardedGraph | None = None,
                 pair_threshold: int | None = None,
                 pair_min_fill: int | None = None,
                 starts=None, exchange: str = "auto",
                 gather: str = "flat",
                 enable_sparse: bool = True,
                 owner_tile_e: int | None = None,
                 owner_minmax_fused: bool = False,
                 use_mxu: bool | str = "auto",
                 health: bool = False,
                 sources=None,
                 audit: str | None = None) -> PushEngine:
    """delta: bucket width for delta-stepping priority ordering
    (weighted runs); "auto" picks a heuristic; None disables (plain
    Bellman-Ford frontier relaxation).  pair_threshold enables pair-
    lane delivery on dense iterations (best after graph.pair_relabel,
    whose ``starts`` should be passed through here).
    enable_sparse=False drops the src-sorted frontier view — the
    big-scale fit lever (it re-doubles edge memory,
    ShardedGraph.memory_report(push_sparse=True)); every iteration
    then runs dense.

    sources=[a, b, c, ...] builds the QUERY-BATCHED k-source engine
    instead (labels [vpad, B], one gather serving every query —
    ``make_batched_program``); start_vertex is then ignored, and
    delta/pair_threshold must be off (single-query machinery)."""
    if weighted and g.weights is None:
        raise ValueError("weighted SSSP needs a weighted graph")
    if sources is not None:
        if delta is not None:
            raise ValueError("delta-stepping is single-query; "
                             "sources=[...] requires delta=None")
        program = make_batched_program(sources, weighted)
    else:
        if start_vertex is None:
            raise ValueError("single-query SSSP needs start_vertex "
                             "(or pass sources=[...] for a batch)")
        if delta == "auto":
            delta = default_delta(g) if weighted else 1.0
        program = make_program(start_vertex, weighted)
    if sg is None:
        vpad_align, _ = sharding_demands(gather)
        sg = ShardedGraph.build(
            g, num_parts, starts=starts,
            pair_threshold=pair_threshold, vpad_align=vpad_align)
    return PushEngine(sg, program, mesh=mesh,
                      delta=delta, pair_threshold=pair_threshold,
                      pair_min_fill=pair_min_fill,
                      exchange=exchange, gather=gather,
                      enable_sparse=enable_sparse,
                      owner_tile_e=owner_tile_e,
                      owner_minmax_fused=owner_minmax_fused,
                      use_mxu=use_mxu, health=health, audit=audit)


def run(g: Graph, start_vertex: int = 0, num_parts: int = 1, mesh=None,
        weighted: bool = False, delta=None, max_iters=None,
        verbose: bool = False):
    """Returns (dist [nv], iterations)."""
    eng = build_engine(g, start_vertex, num_parts, mesh, weighted,
                       delta=delta)
    return eng.run(max_iters=max_iters, verbose=verbose)


def unreachable(dist: np.ndarray) -> np.ndarray:
    if dist.dtype == np.int32:
        return dist >= HOP_INF
    return ~np.isfinite(dist)


def reference_sssp(g: Graph, start_vertex: int = 0,
                   weighted: bool = False) -> np.ndarray:
    """NumPy Bellman-Ford oracle (exact fixed point)."""
    src, dst = g.edge_arrays()
    if weighted:
        w = np.asarray(g.weights, dtype=np.float64)
        dist = np.full(g.nv, np.inf)
    else:
        w = np.ones(g.ne, dtype=np.int64)
        dist = np.full(g.nv, int(HOP_INF), dtype=np.int64)
    dist[start_vertex] = 0
    while True:
        cand = dist[src] + w
        new = dist.copy()
        np.minimum.at(new, dst, cand)
        if np.array_equal(new, dist):
            break
        dist = new
    return dist


def reference_sssp_incremental(g_new: Graph, dist_old: np.ndarray,
                               new_src, new_dst, new_w=None,
                               weighted: bool = False) -> np.ndarray:
    """NumPy INCREMENTAL oracle (round 20, live graphs): revalidate a
    converged distance vector after edge appends by re-relaxing ONLY
    vertices reachable from the touched endpoints — the worklist
    analogue of the frontier-seeded device revalidation
    (lux_tpu/livegraph.LiveGraph.revalidate).

    ``g_new`` is the AUGMENTED graph (base plus the new edges —
    ``Graph.with_edges``), ``dist_old`` the fixed point on the base
    graph, (new_src, new_dst[, new_w]) the appended edges.  Edge
    appends only ever LOWER min-fixed-point distances, so seeding
    from the old fixed point and propagating improvements from the
    new edges' destinations converges to exactly
    ``reference_sssp(g_new, ...)`` — the equality
    tests/test_livegraph.py proves on every sweep point.  Returns the
    new distance vector in dist_old's dtype discipline (int64 hops /
    float64 weighted, matching reference_sssp)."""
    src, dst = g_new.edge_arrays()
    if weighted:
        if new_w is None:
            # same contract as Graph.with_edges: a silently
            # one-weighted append seeds below the true fixed point,
            # and monotone propagation can never repair it
            raise ValueError("weighted incremental oracle needs "
                             "new_w for every appended edge")
        w = np.asarray(g_new.weights, dtype=np.float64)
        dist = np.asarray(dist_old, dtype=np.float64).copy()
        nw = np.asarray(new_w, np.float64)
    else:
        w = np.ones(g_new.ne, dtype=np.int64)
        dist = np.asarray(dist_old, dtype=np.int64).copy()
        nw = np.ones(len(new_src), dtype=np.int64)
    # seed: relax the appended edges against the old fixed point
    frontier = np.zeros(g_new.nv, dtype=bool)
    cand = dist[np.asarray(new_src, np.int64)] + nw
    for d, c in zip(np.asarray(new_dst, np.int64), cand):
        if c < dist[d]:
            dist[d] = c
            frontier[d] = True
    # propagate: only out-edges of improved vertices relax — the
    # touched-reachable region, not the whole graph
    while frontier.any():
        on = frontier[src]
        cand = dist[src[on]] + w[on]
        new = dist.copy()
        np.minimum.at(new, dst[on], cand)
        frontier = new < dist
        dist = new
    return dist


def reference_sssp_decremental(g_new: Graph, dist_old: np.ndarray,
                               touched_dst, start_vertex: int = 0,
                               weighted: bool = False) -> np.ndarray:
    """NumPy DECREMENTAL oracle (round 21, mutation algebra): repair a
    converged distance vector after ANTI-MONOTONE mutations — edge
    deletions and weight updates — by the affected-cone re-seed rule
    the device path mirrors (lux_tpu/livegraph.LiveGraph.revalidate).

    ``g_new`` is the post-mutation graph, ``dist_old`` the fixed point
    on the pre-mutation graph, ``touched_dst`` the destinations of
    every deleted/reweighted edge.  Deletions and weight increases can
    RAISE min-fixed-point distances, which monotone relaxation can
    never repair; but any vertex whose distance changes is reachable
    in ``g_new`` from some touched destination (take the LAST mutated
    edge (u, v) on its stale shortest path: the suffix from v survives
    in ``g_new``).  So: (1) the affected CONE = forward reachability
    from the touched destinations over ``g_new``, (2) re-seed the cone
    from identity (keeping the source seed), (3) relax to fixed point
    — every label starts >= the true fixed point with the source at 0,
    so Bellman-Ford converges to exactly ``reference_sssp(g_new)``
    (the equality tests/test_livegraph.py proves per sweep point;
    weight DECREASES are covered too — the improved paths route
    through a touched destination, hence through the cone)."""
    src, dst = g_new.edge_arrays()
    if weighted:
        w = np.asarray(g_new.weights, dtype=np.float64)
        dist = np.asarray(dist_old, dtype=np.float64).copy()
        inf = np.inf
    else:
        w = np.ones(g_new.ne, dtype=np.int64)
        dist = np.asarray(dist_old, dtype=np.int64).copy()
        inf = np.int64(int(HOP_INF))
    cone = np.zeros(g_new.nv, dtype=bool)
    cone[np.asarray(touched_dst, np.int64)] = True
    while True:
        add = np.zeros(g_new.nv, dtype=bool)
        add[dst[cone[src]]] = True
        add &= ~cone
        if not add.any():
            break
        cone |= add
    dist[cone] = inf
    dist[start_vertex] = 0
    while True:
        cand = dist[src] + w
        new = dist.copy()
        np.minimum.at(new, dst, cand)
        if np.array_equal(new, dist):
            break
        dist = new
    return dist


def reference_sssp_batched(g: Graph, sources,
                           weighted: bool = False) -> np.ndarray:
    """NumPy k-source Bellman-Ford oracle -> ``[nv, B]`` distances.

    Column q is BITWISE-equal to ``reference_sssp(g, sources[q])``:
    the vectorized relaxation applies the identical per-column
    ``np.minimum.at`` updates in the identical edge order, and min
    fixed points are unique (tests/test_batched.py asserts the
    column-equality explicitly — the batched-oracle contract of
    ROADMAP item 2)."""
    src, dst = g.edge_arrays()
    B = len(sources)
    if weighted:
        w = np.asarray(g.weights, dtype=np.float64)[:, None]
        dist = np.full((g.nv, B), np.inf)
    else:
        w = np.ones((g.ne, 1), dtype=np.int64)
        dist = np.full((g.nv, B), int(HOP_INF), dtype=np.int64)
    for q, s in enumerate(sources):
        dist[int(s), q] = 0
    while True:
        cand = dist[src] + w
        new = dist.copy()
        np.minimum.at(new, dst, cand)
        if np.array_equal(new, dist):
            break
        dist = new
    return dist
