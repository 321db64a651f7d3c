"""Single-source shortest paths (push model, convergence-driven).

Two modes:

- ``hops`` (default): unweighted hop-count distances, candidate =
  dist[src] + 1.  This matches the reference exactly — its "SSSP" never
  loads edge weights and computes BFS levels (reference
  sssp_gpu.cu:122,208,225; weights unread in PushLoadTask,
  push_model.inl:60-75; SURVEY.md §7 quirks).
- ``weighted``: true shortest paths with edge weights, candidate
  = dist[src] + w — the superset BASELINE.md's config list asks for.
  The distances take their type from the weights (``distance_dtype``):
  float weights give float32 distances (``+inf`` unreached), INTEGER
  weights give int32 distances, summed in int32 and exact (the GAP
  benchmark's SSSP contract), with ``HOP_INF`` for unreached.

Distances of unreachable vertices stay at INF (the reference seeds
dist = nv as its infinity, sssp_gpu.cu:733-744; we use a large sentinel
and expose ``unreachable`` masks instead of leaking graph-size-dependent
magic values).

What the int32 distances hold.  The edge layouts keep every weight as
float32 (graph.py, ops/pairs.py, ops/owner.py: one storage type for
every program), which holds every integer up to 2^24 exactly, and the
integer relax reads a weight back with an exact cast: the largest
weight is ``INT_WEIGHT_MAX`` = 2^24 = 16,777,216 (``build_engine``
refuses a graph with a larger or a negative one: ``WeightRangeError``).
The largest distance is ``INT_DIST_MAX`` = ``HOP_INF`` - 2 =
1,073,741,821.  A relax adds in int32 and cannot wrap (``HOP_INF`` +
2^24 < 2^31); a sum past ``INT_DIST_MAX`` becomes ``INT_DIST_OVER`` =
``HOP_INF`` - 1, which stays what it is under every further relax, so
an answer that holds it says so: ``ensure_in_range`` (called by ``run``
and the CLI) raises ``DistanceRangeError`` instead of handing out a
wrong distance.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from lux_tpu.engine.delivery import sharding_demands
from lux_tpu.engine.push import PushEngine, PushProgram
from lux_tpu.graph import Graph, ShardedGraph

HOP_INF = np.int32(np.iinfo(np.int32).max // 2)   # +1 cannot overflow
DIST_INF = np.float32(np.inf)
# int32 distances of integer weights (module docstring)
INT_WEIGHT_MAX = 1 << 24
INT_DIST_OVER = np.int32(HOP_INF - 1)
INT_DIST_MAX = int(HOP_INF) - 2


class WeightRangeError(ValueError):
    """An integer weight the int32 relax cannot take exactly: negative,
    or past ``INT_WEIGHT_MAX``."""


class DistanceRangeError(ValueError):
    """An int32 answer in which a distance passed ``INT_DIST_MAX``."""


def distance_dtype(weights):
    """The distance type of a weighted search on ``weights``: int32
    for integer weights, float32 for every other.  Integer weights
    are held to the range the int32 relax is exact on
    (``WeightRangeError``)."""
    weights = np.asarray(weights)
    if not np.issubdtype(weights.dtype, np.integer):
        return np.dtype(np.float32)
    low = int(weights.min(initial=0))
    top = int(weights.max(initial=0))
    if low < 0 or top > INT_WEIGHT_MAX:
        raise WeightRangeError(
            f"integer weights in [{low}, {top}]: int32 distances take "
            f"weights in [0, {INT_WEIGHT_MAX}] (2^24, what the float32 "
            f"edge layouts hold exactly); load them as float32 for "
            f"float32 distances")
    return np.dtype(np.int32)


def ensure_in_range(dist):
    """``dist`` as it is, unless it is an int32 answer in which a
    distance passed ``INT_DIST_MAX`` (``DistanceRangeError``).  Hop
    counts never hold the marker: a path has under ``HOP_INF`` - 1
    edges."""
    if dist.dtype == np.int32:
        over = int(np.count_nonzero(dist == INT_DIST_OVER))
        if over:
            raise DistanceRangeError(
                f"{over} int32 distances passed {INT_DIST_MAX}; load "
                f"the weights as float32 for float32 distances")
    return dist


def _relax_of(weighted: bool, dtype, batched: bool = False):
    """(relax, identity) of the distance type ``dtype``."""
    if not weighted:
        return (lambda src_label, w: src_label + np.int32(1)), HOP_INF
    if dtype == np.int32:
        def relax(src_label, w):
            # the layouts' float32 holds the integer exactly; the sum
            # is int32 and cannot wrap (HOP_INF + 2^24 < 2^31); past
            # INT_DIST_MAX it becomes the marker, and stays it
            w = w.astype(jnp.int32)
            return jnp.minimum(src_label + (w[..., None] if batched
                                            else w), INT_DIST_OVER)
        return relax, HOP_INF
    if batched:
        # weight [.., E] broadcasts over the trailing query axis
        return (lambda src_label, w: src_label + w[..., None]), DIST_INF
    return (lambda src_label, w: src_label + w), DIST_INF


def make_program(start_vertex: int, weighted: bool = False,
                 dtype=np.float32) -> PushProgram:
    """``dtype``: the weighted program's distance type
    (``distance_dtype`` of the graph's weights: ``build_engine``
    passes it); hop counts are int32 whatever it says."""
    dtype = np.dtype(dtype if weighted else np.int32)
    relax, identity = _relax_of(weighted, dtype)

    def init(sg: ShardedGraph):
        if not 0 <= start_vertex < sg.nv:
            raise ValueError(
                f"start vertex {start_vertex} out of range [0, {sg.nv})")
        dist = np.full(sg.nv, identity, dtype=dtype)
        dist[start_vertex] = 0
        active = np.zeros(sg.nv, dtype=bool)
        active[start_vertex] = True
        return sg.to_padded(dist), sg.to_padded(active)

    return PushProgram(reduce="min", relax=relax, identity=identity,
                       init=init, name="sssp")


def make_batched_program(sources, weighted: bool = False,
                         dtype=np.float32) -> PushProgram:
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
    dtype = np.dtype(dtype if weighted else np.int32)
    relax, identity = _relax_of(weighted, dtype, batched=True)

    def init(sg: ShardedGraph):
        for s in sources:
            if not 0 <= s < sg.nv:
                raise ValueError(
                    f"source vertex {s} out of range [0, {sg.nv})")
        dist = np.full((sg.nv, B), identity, dtype=dtype)
        active = np.zeros((sg.nv, B), dtype=bool)
        for q, s in enumerate(sources):
            dist[s, q] = 0
            active[s, q] = True
        return sg.to_padded(dist), sg.to_padded(active)

    return PushProgram(reduce="min", relax=relax, identity=identity,
                       init=init, name="ksssp", batch=B)


def default_delta(g: Graph):
    """Bucket width of ``delta="auto"``: the LARGEST edge weight (1
    where no weight is positive); a Python int on integer weights, so
    it is a whole width > 0 on int32 distances too.

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
    (11-15 and 3): the same order.
    A HIGH-DIAMETER graph is the other way round (the GAP suite's
    road network at ``USA-road-d.CAL``'s counts, 1.89 M vertices,
    degree 2.5, int32 lengths of mean 8,862 and a heavy tail, 4 roots,
    TPU v5e, PERF.md section 6, PR 47): plain frontiers re-relax the
    graph over fronts of a tenth of the vertices, 810 of their 2,038
    trips a search dense, 417.2 s for the 4 searches; this rule's
    808,900 (91 means) 135.7 s (4,568 trips of 2,700 vertices, each
    on the ladder's low rungs); GAP's own width scaled to these
    weights, 192,652 (22 means), 173.3 s (6,769 trips); ten times that
    255.7 s, a tenth of it 338.2 s (13,988 trips).  There the bucket
    bound is what keeps a trip small, but a trip on the lowest rungs
    costs 6 ms at 1.89 M padded vertices whatever it holds (3.6 ms at
    1.07 M: ``USA-road-d.FLA``'s counts, where GAP's width was 2.6%
    ahead of this rule's), so more and emptier trips lose: the largest
    weight is the best width measured there."""
    top = np.max(g.weights, initial=0).item()
    return top if top > 0 else type(top)(1)


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
    dtype = distance_dtype(g.weights) if weighted else None
    if sources is not None:
        if delta is not None:
            raise ValueError("delta-stepping is single-query; "
                             "sources=[...] requires delta=None")
        program = make_batched_program(sources, weighted, dtype)
    else:
        if start_vertex is None:
            raise ValueError("single-query SSSP needs start_vertex "
                             "(or pass sources=[...] for a batch)")
        if delta == "auto":
            delta = default_delta(g) if weighted else 1.0
        program = make_program(start_vertex, weighted, dtype)
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
    dist, iters = eng.run(max_iters=max_iters, verbose=verbose)
    return ensure_in_range(dist), iters


def unreachable(dist: np.ndarray) -> np.ndarray:
    if dist.dtype == np.int32:
        return dist >= HOP_INF
    return ~np.isfinite(dist)


def _oracle_weights(g: Graph, weighted: bool):
    """(weights, infinity) the oracles compute in, by the rule of
    ``distance_dtype``: hops and integer weights in int64 under
    ``HOP_INF``, every other weight in float64 under ``inf``."""
    if weighted and distance_dtype(g.weights) != np.int32:
        return np.asarray(g.weights, dtype=np.float64), np.inf
    w = g.weights if weighted else np.ones(g.ne, dtype=np.int64)
    return np.asarray(w, dtype=np.int64), np.int64(int(HOP_INF))


def reference_sssp(g: Graph, start_vertex: int = 0,
                   weighted: bool = False) -> np.ndarray:
    """NumPy Bellman-Ford oracle (exact fixed point): float64
    distances (``inf`` unreached) of float weights, int64 ones
    (``HOP_INF`` unreached) of hops and of integer weights."""
    src, dst = g.edge_arrays()
    w, inf = _oracle_weights(g, weighted)
    dist = np.full(g.nv, inf, dtype=w.dtype)
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
        w, _inf = _oracle_weights(g_new, True)
        nw = np.asarray(new_w, w.dtype)
    else:
        w = np.ones(g_new.ne, dtype=np.int64)
        nw = np.ones(len(new_src), dtype=np.int64)
    dist = np.asarray(dist_old, dtype=w.dtype).copy()
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
    w, inf = _oracle_weights(g_new, weighted)
    dist = np.asarray(dist_old, dtype=w.dtype).copy()
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
    w, inf = _oracle_weights(g, weighted)
    w = w[:, None]
    dist = np.full((g.nv, B), inf, dtype=w.dtype)
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
