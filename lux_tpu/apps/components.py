"""Connected components via max-label propagation (push model).

Matches the reference's algorithm (reference components_gpu.cu:57-59,
733-739): every vertex starts active with label = its own id; each
iteration a destination takes the max label over its in-neighbors;
convergence when no label changes.  On a symmetrized (undirected)
graph every component converges to the max vertex id in the component
(weakly connected components).  On a DIRECTED graph, loaded as it is
(what ``lux_tpu/cli.py components`` and upstream do; only ``bench.py``
symmetrizes), a label travels along the arc's direction only, and the
fixed point is, for every vertex, the largest id among its ANCESTORS:
the vertices that reach it, itself included.  The check audits the
fixed point either way: labels[dst] >= labels[src] for every edge
(components_gpu.cu:788).
"""

from __future__ import annotations

import numpy as np

from lux_tpu.engine.delivery import sharding_demands
from lux_tpu.engine.push import PushEngine, PushProgram
from lux_tpu.graph import Graph, ShardedGraph


def make_program() -> PushProgram:
    def relax(src_label, w):
        return src_label

    def init(sg: ShardedGraph):
        labels = np.arange(sg.nv, dtype=np.int32)
        active = np.ones(sg.nv, dtype=bool)
        return sg.to_padded(labels), sg.to_padded(active)

    return PushProgram(reduce="max", relax=relax,
                       identity=np.int32(-1), init=init,
                       name="components")


def make_batched_program(seeds) -> PushProgram:
    """Batched SEEDED components: labels ``[vpad, B]`` with column q
    the propagation from the single seed ``seeds[q]`` — label[v, q]
    converges to ``seeds[q]`` where v is reachable from the seed and
    stays -1 elsewhere (on a symmetrized graph: the membership
    labeling of the seed's component).  One label gather per dense
    iteration serves every query (ROADMAP item 2); columns retire
    independently through their active masks.  Max fixed points are
    unique, so each column is bitwise-equal to the single-seed run
    (tests/test_batched.py)."""
    seeds = [int(s) for s in seeds]
    if not seeds:
        raise ValueError("seeds must name at least one query")
    B = len(seeds)

    def relax(src_label, w):
        return src_label

    def init(sg: ShardedGraph):
        for s in seeds:
            if not 0 <= s < sg.nv:
                raise ValueError(
                    f"seed vertex {s} out of range [0, {sg.nv})")
        labels = np.full((sg.nv, B), -1, dtype=np.int32)
        active = np.zeros((sg.nv, B), dtype=bool)
        for q, s in enumerate(seeds):
            labels[s, q] = s
            active[s, q] = True
        return sg.to_padded(labels), sg.to_padded(active)

    return PushProgram(reduce="max", relax=relax,
                       identity=np.int32(-1), init=init,
                       name="cc_seeded", batch=B)


def build_engine(g: Graph, num_parts: int = 1, mesh=None,
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
    """pair_threshold enables pair-lane delivery on dense iterations
    (best after graph.pair_relabel, passing its ``starts`` through;
    labels are vertex ids, so map results back through the relabel
    permutation).  enable_sparse=False drops the src-sorted frontier
    view — the big-scale fit lever (it re-doubles edge memory,
    ShardedGraph.memory_report(push_sparse=True)); every iteration
    then runs dense.

    sources=[a, b, ...] builds the QUERY-BATCHED seeded engine
    (``make_batched_program``): column q labels the vertices
    reachable from seed a with the seed's id (labels [vpad, B], one
    gather serving every query); pair_threshold must be off then."""
    if sg is None:
        vpad_align, _ = sharding_demands(gather)
        sg = ShardedGraph.build(
            g, num_parts, starts=starts,
            pair_threshold=pair_threshold, vpad_align=vpad_align)
    program = (make_program() if sources is None
               else make_batched_program(sources))
    return PushEngine(sg, program, mesh=mesh,
                      pair_threshold=pair_threshold,
                      pair_min_fill=pair_min_fill, exchange=exchange,
                      gather=gather, enable_sparse=enable_sparse,
                      owner_tile_e=owner_tile_e,
                      owner_minmax_fused=owner_minmax_fused,
                      use_mxu=use_mxu, health=health, audit=audit)


def run(g: Graph, num_parts: int = 1, mesh=None, max_iters=None,
        verbose: bool = False):
    """Returns (labels [nv], iterations)."""
    eng = build_engine(g, num_parts, mesh)
    return eng.run(max_iters=max_iters, verbose=verbose)


def symmetrize(src, dst, weights=None):
    """Add reverse edges — CC semantics expect an undirected graph."""
    s = np.concatenate([src, dst])
    d = np.concatenate([dst, src])
    if weights is not None:
        return s, d, np.concatenate([weights, weights])
    return s, d


def reference_components(g: Graph) -> np.ndarray:
    """NumPy oracle: iterate max-propagation to fixed point."""
    src, dst = g.edge_arrays()
    labels = np.arange(g.nv, dtype=np.int64)
    while True:
        new = labels.copy()
        np.maximum.at(new, dst, labels[src])
        if np.array_equal(new, labels):
            return labels
        labels = new


def reference_components_incremental(g_new: Graph,
                                     labels_old: np.ndarray,
                                     new_src, new_dst) -> np.ndarray:
    """NumPy INCREMENTAL oracle (round 20, live graphs): revalidate
    converged max-propagation labels after edge appends by
    propagating ONLY from the touched endpoints (the worklist
    analogue of lux_tpu/livegraph.LiveGraph.revalidate).  Appends
    only ever RAISE max-fixed-point labels (components can merge,
    never split), so seeding from the old fixed point and pushing
    improvements from the new edges converges to exactly
    ``reference_components(g_new)`` — proved in
    tests/test_livegraph.py."""
    src, dst = g_new.edge_arrays()
    labels = np.asarray(labels_old, dtype=np.int64).copy()
    frontier = np.zeros(g_new.nv, dtype=bool)
    for s, d in zip(np.asarray(new_src, np.int64),
                    np.asarray(new_dst, np.int64)):
        if labels[s] > labels[d]:
            labels[d] = labels[s]
            frontier[d] = True
    while frontier.any():
        on = frontier[src]
        new = labels.copy()
        np.maximum.at(new, dst[on], labels[src[on]])
        frontier = new > labels
        labels = new
    return labels


def reference_components_decremental(g_new: Graph,
                                     labels_old: np.ndarray,
                                     touched_dst) -> np.ndarray:
    """NumPy DECREMENTAL oracle (round 21, mutation algebra): repair
    converged max-propagation labels after edge DELETIONS by the
    affected-cone re-seed rule (lux_tpu/livegraph.LiveGraph.
    revalidate's device mirror).  A deletion can LOWER a label
    (a component splits), which max-propagation can never repair; any
    vertex whose label changes is reachable in ``g_new`` from some
    deleted edge's destination (the suffix of its stale label-witness
    path past the LAST deleted edge survives).  Re-seed the cone —
    forward reachability from ``touched_dst`` over ``g_new`` — to the
    init labels (own id) and propagate to fixed point: every label
    starts <= the true fixed point and >= its init seed, so the max
    fixed point is exactly ``reference_components(g_new)`` (proved in
    tests/test_livegraph.py)."""
    src, dst = g_new.edge_arrays()
    labels = np.asarray(labels_old, dtype=np.int64).copy()
    cone = np.zeros(g_new.nv, dtype=bool)
    cone[np.asarray(touched_dst, np.int64)] = True
    while True:
        add = np.zeros(g_new.nv, dtype=bool)
        add[dst[cone[src]]] = True
        add &= ~cone
        if not add.any():
            break
        cone |= add
    labels[cone] = np.arange(g_new.nv, dtype=np.int64)[cone]
    while True:
        new = labels.copy()
        np.maximum.at(new, dst, labels[src])
        if np.array_equal(new, labels):
            return labels
        labels = new


def reference_components_batched(g: Graph, seeds) -> np.ndarray:
    """NumPy seeded-propagation oracle -> ``[nv, B]`` labels: column q
    is ``seeds[q]`` where the vertex is reachable from the seed, -1
    elsewhere.  Column q is BITWISE-equal to running this oracle with
    the single seed ``[seeds[q]]`` (max fixed points are unique;
    tests/test_batched.py asserts the column equality)."""
    src, dst = g.edge_arrays()
    B = len(seeds)
    labels = np.full((g.nv, B), -1, dtype=np.int64)
    for q, s in enumerate(seeds):
        labels[int(s), q] = int(s)
    while True:
        new = labels.copy()
        np.maximum.at(new, dst, labels[src])
        if np.array_equal(new, labels):
            return labels
        labels = new
