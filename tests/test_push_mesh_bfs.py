"""Graph500 BFS sharded over a mesh of four (the benchmark's cell
``bfs.kron23.mesh4`` at a size the CPU's virtual devices run): the
push engine's owner min-exchange, the gathered frontier queues and the
ladder's rung choice across a mesh give the levels a plain frontier
BFS gives, exactly, whatever the root, the fused exchange and the pair
rows."""

import functools

import numpy as np
import pytest

from lux_tpu import telemetry
from lux_tpu.apps import sssp
from lux_tpu.convert import rmat_edges
from lux_tpu.graph import Graph, ShardedGraph, pair_relabel
from lux_tpu.parallel.mesh import make_mesh

SCALE, EDGE_FACTOR, GRAPH_SEED = 10, 16, 3
# benchmarks/configs/kron23-bfs-mesh4.json "engine", and the two knobs
# the cases vary
ENGINE = {"pair_threshold": 16, "pair_min_fill": 24,
          "enable_sparse": True, "exchange": "owner"}
CASES = [(fused, pairs) for fused in (False, True)
         for pairs in (True, False)]
CASE_IDS = [f"{'fused' if f else 'unfused'}-{'pairs' if p else 'nopairs'}"
            for f, p in CASES]
ROOTS = ("hub", "leaf", "overflow", "middle")


@functools.lru_cache(maxsize=None)
def _graph():
    """The symmetrized Kronecker graph (Graph500's A/B/C, edge factor
    16) with its adjacency by source and the levels' oracle."""
    src, dst, nv = rmat_edges(SCALE, EDGE_FACTOR, GRAPH_SEED)
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    deg = np.bincount(src, minlength=nv)
    offsets = np.concatenate([[0], np.cumsum(deg)])
    neighbours = dst[np.argsort(src, kind="stable")]
    return Graph.from_edges(src, dst, nv), offsets, neighbours


@functools.lru_cache(maxsize=None)
def _frontier_bfs(root):
    """Plain frontier BFS -> (levels [nv], -1 where unreached;
    [(frontier vertices, their out-edges)] entering each iteration)."""
    g, offsets, neighbours = _graph()
    levels = np.full(g.nv, -1, np.int64)
    levels[root] = 0
    frontier, profile = np.asarray([root]), []
    while frontier.size:
        profile.append((frontier.size, int(
            (offsets[frontier + 1] - offsets[frontier]).sum())))
        reached = np.unique(np.concatenate(
            [neighbours[offsets[v]:offsets[v + 1]] for v in frontier]))
        frontier = reached[levels[reached] < 0]
        levels[frontier] = len(profile)
    return levels, profile


@functools.lru_cache(maxsize=None)
def _engine(num_parts, fused, pairs):
    """-> (engine, perm or None): built as benchmarks/runners/
    batch_push.py builds the cell's (degree relabel under the pair
    threshold, the layout, ``apps.sssp.build_engine``)."""
    g, _o, _n = _graph()
    opts = dict(ENGINE, owner_minmax_fused=fused)
    if num_parts == 1:
        del opts["exchange"]        # kron21-bfs: nothing to exchange
    g_run, perm, starts = g, None, None
    if pairs:
        g_run, perm, starts = pair_relabel(
            g, num_parts, pair_threshold=opts["pair_threshold"])
    else:
        del opts["pair_threshold"], opts["pair_min_fill"]
    sg = ShardedGraph.build(g_run, num_parts, starts=starts,
                            pair_threshold=opts.get("pair_threshold"))
    mesh = make_mesh(num_parts) if num_parts > 1 else None
    eng = sssp.build_engine(g_run, start_vertex=0, num_parts=num_parts,
                            mesh=mesh, weighted=False, sg=sg, **opts)
    return eng, perm


def _search(eng, perm, root):
    """One search from a fresh state through place / converge / unpad
    -> (levels in the generator's ids, the ``push.converge`` mark)."""
    nv, sg = eng.sg.nv, eng.sg
    rank = np.arange(nv)
    if perm is not None:
        rank = np.empty(nv, np.int64)
        rank[perm] = np.arange(nv)
    label = np.full(nv, sssp.HOP_INF, np.int32)
    active = np.zeros(nv, bool)
    label[rank[root]], active[rank[root]] = 0, True
    label, active = eng.place(sg.to_padded(label), sg.to_padded(active))
    label, _active, _it = eng.converge(label, active)
    hops = eng.unpad(label).astype(np.int64)
    mark = [r for r in telemetry.spans()
            if r["name"] == "push.converge"][-1]["counts"]
    levels = np.where(hops >= sssp.HOP_INF, -1, hops)
    return levels[rank], mark


@functools.lru_cache(maxsize=None)
def _roots():
    """A hub, a leaf, a root whose level-2 frontier fits the sparse
    queue while its out-edges overflow the top edge budget of every
    part's share (more than num_parts x the budget in all), and one of
    middling degree; generator ids."""
    _g, offsets, _n = _graph()
    eng, _perm = _engine(4, False, True)
    deg = np.diff(offsets)
    _usable, limit, _pull = eng._sparse_mode()

    def overflows(v):
        profile = _frontier_bfs(int(v))[1]
        return (len(profile) > 2 and profile[2][0] <= limit
                and profile[2][1] > 4 * eng.edge_budget)

    overflow = next(int(v) for v in np.flatnonzero(deg > 1)
                    if overflows(v))
    return {"hub": int(np.argmax(deg)),
            "leaf": int(np.flatnonzero(deg == 1)[0]),
            "overflow": overflow,
            "middle": int(np.flatnonzero(deg == 16)[0])}


@pytest.mark.parametrize("root", ROOTS)
@pytest.mark.parametrize("fused,pairs", CASES, ids=CASE_IDS)
def test_mesh_levels_equal_the_frontier_bfs_and_one_part(fused, pairs,
                                                        root):
    v = _roots()[root]
    want, _profile = _frontier_bfs(v)
    got, _mark = _search(*_engine(4, fused, pairs), v)
    np.testing.assert_array_equal(got, want)
    one, _mark1 = _search(*_engine(1, fused, pairs), v)
    np.testing.assert_array_equal(got, one)


@pytest.mark.parametrize("fused,pairs", CASES, ids=CASE_IDS)
def test_the_ladder_ran_across_the_mesh(fused, pairs):
    """Sparse iterations, and among them lower edge-budget rungs, on
    the mesh; the overflowing level-2 frontier takes the top rung."""
    eng, perm = _engine(4, fused, pairs)
    assert (eng.delivery.pairs is not None) == pairs
    for root in ROOTS:
        _levels, mark = _search(eng, perm, _roots()[root])
        assert mark["sparse_iters"] > 0 and mark["low_rung_iters"] > 0
        # and a level too wide for the queue: dense, or bottom-up
        assert mark["iters"] - mark["sparse_iters"] \
            + mark["pull_iters"] > 0
    _levels, mark = _search(eng, perm, _roots()["overflow"])
    assert mark["sparse_iters"] > mark["low_rung_iters"]


@pytest.mark.parametrize("fused,pairs", CASES, ids=CASE_IDS)
def test_the_step_names_both_exchanges(fused, pairs):
    """The lowered step carries the scopes the benchmark's trace
    metrics read: the dense branch's owner delivery and the sparse
    branch's collectives."""
    eng, _perm = _engine(4, fused, pairs)
    jitted, args = eng.audit_variant("step")
    text = jitted.lower(*args()).as_text(debug_info=True)
    assert "lux_sparse_exchange" in text and "lux_gen_exchange" in text


def test_one_part_names_no_sparse_exchange():
    """On one device the sparse branch has no collective, and the
    scope names nothing: the one-chip program is the parent's."""
    eng, _perm = _engine(1, False, True)
    jitted, args = eng.audit_variant("step")
    text = jitted.lower(*args()).as_text(debug_info=True)
    assert "lux_sparse_exchange" not in text and "lux_sparse" in text


# ---- the bottom-up step across parts (PR 33) ------------------------


@functools.lru_cache(maxsize=None)
def _engine_four_parts_one_device():
    """Four parts with no mesh: the cross-part combine of the pulled
    buffer is a reduce over the part axis instead of a collective."""
    g, _o, _n = _graph()
    g_run, perm, starts = pair_relabel(
        g, 4, pair_threshold=ENGINE["pair_threshold"])
    sg = ShardedGraph.build(g_run, 4, starts=starts,
                            pair_threshold=ENGINE["pair_threshold"])
    opts = {k: v for k, v in ENGINE.items() if k != "exchange"}
    return sssp.build_engine(g_run, start_vertex=0, num_parts=4,
                             weighted=False, sg=sg, **opts), perm


@pytest.mark.parametrize("root", ROOTS)
@pytest.mark.parametrize("layout", ["mesh4", "mesh4-fused-nopairs",
                                    "parts4"])
def test_the_bottom_up_step_combines_across_parts(layout, root):
    """A search whose wide level is found bottom-up: every part
    reduces its local neighbours' candidates into the gathered queue's
    buffer, one ``pmin`` over the mesh (a reduce over the part axis on
    one device) combines them, and the levels are the one-part
    engine's and the frontier BFS's."""
    eng, perm = {"mesh4": lambda: _engine(4, False, True),
                 "mesh4-fused-nopairs": lambda: _engine(4, True, False),
                 "parts4": _engine_four_parts_one_device}[layout]()
    assert eng.pull
    v = _roots()[root]
    got, mark = _search(eng, perm, v)
    np.testing.assert_array_equal(got, _frontier_bfs(v)[0])
    one, mark1 = _search(*_engine(1, False, True), v)
    np.testing.assert_array_equal(got, one)
    assert mark["iters"] == mark1["iters"]
    assert mark["pull_iters"] >= 1 and mark1["pull_iters"] >= 1


def test_the_pulled_buffer_is_exchanged_under_the_sparse_scope():
    """On the mesh the step's combine is a collective of the sparse
    branch's exchange scope, inside ``lux_pull``."""
    eng, _perm = _engine(4, False, True)
    jitted, args = eng.audit_variant("step")
    text = jitted.lower(*args()).as_text(debug_info=True)
    assert "lux_pull/lux_sparse_exchange" in text
