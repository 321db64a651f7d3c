"""Push engine: SSSP/BFS and Connected Components vs NumPy oracles,
plus the fixed-point audits and the mesh path."""

import functools

import jax
import numpy as np
import pytest

from lux_tpu import check
from lux_tpu.apps import components, sssp
from lux_tpu.convert import rmat_edges, uniform_random_edges
from lux_tpu.graph import Graph
from lux_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8)


def chain_graph(n=10):
    """0 -> 1 -> ... -> n-1 plus an unreachable island {n, n+1}."""
    src = np.concatenate([np.arange(n - 1), [n]]).astype(np.uint32)
    dst = np.concatenate([np.arange(1, n), [n + 1]]).astype(np.uint32)
    return Graph.from_edges(src, dst, n + 2)


class TestSSSP:
    def test_chain_hops(self):
        g = chain_graph(10)
        dist, iters = sssp.run(g, start_vertex=0, num_parts=2)
        assert dist[:10].tolist() == list(range(10))
        assert sssp.unreachable(dist)[10:].all()
        assert iters == 10  # 9 propagation steps + 1 empty-frontier probe

    @pytest.mark.parametrize("num_parts", [1, 4])
    def test_random_matches_oracle(self, num_parts):
        src, dst = uniform_random_edges(250, 1800, seed=13)
        g = Graph.from_edges(src, dst, 250)
        dist, _ = sssp.run(g, start_vertex=3, num_parts=num_parts)
        want = sssp.reference_sssp(g, start_vertex=3)
        reach = ~sssp.unreachable(dist)
        np.testing.assert_array_equal(dist[reach], want[reach])
        assert np.array_equal(sssp.unreachable(dist),
                              want >= int(sssp.HOP_INF))

    def test_dense_only_app_passthrough(self):
        """The big-scale fit lever: apps expose enable_sparse=False /
        owner_tile_e (sssp/components.build_engine), dropping the
        src-sorted view; results must still match the oracle."""
        src, dst = uniform_random_edges(250, 1800, seed=13)
        g = Graph.from_edges(src, dst, 250)
        eng = sssp.build_engine(g, start_vertex=3, num_parts=2,
                                enable_sparse=False, exchange="owner",
                                owner_tile_e=128)
        assert eng.owner is not None and "src_ids" not in eng.arrays
        dist, _ = eng.run()
        want = sssp.reference_sssp(g, start_vertex=3)
        reach = ~sssp.unreachable(dist)
        np.testing.assert_array_equal(dist[reach], want[reach])

    def test_weighted_matches_oracle(self):
        src, dst, w = uniform_random_edges(120, 900, seed=21,
                                           weighted=True)
        g = Graph.from_edges(src, dst, 120, weights=w)
        dist, _ = sssp.run(g, start_vertex=0, num_parts=3, weighted=True)
        want = sssp.reference_sssp(g, start_vertex=0, weighted=True)
        np.testing.assert_allclose(dist, want.astype(np.float32),
                                   rtol=1e-6)

    @pytest.mark.parametrize("delta", ["auto", 2.5])
    def test_delta_stepping_matches_oracle(self, delta):
        src, dst, w = uniform_random_edges(120, 900, seed=22,
                                           weighted=True)
        g = Graph.from_edges(src, dst, 120, weights=w)
        dist, iters = sssp.run(g, start_vertex=0, num_parts=2,
                               weighted=True, delta=delta)
        want = sssp.reference_sssp(g, start_vertex=0, weighted=True)
        np.testing.assert_allclose(dist, want.astype(np.float32),
                                   rtol=1e-6)
        assert iters > 0

    def test_delta_stepping_mesh_matches_single(self, mesh8):
        src, dst, w = uniform_random_edges(200, 1400, seed=23,
                                           weighted=True)
        g = Graph.from_edges(src, dst, 200, weights=w)
        d1, _ = sssp.run(g, start_vertex=5, num_parts=1, weighted=True,
                         delta="auto")
        d8, _ = sssp.run(g, start_vertex=5, num_parts=8, mesh=mesh8,
                         weighted=True, delta="auto")
        np.testing.assert_allclose(d8, d1, rtol=1e-6)

    def test_delta_below_ulp_terminates(self):
        # Regression: with float32 labels and a bucket width below one
        # ulp at the current distance magnitude, active_min + delta
        # rounds back to active_min and the bucket advance used to
        # livelock inside the compiled while_loop (ADVICE round 1).
        # Weights ~1e8 with delta=1.0 reproduce it: 1.0 < ulp(1e8)=8.
        # max_iters caps only relax iterations, not advances, so a
        # regressed livelock would HANG here — fail via alarm instead.
        import signal

        def boom(signum, frame):
            raise TimeoutError("delta advance livelock regressed")

        old = signal.signal(signal.SIGALRM, boom)
        signal.alarm(120)
        try:
            src = np.array([0, 1, 2], np.uint32)
            dst = np.array([1, 2, 3], np.uint32)
            w = np.full(3, 1e8, np.float32)
            g = Graph.from_edges(src, dst, 4, weights=w)
            dist, _ = sssp.run(g, start_vertex=0, weighted=True,
                               delta=1.0, max_iters=100)
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
        np.testing.assert_allclose(
            dist, np.array([0, 1e8, 2e8, 3e8], np.float32), rtol=1e-6)

    def test_delta_rejects_max_program(self):
        from lux_tpu.engine.push import PushEngine
        g = chain_graph(6)
        from lux_tpu.graph import ShardedGraph
        sg = ShardedGraph.build(g, 1)
        from lux_tpu.apps.components import make_program
        with pytest.raises(ValueError, match="min"):
            PushEngine(sg, make_program(), delta=1.0)

    def test_check_task(self):
        src, dst = uniform_random_edges(150, 1000, seed=17)
        g = Graph.from_edges(src, dst, 150)
        dist, _ = sssp.run(g, start_vertex=0, num_parts=2)
        res = check.check_sssp(g, dist)
        assert res.ok, str(res)
        # a corrupted result must FAIL the audit: inflate the distance
        # of a vertex that has an in-edge from a reached vertex
        d64 = dist.astype(np.int64)
        s, t = g.edge_arrays()
        ok_edges = d64[s] < int(sssp.HOP_INF)
        victim = t[ok_edges][0]
        bad = dist.copy()
        bad[victim] = d64[s[ok_edges][0]] + 10
        assert not check.check_sssp(g, bad).ok

    def test_max_iters_cap(self):
        g = chain_graph(20)
        dist, iters = sssp.run(g, start_vertex=0, max_iters=3)
        assert iters == 3
        assert dist[3] == 3 and sssp.unreachable(dist)[6]

    def test_mesh_matches_single(self, mesh8):
        src, dst, nv = rmat_edges(scale=10, edge_factor=6, seed=6)
        g = Graph.from_edges(src, dst, nv)
        d1, i1 = sssp.run(g, start_vertex=1, num_parts=8)
        d8, i8 = sssp.run(g, start_vertex=1, num_parts=8, mesh=mesh8)
        np.testing.assert_array_equal(d1, d8)
        assert i1 == i8

    def test_verbose_stepwise_matches(self, capsys):
        g = chain_graph(5)
        d1, _ = sssp.run(g, start_vertex=0, num_parts=2, verbose=True)
        out = capsys.readouterr().out
        assert "frontier=" in out
        d2, _ = sssp.run(g, start_vertex=0, num_parts=2)
        np.testing.assert_array_equal(d1, d2)


class TestComponents:
    def test_two_islands(self):
        # undirected pairs: {0,1,2} and {3,4}
        src = np.array([0, 1, 3], dtype=np.uint32)
        dst = np.array([1, 2, 4], dtype=np.uint32)
        s, d = components.symmetrize(src, dst)
        g = Graph.from_edges(s, d, 5)
        labels, _ = components.run(g, num_parts=2)
        assert labels[0] == labels[1] == labels[2] == 2
        assert labels[3] == labels[4] == 4

    @pytest.mark.parametrize("num_parts", [1, 5])
    def test_random_matches_oracle(self, num_parts):
        src, dst = uniform_random_edges(300, 600, seed=31)
        s, d = components.symmetrize(src, dst)
        g = Graph.from_edges(s, d, 300)
        labels, _ = components.run(g, num_parts=num_parts)
        want = components.reference_components(g)
        np.testing.assert_array_equal(labels, want)
        assert check.check_components(g, labels).ok

    def test_mesh_matches_single(self, mesh8):
        src, dst = uniform_random_edges(400, 900, seed=33)
        s, d = components.symmetrize(src, dst)
        g = Graph.from_edges(s, d, 400)
        l1, _ = components.run(g, num_parts=8)
        l8, _ = components.run(g, num_parts=8, mesh=mesh8)
        np.testing.assert_array_equal(l1, l8)

    def test_check_catches_corruption(self):
        src = np.array([0, 1], dtype=np.uint32)
        dst = np.array([1, 0], dtype=np.uint32)
        g = Graph.from_edges(src, dst, 2)
        labels, _ = components.run(g)
        assert check.check_components(g, labels).ok
        assert not check.check_components(g, np.array([5, 0])).ok


def test_pagerank_residual_check():
    from lux_tpu.apps import pagerank
    src, dst = uniform_random_edges(100, 800, seed=41)
    g = Graph.from_edges(src, dst, 100)
    ranks = pagerank.run(g, 60, num_parts=2)
    assert check.check_pagerank(g, ranks, tol=1e-5).ok


def test_delta_rejects_nonpositive():
    src, dst, w = uniform_random_edges(60, 300, seed=30, weighted=True)
    g = Graph.from_edges(src, dst, 60, weights=w)
    with pytest.raises(ValueError, match="not > 0"):
        sssp.build_engine(g, 0, weighted=True, delta=0.0)
    # fractional delta on int32 hop labels truncates to 0 -> rejected
    with pytest.raises(ValueError, match="not > 0"):
        sssp.build_engine(g, 0, weighted=False, delta=0.5)


@pytest.mark.parametrize("app", ["sssp", "cc"])
def test_push_streamed_dense_matches_default(app):
    """stream_msgs=True (billion-edge memory mode) dense iterations
    must reach the same fixed point as the fused form."""
    from lux_tpu.apps import components, sssp
    from lux_tpu.convert import rmat_graph
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.graph import Graph, ShardedGraph

    g = rmat_graph(scale=9, edge_factor=8, seed=15)
    if app == "cc":
        s, d = components.symmetrize(*g.edge_arrays())
        g = Graph.from_edges(s, d, g.nv)
        prog = components.make_program()
        ref = components.reference_components(g)
    else:
        prog = sssp.make_program(0)
        ref = sssp.reference_sssp(g, 0)
    # disable sparse so every iteration exercises the DENSE streamed
    # path
    eng = PushEngine(ShardedGraph.build(g, 2), prog,
                     enable_sparse=False, stream_msgs=True)
    assert eng.stream_chunks
    label, active = eng.init_state()
    label, active, _ = eng.converge(label, active, 200)
    np.testing.assert_array_equal(
        eng.unpad(label).astype(np.int64), ref)


# ---------------------------------------------------------------------
# the sparse iteration's ladder (PR 29): each iteration runs on the
# smallest queue / edge-budget rungs that hold its frontier, and
# nothing but the shapes changes


LADDER_NV = 8192
LADDER_EB = 1600                  # top edge budget: rungs 100/400/1600
_T0, _W0 = 6144, 8000             # target pools, in the LAST part


def _ladder_graph(weighted=False):
    """Vertices 0..10: sources of out-degree 1, 2, 4 .. 1024 (vertex
    10, degree 1024, is a hub over every lower budget), all into the
    target pool T = [_T0, _T0 + 1024); vertices 16..1000 have no
    out-edges; every T vertex has one edge into W = [_W0, _W0 + 64).
    Any (count, out-edge total) with total < 2048 and count >= 11 is a
    frontier of sources (the total's binary digits) plus no-out-edge
    vertices."""
    src = [np.full(1 << k, k) for k in range(11)]
    dst = [_T0 + np.arange(1 << k) for k in range(11)]
    src.append(_T0 + np.arange(1024))
    dst.append(_W0 + np.arange(1024) % 64)
    src, dst = np.concatenate(src), np.concatenate(dst)
    w = None
    if weighted:
        w = np.random.default_rng(5).integers(
            1, 9, src.size).astype(np.float32)
    return Graph.from_edges(src, dst, LADDER_NV, weights=w)


def _ladder_frontier(count, total):
    """Vertex ids of a frontier of ``count`` vertices and ``total``
    out-edges on ``_ladder_graph``."""
    srcs = [k for k in range(11) if total >> k & 1]
    assert total < 2048 and len(srcs) <= count <= len(srcs) + 984
    return np.asarray(srcs + list(range(16, 16 + count - len(srcs))))


_LADDER_KINDS = {
    # kind: (program, weighted, num_parts, mesh devices, use_mxu)
    "bfs-np1": ("min", False, 1, 0, "auto"),
    "bfs-np1-mxu": ("min", False, 1, 0, True),
    "bfs-mesh2": ("min", False, 2, 2, "auto"),
    "bfs-mesh4": ("min", False, 4, 4, "auto"),
    "sssp-weighted-np2": ("min", True, 2, 0, "auto"),
    "cc-max-mesh2": ("max", False, 2, 2, "auto"),
}


@functools.lru_cache(maxsize=None)
def _ladder_engines(kind):
    """-> (graph, ladder engine, top-rung-only engine), built once a
    kind: every case of a kind is another initial frontier on the same
    two compiled programs."""
    from lux_tpu.engine import push
    from lux_tpu.graph import ShardedGraph
    reduce, weighted, num_parts, ndev, use_mxu = _LADDER_KINDS[kind]
    g = _ladder_graph(weighted)
    starts = np.linspace(0, LADDER_NV, num_parts + 1).astype(np.int64)
    sg = ShardedGraph.build(g, num_parts, starts=starts)
    prog = (sssp.make_program(0, weighted) if reduce == "min"
            else components.make_program())
    mesh = make_mesh(ndev) if ndev else None

    def build():
        return push.PushEngine(sg, prog, mesh=mesh, use_mxu=use_mxu,
                               edge_budget=LADDER_EB)

    # the tests' own ladder, three budget rungs, whatever divisors
    # the engine ships with
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(push, "QUEUE_RUNG_DIVISORS", (16,))
        mp.setattr(push, "BUDGET_RUNG_DIVISORS", (16, 4))
        eng = build()
        mp.setattr(push, "QUEUE_RUNG_DIVISORS", ())
        mp.setattr(push, "BUDGET_RUNG_DIVISORS", ())
        top = build()
    assert top.queue_rungs == (eng.queue_cap,)
    assert top.budget_rungs == (LADDER_EB,)
    assert eng.queue_rungs == (eng.queue_cap // 16, eng.queue_cap)
    assert eng.budget_rungs == (100, 400, LADDER_EB)
    return g, eng, top


def _ladder_state(eng, frontier):
    """(label, active) host arrays with ``frontier`` active: BFS/SSSP
    sources at distance 0; components' own-id labels."""
    label, _ = eng.program.init(eng.sg)
    label = eng.sg.from_padded(np.asarray(label)).copy()
    if eng.program.reduce == "min":
        label[:] = eng.program.identity
        label[frontier] = 0
    active = np.zeros(eng.sg.nv, bool)
    active[frontier] = True
    return eng.sg.to_padded(label), eng.sg.to_padded(active)


def _last_mark():
    from lux_tpu import telemetry
    return [r for r in telemetry.spans()
            if r["name"] == "push.converge"][-1]["counts"]


def _rule(eng, g, active_host):
    """The ladder's rule in NumPy from the frontier entering an
    iteration -> (count, most out-edges landing in one part, sparse?,
    lower budget rung?)."""
    act = eng.sg.from_padded(active_host)
    count = int(act.sum())
    src, dst = g.edge_arrays()[:2]
    part = np.searchsorted(eng.sg.starts, dst[act[src]],
                           side="right") - 1
    total = int(np.bincount(part, minlength=eng.sg.num_parts).max())
    usable, limit, _pull = eng._sparse_mode()
    sparse = usable and count <= limit
    return count, total, sparse, sparse and total <= eng.budget_rungs[-2]


def _check_ladder_case(kind, count, total):
    """One initial frontier of (count, total) on the ladder engine and
    on the top-rung-only engine: labels, ``iters`` and ``sparse_iters``
    bit-identical; then the ladder engine again one iteration a call,
    each iteration's ``sparse_iters`` / ``low_rung_iters`` against the
    rule on the frontier that entered it."""
    g, eng, top = _ladder_engines(kind)
    frontier = _ladder_frontier(count, total)
    runs = {}
    for name, e in (("ladder", eng), ("top", top)):
        label, _a, it = e.converge(*e.place(*_ladder_state(e, frontier)))
        runs[name] = (e.unpad(label), int(it), _last_mark())
        assert runs[name][2]["iters"] == int(it)
    lab, it, counts = runs["ladder"]
    tlab, tit, tcounts = runs["top"]
    np.testing.assert_array_equal(lab, tlab)
    assert (it, counts["sparse_iters"]) == (tit, tcounts["sparse_iters"])
    assert tcounts["low_rung_iters"] == 0

    label, active = eng.place(*_ladder_state(eng, frontier))
    series, n_sparse, n_low = [], 0, 0
    for _ in range(it):
        cnt, tot, sparse, low = _rule(eng, g, np.asarray(active))
        label, active, one = eng.converge(label, active, 1)
        mark = _last_mark()
        assert (int(one), mark["sparse_iters"], mark["low_rung_iters"]) \
            == (1, int(sparse), int(low)), (cnt, tot)
        series.append((cnt, tot))
        n_sparse += sparse
        n_low += low
    np.testing.assert_array_equal(eng.unpad(label), lab)
    assert not np.asarray(active).any()
    assert (n_sparse, n_low) == (counts["sparse_iters"],
                                 counts["low_rung_iters"])
    return series, counts


def _q_cases(kind):
    """Counts just under, at and just over the lower queue rung, and
    at the sparse limit (the top queue's last count)."""
    _g, eng, _top = _ladder_engines(kind)
    q0 = eng.queue_rungs[0]
    return {"q0-1": q0 - 1, "q0": q0, "q0+1": q0 + 1,
            "limit": eng._sparse_mode()[1]}


_E_CASES = {"eb0-1": 99, "eb0": 100, "eb0+1": 101,
            "eb1-1": 399, "eb1": 400, "eb1+1": 401,
            "top-1": 1599, "top": 1600, "top+1": 1601}


@pytest.mark.parametrize("e_case", list(_E_CASES))
@pytest.mark.parametrize("q_case", ["q0-1", "q0", "q0+1", "limit"])
def test_ladder_single_part_every_boundary(q_case, e_case):
    """One part, so the first iteration's (count, total) is exactly
    the case's: count fits a rung and total does not, and the reverse,
    at / just under / just over every rung; one edge over the top
    budget still truncates (a second sparse iteration finishes the
    queue) and still converges."""
    count, total = _q_cases("bfs-np1")[q_case], _E_CASES[e_case]
    series, counts = _check_ladder_case("bfs-np1", count, total)
    assert series[0] == (count, total)
    assert series[0][1] > LADDER_EB or series[1][0] != series[0][0]
    if total > LADDER_EB:             # truncated: the suffix came back
        assert series[1][0] >= 1 and counts["sparse_iters"] >= 2
    assert counts["low_rung_iters"] >= (total <= 400)


@pytest.mark.parametrize("q_case,e_case", [
    ("q0-1", "eb0"), ("q0", "eb0+1"), ("q0+1", "eb1"),
    ("q0", "eb1+1"), ("limit", "eb0-1"), ("limit", "top"),
    ("q0+1", "top+1"), ("limit", "top+1")])
@pytest.mark.parametrize("kind", [k for k in _LADDER_KINDS
                                  if k != "bfs-np1"])
def test_ladder_kinds_boundaries(kind, q_case, e_case):
    """The same on meshes of 2 and 4 devices (the rung is the psum'd
    count's and the pmax'd total's, one for every device: the branches
    hold the collectives), weighted, through the MXU expansion, and
    for a max program.  Every target lies in the last part, so the
    most any part holds is the whole total."""
    count, total = _q_cases(kind)[q_case], _E_CASES[e_case]
    series, _counts = _check_ladder_case(kind, count, total)
    assert series[0] == (count, total)


@pytest.mark.parametrize("kind", ["bfs-np1", "bfs-mesh4"])
def test_ladder_hub_over_every_lower_budget_takes_the_top(kind):
    """A frontier of ONE vertex whose degree (1024) exceeds both lower
    budgets: a lower rung would have to truncate it and could never
    finish it; the rule sends it to the top."""
    g, eng, _top = _ladder_engines(kind)
    assert 1024 > eng.budget_rungs[-2]
    label, active = eng.place(*_ladder_state(eng, np.asarray([10])))
    assert _rule(eng, g, np.asarray(active)) == (1, 1024, True, False)
    label, active, _it = eng.converge(label, active, 1)
    mark = _last_mark()
    assert (mark["sparse_iters"], mark["low_rung_iters"]) == (1, 0)
    got = eng.unpad(label)
    assert (got[_T0:_T0 + 1024] == 1).all() and got[10] == 0


# ---------------------------------------------------------------------
# the bottom-up step (PR 33): a frontier too wide for the queue still
# runs on the sparse ladder where the UNREACHED vertices fit it, the
# graph is symmetric and no reached vertex can improve


def _mark_after(eng, label, active, max_iters=None):
    """converge from a host state -> (labels [nv], iterations, mark)."""
    label, _a, it = eng.converge(*eng.place(label, active), max_iters)
    return eng.unpad(label), int(it), _last_mark()


def _bfs_levels(g, root):
    """Plain frontier BFS in NumPy -> hop labels, HOP_INF unreached."""
    src, dst = g.edge_arrays()
    order = np.argsort(src, kind="stable")
    nbr = dst[order]
    off = np.concatenate([[0], np.cumsum(np.bincount(src,
                                                     minlength=g.nv))])
    levels = np.full(g.nv, int(sssp.HOP_INF), np.int64)
    levels[root] = 0
    frontier, depth = np.asarray([root]), 0
    while frontier.size:
        depth += 1
        seen = np.unique(np.concatenate(
            [nbr[off[v]:off[v + 1]] for v in frontier]))
        frontier = seen[levels[seen] > depth]
        levels[frontier] = depth
    return levels


@functools.lru_cache(maxsize=None)
def _kron_engines(num_parts=1):
    """A symmetrized Kronecker graph (scale 12) -> (graph, engine,
    the same engine with the step unavailable): the queue shrunk
    (``sparse_threshold`` 32), and still a search's wide late level
    leaves fewer unreached vertices than it holds."""
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.graph import ShardedGraph
    src, dst, nv = rmat_edges(12, 16, 3)
    g = Graph.from_edges(np.concatenate([src, dst]),
                         np.concatenate([dst, src]), nv)
    sg = ShardedGraph.build(g, num_parts)

    def build():
        return PushEngine(sg, sssp.make_program(0), sparse_threshold=32)

    eng = build()
    with pytest.MonkeyPatch.context() as mp:
        # the seam: the view is not taken for an in-edge list
        sg._symmetric_cache = None
        mp.setattr(ShardedGraph, "edges_symmetric", lambda self: False)
        off = build()
    assert eng.pull and not off.pull
    assert eng._sparse_mode() == off._sparse_mode()[:2] + (True,)
    return g, eng, off


def _root_state(eng, root):
    label = np.full(eng.sg.nv, sssp.HOP_INF, np.int32)
    active = np.zeros(eng.sg.nv, bool)
    label[root], active[root] = 0, True
    return eng.sg.to_padded(label), eng.sg.to_padded(active)


def _sweep_roots(g):
    deg = np.asarray(g.out_degrees)
    some = np.flatnonzero(deg > 0)
    return [int(np.argmax(deg)), int(np.flatnonzero(deg == 1)[0]),
            int(np.flatnonzero(deg == 16)[0]),
            *map(int, some[:: len(some) // 5][:5])]


@pytest.mark.parametrize("k", range(8))
def test_pull_step_sweep_equals_the_frontier_bfs(k):
    """(a) every root of a sweep: labels are the NumPy BFS's, the
    iteration count is the one without the step (a dense iteration
    became a bottom-up one that finds the same vertices), and the step
    ran."""
    g, eng, off = _kron_engines()
    root = _sweep_roots(g)[k]
    got, it, mark = _mark_after(eng, *_root_state(eng, root))
    want, it_off, mark_off = _mark_after(off, *_root_state(off, root))
    np.testing.assert_array_equal(got, _bfs_levels(g, root))
    np.testing.assert_array_equal(got, want)
    assert it == it_off
    assert mark_off["pull_iters"] == 0
    assert mark["pull_iters"] >= 1
    assert mark["sparse_iters"] == \
        mark_off["sparse_iters"] + mark["pull_iters"]


def test_pull_step_is_not_built_on_a_directed_graph():
    """(b) a directed graph keeps the program it had: no step, two
    counters (and, since PR 39, the ladder's fill words) behind the
    public outputs, no ``lux_pull`` in the lowered text,
    ``pull_iters`` 0 on the mark."""
    src, dst, nv = rmat_edges(10, 8, 0)
    eng = sssp.build_engine(Graph.from_edges(src, dst, nv), 0)
    assert not eng.pull and eng._sparse_mode()[2] is False
    jitted, args = eng.audit_variant("converge")
    assert len(jax.eval_shape(jitted, *args())) == 3 + 2 + 1
    assert "lux_pull" not in jitted.lower(*args()).as_text(
        debug_info=True)
    _labels, it = eng.run()
    mark = _last_mark()
    assert mark["pull_iters"] == 0 and mark["iters"] == it
    _g, on, _off = _kron_engines()
    jitted, args = on.audit_variant("converge")
    assert len(jax.eval_shape(jitted, *args())) == 3 + 3 + 1
    assert "lux_pull" in jitted.lower(*args()).as_text(debug_info=True)


# a hand-built symmetric graph for the three conditions: root 0, a
# wide level A (600 vertices, past the count limit of 256), and the
# unreached T behind it, each T vertex tied to ``fan`` vertices of A
_HUB_NV, _HUB_A = 4096, 600


@functools.lru_cache(maxsize=None)
def _hub_case(n_t, fan, edge_budget):
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.graph import ShardedGraph
    a = 1 + np.arange(_HUB_A)
    t = 1 + _HUB_A + np.arange(n_t)
    src = [np.zeros(_HUB_A, np.int64), np.repeat(t, fan)]
    dst = [a, a[(np.arange(n_t * fan) * 7) % _HUB_A]]
    src, dst = np.concatenate(src), np.concatenate(dst)
    g = Graph.from_edges(np.concatenate([src, dst]),
                         np.concatenate([dst, src]), _HUB_NV)
    eng = PushEngine(ShardedGraph.build(g, 1), sssp.make_program(0),
                     edge_budget=edge_budget)
    assert eng.pull and eng.queue_cap == 356
    assert eng._sparse_mode()[1] == 256
    return g, eng, t


@pytest.mark.parametrize("n_t,fan,edge_budget,pulls", [
    (8, 5, 1000, True),            # fits both, on the LOW budget rung
    (8, 100, 1000, True),          # fits both, on the top one
    (8, 100, 800, True),           # its edges AT the top budget
    (8, 100, 799, False),          # one edge over it: dense
    (356, 1, 1000, True),          # its count AT the queue's capacity
    (357, 1, 1000, False),         # one vertex over it: dense
    (400, 2, 700, False),          # over both
], ids=["fits-low", "fits", "edges-at", "edges-over", "count-at",
        "count-over", "both-over"])
def test_pull_step_never_truncates(n_t, fan, edge_budget, pulls):
    """(d) the unreached set must fit the queue's top rung and its
    edges the top budget, or the wide level runs dense; the answer is
    the BFS's either way."""
    g, eng, t = _hub_case(n_t, fan, edge_budget)
    assert eng.budget_rungs == (edge_budget // 16, edge_budget)
    label, active = _root_state(eng, 0)
    label, active, _ = eng.converge(*eng.place(label, active), 1)
    assert int(np.asarray(active).sum()) == _HUB_A
    label, active, _ = eng.converge(label, active, 1)
    mark = _last_mark()
    assert (mark["sparse_iters"], mark["pull_iters"]) == \
        (int(pulls), int(pulls))
    assert mark["low_rung_iters"] == int(
        pulls and n_t * fan <= eng.budget_rungs[0])
    got = eng.unpad(label)
    assert (got[t] == 2).all()
    # the new frontier is T, whichever way it was found
    np.testing.assert_array_equal(
        np.flatnonzero(eng.sg.from_padded(np.asarray(active))), t)
    label, _a, _ = eng.converge(label, active)
    np.testing.assert_array_equal(eng.unpad(label), _bfs_levels(g, 0))


def test_pull_step_declines_on_a_stale_label():
    """(c) the guard: one vertex of the next level already holds a
    label, too long by three.  Pushing from the frontier repairs it;
    pulling into the unreached would leave it, so the step declines
    and the answer stays exact.  Without the stale label the same
    state runs bottom-up."""
    g, eng, t = _hub_case(8, 100, 1000)
    label, active = _root_state(eng, 0)
    label, active, _ = eng.converge(*eng.place(label, active), 1)
    label, active = np.asarray(label).copy(), np.asarray(active)
    assert (eng.sg.from_padded(label)[t] == sssp.HOP_INF).all()
    stale = label.copy()
    stale[0, t[3]] = 2 + 3
    for lab, pulls in ((label, 1), (stale, 0)):
        one, act, _ = eng.converge(*eng.place(lab, active), 1)
        assert _last_mark()["pull_iters"] == pulls
        assert eng.unpad(one)[t[3]] == 2
        done, _a, _ = eng.converge(one, act)
        np.testing.assert_array_equal(eng.unpad(done),
                                      _bfs_levels(g, 0))


def test_pull_step_stays_off_an_empty_unreached_set():
    """Nothing left to find and a frontier past the count limit: dense
    as before, never a bottom-up step that only clears the frontier."""
    g, eng, t = _hub_case(8, 100, 1000)
    label = np.full(_HUB_NV, 1, np.int32)
    label[0] = 0
    label[1 + _HUB_A + 8:] = sssp.HOP_INF      # the isolated rest
    label[t] = 2
    active = np.zeros(_HUB_NV, bool)
    active[1:1 + _HUB_A] = True
    got, it, mark = _mark_after(eng, eng.sg.to_padded(label),
                                eng.sg.to_padded(active))
    assert (mark["pull_iters"], mark["sparse_iters"]) == (0, 0)
    np.testing.assert_array_equal(got, _bfs_levels(g, 0))


@pytest.mark.parametrize("app", ["components", "sssp-weighted",
                                 "sssp-weighted-delta"])
@pytest.mark.parametrize("num_parts", [1, 4])
def test_pull_step_leaves_the_other_programs_answers(app, num_parts):
    """(f) on a symmetric graph the max-reduce program and weighted
    SSSP, with and without the delta schedule, equal their references;
    components never has a vertex at the identity, the delta schedule
    builds no step."""
    src, dst, nv = rmat_edges(10, 8, 5)
    w = np.random.default_rng(2).integers(1, 9, src.size).astype(
        np.float32)
    src, dst, w = (np.concatenate([src, dst]),
                   np.concatenate([dst, src]), np.concatenate([w, w]))
    if app == "components":
        g = Graph.from_edges(src, dst, nv)
        eng = components.build_engine(g, num_parts=num_parts)
        want = components.reference_components(g)
    else:
        g = Graph.from_edges(src, dst, nv, weights=w)
        eng = sssp.build_engine(
            g, 0, num_parts=num_parts, weighted=True,
            delta="auto" if app.endswith("delta") else None)
        want = sssp.reference_sssp(g, 0, weighted=True)
    assert eng.pull == (not app.endswith("delta"))
    got, _it = eng.run()
    mark = _last_mark()
    if app == "components":
        assert mark["pull_iters"] == 0
    np.testing.assert_array_equal(
        np.where(np.isfinite(got), got, np.inf) if got.dtype.kind == "f"
        else got, want)


def test_pull_step_on_weighted_levels_where_its_guard_holds():
    """Weighted SSSP with every weight equal is BFS in disguise: the
    guard (best frontier label + the least weight against the worst
    reached label) holds on the wide level and the step engages; the
    answer is the reference's."""
    g0, eng0, t = _hub_case(8, 100, 1000)
    from lux_tpu.engine.push import PushEngine
    src, dst = g0.edge_arrays()
    g = Graph.from_edges(src, dst, g0.nv,
                         weights=np.full(src.size, 2.5, np.float32))
    eng = PushEngine(eng0.sg.__class__.build(g, 1),
                     sssp.make_program(0, weighted=True),
                     edge_budget=1000)
    assert eng.pull and eng._weight_ends == (2.5, 2.5)
    got, _it = eng.run()
    assert _last_mark()["pull_iters"] == 1
    np.testing.assert_array_equal(
        got, sssp.reference_sssp(g, 0, weighted=True))
