"""Weighted SSSP with INTEGER weights: int32 distances, summed in int32
and exact (the GAP benchmark's SSSP contract), under every schedule the
push engine offers.

Float weights give float32 distances (``test_sssp_float_weights.py``,
bit for bit as before); integer weights used to be summed in float32
too, which is exact only while every sum stays under 2^24.  Here the
reference is a binary-heap Dijkstra in Python integers written in this
file, independent of ``benchmarks/`` and of the program.
"""

import functools
import heapq

import numpy as np
import pytest

from lux_tpu import cli, device_check, telemetry
from lux_tpu import format as luxfmt
from lux_tpu.apps import sssp
from lux_tpu.convert import rmat_edges
from lux_tpu.graph import Graph, ShardedGraph, pair_relabel

SCALE, EF, SEED = 9, 8, 11
NV = 1 << SCALE
INF = int(sssp.HOP_INF)
# plain frontiers, the rule, and three fixed widths: far under the
# mean weight (500), about it, and over the largest
DELTAS = (None, "auto", 7, 500, 4000)


def dijkstra(nv, src, dst, w, root):
    out = [[] for _ in range(nv)]
    for s, d, x in zip(src.tolist(), dst.tolist(), w.tolist()):
        out[s].append((d, x))
    dist = [INF] * nv
    dist[root] = 0
    heap = [(0, root)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for v, x in out[u]:
            if d + x < dist[v]:
                dist[v] = d + x
                heapq.heappush(heap, (d + x, v))
    return np.asarray(dist, dtype=np.int64)


@functools.lru_cache(maxsize=None)
def _arcs(low=1, high=1000):
    """A symmetrized R-MAT graph, one integer weight a generated tuple
    on both stored directions; some vertices have no edge at all."""
    s, d = rmat_edges(SCALE, EF, seed=SEED)[:2]
    w = np.random.default_rng(SEED).integers(
        low, high + 1, size=len(s)).astype(np.int32)
    return (np.concatenate([s, d]).astype(np.uint32),
            np.concatenate([d, s]).astype(np.uint32),
            np.concatenate([w, w]))


@functools.lru_cache(maxsize=None)
def _roots():
    src, _dst, _w = _arcs()
    has_edge = np.flatnonzero(np.bincount(src, minlength=NV))
    return tuple(int(v) for v in np.random.default_rng(SEED).choice(
        has_edge, size=3, replace=False))


@functools.lru_cache(maxsize=None)
def _want(root, low=1, high=1000):
    return dijkstra(NV, *_arcs(low, high), root)


@functools.lru_cache(maxsize=None)
def _laid_out(num_parts, low=1, high=1000):
    """As the road cell's runner lays a graph out: relabelled for pair
    rows.  -> (graph, perm, rank, sharded layout)."""
    g = Graph.from_edges(*_arcs(low, high)[:2], NV,
                         weights=_arcs(low, high)[2])
    g_run, perm, starts = pair_relabel(g, num_parts, pair_threshold=16)
    sg = ShardedGraph.build(g_run, num_parts, starts=starts,
                            pair_threshold=16)
    rank = np.empty(NV, np.int64)
    rank[perm] = np.arange(NV)
    return g_run, perm, rank, sg


@functools.lru_cache(maxsize=None)
def _engine(delta, num_parts, sparse=True, low=1, high=1000):
    g_run, _perm, _rank, sg = _laid_out(num_parts, low, high)
    return sssp.build_engine(g_run, start_vertex=0,
                             num_parts=num_parts, weighted=True,
                             delta=delta, sg=sg, pair_threshold=16,
                             enable_sparse=sparse)


def _search(eng, num_parts, root, low=1, high=1000, **kw):
    """Distances by the FILE's vertex ids, and the call's mark."""
    _g, perm, rank, sg = _laid_out(num_parts, low, high)
    label = np.full(NV, sssp.HOP_INF, dtype=np.int32)
    active = np.zeros(NV, dtype=bool)
    label[rank[root]], active[rank[root]] = 0, True
    label, _a, _it = eng.converge(
        *eng.place(sg.to_padded(label), sg.to_padded(active)), **kw)
    got = np.empty(NV, np.int32)
    got[perm] = eng.unpad(label)
    return got, _last_mark()


def _last_mark():
    return [r for r in telemetry.spans()
            if r["name"] == "push.converge"][-1]["counts"]


def test_distance_type_follows_the_weights():
    i32, f32 = np.dtype(np.int32), np.dtype(np.float32)
    assert sssp.distance_dtype(np.array([1, 2], np.int32)) == i32
    assert sssp.distance_dtype(np.array([1, 2], np.int64)) == i32
    assert sssp.distance_dtype(np.array([1, 2], np.uint8)) == i32
    assert sssp.distance_dtype(np.array([1, 2], np.float32)) == f32
    assert sssp.distance_dtype(np.array([1, 2], np.float64)) == f32
    assert sssp.distance_dtype(np.zeros(0, np.int32)) == i32
    prog = sssp.make_program(0, True, np.int32)
    assert np.asarray(prog.identity).dtype == i32
    assert int(prog.identity) == INF
    # the float program is what it was, and the default
    for prog in (sssp.make_program(0, True),
                 sssp.make_program(0, True, np.float32),
                 sssp.make_batched_program([0, 1], True)):
        ident = np.asarray(prog.identity)
        assert ident.dtype == f32 and np.isposinf(ident)
    assert np.asarray(sssp.make_program(0).identity).dtype == i32
    assert sssp.INT_WEIGHT_MAX == 1 << 24
    assert sssp.INT_DIST_MAX == INF - 2 and sssp.INT_DIST_OVER == INF - 1


@pytest.mark.parametrize("root", range(3))
@pytest.mark.parametrize("num_parts", [1, 2])
@pytest.mark.parametrize("delta", DELTAS)
def test_int32_distances_equal_dijkstra(delta, num_parts, root):
    root = _roots()[root]
    eng = _engine(delta, num_parts)
    got, mark = _search(eng, num_parts, root)
    want = _want(root)
    assert got.dtype == np.int32
    assert np.array_equal(got, want)
    assert (want == INF).any() and (want != INF).sum() > NV // 2
    assert np.array_equal(sssp.unreachable(got), want == INF)
    if delta is not None:
        assert isinstance(eng.delta, int) and eng.delta > 0
        assert mark["front_edges"] >= mark["front_vertices"] > 0
        assert mark["iters"] > 0
    else:
        assert mark["front_vertices"] == mark["advances"] == 0


@pytest.mark.parametrize("delta", [None, "auto", 500])
def test_int32_distances_without_the_sparse_view(delta):
    root = _roots()[0]
    got, _mark = _search(_engine(delta, 1, sparse=False), 1, root)
    assert np.array_equal(got, _want(root))


def test_front_vertices_are_the_fronts_of_the_relax_trips():
    """The mark's ``front_vertices`` is the sum of the bucket fronts
    the relax trips entered with (``converge_stats``' per-iteration
    frontier of a delta engine), and narrower buckets hold less."""
    _g, _perm, rank, sg = _laid_out(1)
    r = int(rank[_roots()[1]])
    per_trip = {}
    for delta in (7, 500, "auto"):
        eng = _engine(delta, 1)
        label = np.full(NV, sssp.HOP_INF, dtype=np.int32)
        active = np.zeros(NV, dtype=bool)
        label[r], active[r] = 0, True
        out = eng.converge_stats(*eng.place(sg.to_padded(label),
                                            sg.to_padded(active)))
        mark = _last_mark()
        n = int(out[2])
        sizes = np.asarray(out[3])[:n].astype(np.int64)
        assert n == mark["iters"] and n < eng.stats_cap
        assert mark["front_vertices"] == int(sizes.sum())
        per_trip[delta] = mark["front_vertices"] / n
    assert per_trip[7] < per_trip[500] < per_trip["auto"]


LOW, HIGH = 1 << 22, 1 << 24


@pytest.mark.parametrize("delta", [None, "auto", 1 << 23])
def test_past_two_to_24_float32_rounds_and_int32_is_exact(delta):
    """Weights of 2^22 up to the largest the type takes, 2^24: every
    distance of two hops or more passes 2^24 = 16,777,216, where
    float32 holds even numbers only.  The float32
    program (the same weights as float32) differs from Dijkstra; the
    int32 one is exact."""
    root = _roots()[0]
    want = _want(root, LOW, HIGH)
    far = want[want != INF]
    assert far.max() > 1 << 24
    got, _mark = _search(_engine(delta, 1, True, LOW, HIGH), 1, root,
                         LOW, HIGH)
    assert np.array_equal(got, want)
    g_run, perm, rank, sg = _laid_out(1, LOW, HIGH)
    as_float = Graph.from_edges(*g_run.edge_arrays(), NV,
                                weights=np.asarray(
                                    g_run.weights, np.float32))
    dist, _iters = sssp.run(as_float, start_vertex=int(rank[root]),
                            weighted=True, delta=delta)
    assert dist.dtype == np.float32
    rounded = np.empty(NV, np.float64)
    rounded[perm] = dist
    reached = want != INF
    assert np.array_equal(np.isfinite(rounded), reached)
    wrong = int(np.count_nonzero(rounded[reached] != want[reached]))
    assert wrong > 10
    # and only past 2^24: below it float32 sums of integers are exact
    assert np.array_equal(rounded[reached & (want < 1 << 24)],
                          want[reached & (want < 1 << 24)])
    # the device check adds in int32 on int32 distances: in float32 it
    # would see violations that are roundings
    assert device_check.check_sssp_device(
        sg, sg.to_padded(got[perm]), weighted=True).ok


def _chain(weights):
    n = len(weights) + 1
    return Graph.from_edges(np.arange(n - 1, dtype=np.uint32),
                            np.arange(1, n, dtype=np.uint32), n,
                            weights=np.asarray(weights))


def test_weights_past_the_stated_limit_are_refused_by_name():
    top = sssp.INT_WEIGHT_MAX
    ok = _chain(np.array([top, 1, top], np.int32))
    dist, _iters = sssp.run(ok, weighted=True, delta="auto")
    assert dist.tolist() == [0, top, top + 1, 2 * top + 1]
    for bad in ([top + 1, 1], [3, -1], [np.iinfo(np.int32).max, 2]):
        g = _chain(np.array(bad, np.int32))
        with pytest.raises(sssp.WeightRangeError, match="2\\^24"):
            sssp.build_engine(g, weighted=True)
        with pytest.raises(sssp.WeightRangeError):
            sssp.build_engine(g, weighted=True, sources=[0, 1])
    # float weights have no such limit, and hops read no weight
    sssp.build_engine(_chain(np.array([3e9, 1.0], np.float32)),
                      weighted=True)
    sssp.build_engine(_chain(np.array([top + 1, 1], np.int32)))


@pytest.mark.parametrize("delta", [None, "auto"])
def test_distances_past_the_stated_limit_are_refused_by_name(
        delta, tmp_path, capsys):
    """63 arcs of 2^24 and one of 2^24 - 3 sum to INT_DIST_MAX
    exactly: held.  One arc more and the sum passes it: the answer
    holds the marker, no label wraps, and ``run`` and the CLI refuse."""
    top = sssp.INT_WEIGHT_MAX
    held = [top] * 63 + [top - 3]
    assert sum(held) == sssp.INT_DIST_MAX
    dist, _iters = sssp.run(_chain(np.array(held, np.int32)),
                            weighted=True, delta=delta)
    assert dist[-1] == sssp.INT_DIST_MAX
    assert dist.tolist() == np.cumsum([0] + held).tolist()
    over = _chain(np.array(held + [1, 5, top], np.int32))
    eng = sssp.build_engine(over, weighted=True, delta=delta)
    label, _a, _it = eng.converge(*eng.init_state())
    got = eng.unpad(label)
    assert got[:65].tolist() == np.cumsum([0] + held).tolist()
    assert (got[65:] == sssp.INT_DIST_OVER).all()       # never wraps
    with pytest.raises(sssp.DistanceRangeError, match="3 int32"):
        sssp.ensure_in_range(got)
    with pytest.raises(sssp.DistanceRangeError):
        sssp.run(over, weighted=True, delta=delta)
    path = str(tmp_path / "over.lux")
    luxfmt.write_lux(path, over.row_ptrs, over.col_idx,
                     weights=over.weights, degrees=over.out_degrees)
    flags = [] if delta is None else ["-delta", "auto"]
    assert cli.main(["sssp", "-file", path, "-weighted", *flags]) == 2
    assert "passed 1073741821" in capsys.readouterr().err
    # hop counts and float distances pass through
    assert sssp.ensure_in_range(np.array([INF - 1], np.int64))[0]
    assert sssp.ensure_in_range(np.array([np.inf], np.float32))[0]


def test_auto_is_the_width_it_was_on_both_measured_shapes():
    """``default_delta`` on the two shapes PERF.md's sweeps chose it
    on: Graph500 kernel 3's float32 uniform [0, 1) (the largest
    weight, the same float) and ``bench.py``'s integer 1..5 (5, now a
    whole number so that it is a width on int32 labels too)."""
    from benchmarks.reference import edge_weights
    w = edge_weights.tuple_weights(1 << 14, 1)
    g = _chain(w[: (1 << 14)])
    got = sssp.default_delta(g)
    assert isinstance(got, float) and got == float(w.max())
    assert np.float32(got) == w.max()                 # bit for bit
    ints = (np.arange(1 << 10) % 5 + 1).astype(np.int32)
    got = sssp.default_delta(_chain(ints))
    assert isinstance(got, int) and got == 5
    eng = sssp.build_engine(_chain(ints), weighted=True, delta="auto")
    assert eng.delta == 5
    assert sssp.default_delta(_chain(np.zeros(3, np.int32))) == 1
    assert sssp.default_delta(_chain(np.zeros(3, np.float32))) == 1.0


LONG = telemetry.DEFAULT_STATS_CAP + 200


@pytest.mark.parametrize("delta", [None, "auto", 40])
def test_a_search_of_more_trips_than_the_stats_cap(delta):
    """A chain of more vertices than ``DEFAULT_STATS_CAP``: one
    ``converge`` call of thousands of trips counts them all, and
    ``converge_stats`` keeps the first ``stats_cap`` rows and drops
    the rest without touching the answer."""
    w = (np.arange(LONG - 1) % 9 + 1).astype(np.int32)
    g = _chain(w)
    want = np.concatenate([[0], np.cumsum(w)])
    eng = sssp.build_engine(g, weighted=True, delta=delta)
    label, _a, it = eng.converge(*eng.init_state())
    mark = _last_mark()
    assert np.array_equal(eng.unpad(label), want)
    # a vertex a trip; the last vertex has no edge to relax
    assert int(it) == mark["iters"] == LONG
    assert mark["sparse_iters"] == mark["low_rung_iters"] == LONG
    assert mark["queue_items"] == mark["budget_edges"] + 1 == LONG
    if delta is not None:
        assert mark["front_vertices"] == LONG
        assert mark["front_edges"] == LONG - 1
        assert mark["advances"] > (LONG // 40 if delta == 40 else 400)
    out = eng.converge_stats(*eng.init_state())
    assert np.array_equal(eng.unpad(out[0]), want)
    assert int(out[2]) == LONG > eng.stats_cap
    sizes, edges = np.asarray(out[3]), np.asarray(out[4])
    assert sizes.shape == edges.shape == (eng.stats_cap,)
    assert (sizes == 1).all()
    assert (edges == 1).all()
    stats = telemetry.IterStats()
    stats.extend_push(out[3], out[4], int(out[2]), out[5], out[6])
    assert len(stats) == eng.stats_cap and stats.truncated


def test_a_long_search_in_segments_and_replayed():
    """The same chain through the paths the CLI drives: duration-
    budgeted segments (``-seg-budget``) and the verbose replay."""
    w = (np.arange(LONG - 1) % 9 + 1).astype(np.int32)
    g = _chain(w)
    want = np.concatenate([[0], np.cumsum(w)])
    eng = sssp.build_engine(g, weighted=True, delta="auto")
    dist, iters = eng.run(seg_budget=0.05)
    assert np.array_equal(dist, want) and iters == LONG
    dist, iters = eng.run(verbose=True)
    assert np.array_equal(dist, want) and iters == LONG


@pytest.mark.parametrize("num_parts", [1, 2])
def test_batched_int32_columns_equal_the_single_searches(num_parts):
    """The query-batched program comes with the type: each column is
    the single-source answer."""
    g = Graph.from_edges(*_arcs()[:2], NV, weights=_arcs()[2])
    eng = sssp.build_engine(g, num_parts=num_parts, weighted=True,
                            sources=list(_roots()))
    label, _a, _it = eng.converge(*eng.init_state())
    got = eng.unpad(label)
    assert got.dtype == np.int32 and got.shape == (NV, 3)
    for q, root in enumerate(_roots()):
        assert np.array_equal(got[:, q], _want(root))
    want = sssp.reference_sssp_batched(g, _roots(), weighted=True)
    assert want.dtype == np.int64 and np.array_equal(got, want)


def test_oracles_follow_the_weights_type():
    g = Graph.from_edges(*_arcs()[:2], NV, weights=_arcs()[2])
    root = _roots()[2]
    want = sssp.reference_sssp(g, root, weighted=True)
    assert want.dtype == np.int64 and np.array_equal(want, _want(root))
    as_float = Graph.from_edges(*_arcs()[:2], NV,
                                weights=_arcs()[2].astype(np.float32))
    f = sssp.reference_sssp(as_float, root, weighted=True)
    assert f.dtype == np.float64
    assert np.array_equal(np.isinf(f), want == INF)
    assert np.array_equal(f[np.isfinite(f)], want[want != INF])
