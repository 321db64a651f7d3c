"""Weighted SSSP with REAL-valued weights (Graph500 kernel 3's
float32 uniform [0, 1)) under every schedule the push engine offers.

The integer-weighted tests compare float32 sums of small integers,
which are exact whatever the order; here every addition rounds.  The
contract is still exact: ``fl32(a + w)`` is monotone in ``a``, so the
fixed point of the monotone min is unique whatever the schedule, and
the engine must reach the plain reference's
(``benchmarks/reference/sssp.py``) bit for bit.

R-MAT scale 10 x 16, symmetrized with one weight a generated tuple
(both stored directions carry it), 4 roots of non-zero degree.
"""

import functools
import types

import numpy as np
import pytest

from benchmarks.reference import edge_weights
from benchmarks.reference import sssp as ref
from lux_tpu import cli, telemetry
from lux_tpu import format as luxfmt
from lux_tpu.apps import sssp
from lux_tpu.convert import rmat_edges
from lux_tpu.engine.push import PushEngine
from lux_tpu.graph import Graph, ShardedGraph, pair_relabel
from lux_tpu.parallel.mesh import make_mesh

SCALE, EF, SEED = 10, 16, 5
NV = 1 << SCALE
DELTAS = (None, 0.1, "auto")
SCHEDULES = [(d, s) for d in DELTAS for s in (True, False)]


@functools.lru_cache(maxsize=None)
def _arcs():
    """(src, dst, w) as stored, and the reference's destination-sorted
    (offsets, src, w)."""
    s, d = rmat_edges(SCALE, EF, seed=SEED)[:2]
    w = edge_weights.tuple_weights(len(s), SEED)
    src, dst, w = edge_weights.both_directions(
        s.astype(np.uint32), d.astype(np.uint32), w)
    return (src, dst, w), edge_weights.by_destination(src, dst, w, NV)


@functools.lru_cache(maxsize=None)
def _roots():
    (src, _dst, _w), _ = _arcs()
    has_edge = np.flatnonzero(np.bincount(src, minlength=NV))
    return tuple(int(v) for v in np.random.default_rng(SEED).choice(
        has_edge, size=4, replace=False))


@functools.lru_cache(maxsize=None)
def _want(root: int):
    _, (offsets, src, w) = _arcs()
    return ref.fixed_point_f32(offsets, src, w, root)[0]


@functools.lru_cache(maxsize=None)
def _laid_out(weights_as=None, num_parts=1):
    """(graph as the engine runs it, rank[file id] = engine id, sharded
    layout), relabelled for pair rows as the cell's runner does."""
    (src, dst, w), _ = _arcs()
    if weights_as is not None:
        w = weights_as(w)
    g = Graph.from_edges(src, dst, NV, weights=w)
    g_run, perm, starts = pair_relabel(g, num_parts, pair_threshold=16)
    sg = ShardedGraph.build(g_run, num_parts, starts=starts,
                            pair_threshold=16)
    rank = np.empty(NV, np.int64)
    rank[perm] = np.arange(NV)
    return g_run, perm, rank, sg


def _engine(delta, sparse, weights_as=None):
    g_run, _perm, _rank, sg = _laid_out(weights_as)
    return sssp.build_engine(g_run, start_vertex=0, weighted=True,
                             delta=delta, sg=sg, pair_threshold=16,
                             enable_sparse=sparse)


def _start(eng, sg, r):
    label = np.full(NV, np.inf, dtype=np.float32)
    active = np.zeros(NV, dtype=bool)
    label[r], active[r] = 0, True
    return eng.place(sg.to_padded(label), sg.to_padded(active))


def _in_file_ids(eng, perm, label):
    """The engine's padded labels as distances by the FILE's ids."""
    got = np.empty(NV, np.float32)
    got[perm] = eng.unpad(label)
    return got


@functools.lru_cache(maxsize=None)
def _answers(delta, sparse, weights_as=None):
    """root -> distances in the FILE's vertex ids, one engine and one
    executable for the four roots."""
    _g, perm, rank, sg = _laid_out(weights_as)
    eng = _engine(delta, sparse, weights_as)
    out = {}
    for root in _roots():
        label, _active, _it = eng.converge(
            *_start(eng, sg, int(rank[root])))
        out[root] = _in_file_ids(eng, perm, label)
    return out


def test_auto_resolves_to_a_finite_width_the_largest_weight():
    (_src, _dst, w), _ = _arcs()
    eng = _engine("auto", True)
    assert eng.delta == float(w.max()) and 0 < eng.delta < np.inf
    assert sssp.default_delta(Graph.from_edges(
        [0, 1], [1, 0], 2, weights=np.zeros(2, np.float32))) == 1.0


@pytest.mark.parametrize("root", range(4))
@pytest.mark.parametrize("delta,sparse", SCHEDULES)
def test_engine_reaches_the_float32_fixed_point_bit_for_bit(
        delta, sparse, root):
    root = _roots()[root]
    assert ref.mismatched(_answers(delta, sparse)[root],
                          _want(root)) == 0


@pytest.mark.parametrize("root", range(4))
@pytest.mark.parametrize("delta,sparse", SCHEDULES)
def test_answer_is_the_shortest_path_to_rounding(delta, sparse, root):
    """Within 1e-6 relative of a float64 Dijkstra: a path of a dozen
    or two float32 additions, each off by at most 6e-8."""
    root = _roots()[root]
    _is_shortest_to_rounding(_answers(delta, sparse)[root], root)


def _is_shortest_to_rounding(got, root):
    true = _dijkstra(root)
    got = got.astype(np.float64)
    assert np.array_equal(np.isfinite(got), np.isfinite(true))
    reached = np.isfinite(true) & (true > 0)
    assert reached.sum() > NV // 2
    gap = np.abs(got[reached] - true[reached]) / true[reached]
    assert gap.max() <= 1e-6
    assert got[root] == 0


@functools.lru_cache(maxsize=None)
def _dijkstra(root: int):
    _, (offsets, src, w) = _arcs()
    return ref.dijkstra_f64(offsets, src, w, root)


@pytest.mark.parametrize("root", range(4))
@pytest.mark.parametrize("delta,sparse", SCHEDULES)
def test_answer_violates_no_edge(delta, sparse, root):
    root = _roots()[root]
    (src, dst, w), _ = _arcs()
    got = _answers(delta, sparse)[root]
    assert ref.edges_violated(got, src, dst, w) == 0
    assert ref.roots_nonzero(got, root) == 0


@pytest.mark.parametrize("root", range(4))
def test_control_bfloat16_weights_fail_the_exact_comparison(root):
    """The limit 0 separates sound from unsound: an engine that sees
    the weights rounded to bfloat16 misses the fixed point on
    hundreds of vertices and breaks the edge rule."""
    root = _roots()[root]
    (src, dst, w), _ = _arcs()
    got = _answers("auto", True, ref.to_bfloat16)[root]
    assert ref.mismatched(got, _want(root)) > NV // 4
    assert ref.edges_violated(got, src, dst, w) > 0


def _replay(eng, sg, r):
    """The bucket schedule on the host, trip by trip, each relax trip
    one ``converge`` of the engine capped at ONE iteration on the
    bucket's front (every front label lies under ``min + delta``, so
    that call's own first bucket is the whole front): -> (relax trips,
    advance trips, out-edges of the fronts the relax trips entered
    with, edges those trips relaxed: a sparse trip's are what its
    budget stage expanded, by that call's own mark)."""
    delta = np.float32(eng.delta)
    deg = np.asarray(sg.deg_padded).astype(np.int64)
    label, active = (np.asarray(x) for x in _start(eng, sg, r))
    bound = np.float32(label[active].min() + delta)
    relaxes = advances = offered = relaxed = 0
    while active.any():
        front = active & (label < bound)
        if front.any():
            new_label, new_active, it = eng.converge(
                *eng.place(label.copy(), front), max_iters=1)
            trip = _last_mark()
            assert int(it) == 1 and trip["advances"] == 0
            edges = int(deg[front].sum())
            did = trip["budget_edges"] if trip["sparse_iters"] \
                else edges
            assert trip["front_edges"] == did <= edges
            offered += edges
            relaxed += did
            label = np.asarray(new_label)
            active = (active & ~front) | np.asarray(new_active)
            relaxes += 1
        else:
            low = np.float32(label[active].min())
            bound = max(np.float32(low + delta),
                        np.nextafter(low, np.float32(np.inf)))
            advances += 1
    return relaxes, advances, offered, relaxed


def _last_mark():
    return [r for r in telemetry.spans()
            if r["name"] == "push.converge"][-1]["counts"]


@pytest.mark.parametrize("root", range(2))
@pytest.mark.parametrize("sparse", [True, False])
@pytest.mark.parametrize("delta", [0.1, "auto"])
def test_mark_counts_the_bucket_loops_trips(delta, sparse, root):
    """``iters`` + ``advances`` = the loop's trips, ``front_edges`` =
    the edges its relax trips relaxed, ``graph_edges`` = the stored
    edges.  ``converge_stats``' per-iteration edges are the fronts'
    out-edges, OFFERED: the same number on an engine without the
    ladder, more where a sparse trip's front overflowed the edge
    budget and only a prefix was relaxed."""
    _g, _perm, rank, sg = _laid_out()
    r = int(rank[_roots()[root]])
    eng = _engine(delta, sparse)
    relaxes, advances, offered, relaxed = _replay(eng, sg, r)
    assert advances > 0 and relaxes > 0
    _l, _a, it = eng.converge(*_start(eng, sg, r))
    got = _last_mark()
    assert int(it) == got["iters"] == relaxes
    assert got["advances"] == advances
    assert got["iters"] + got["advances"] == relaxes + advances
    assert got["front_edges"] == relaxed
    assert got["graph_edges"] == sg.ne
    out = eng.converge_stats(*_start(eng, sg, r))
    per_iter = np.asarray(out[4]).astype(np.int64)
    assert int(per_iter.sum()) == offered
    assert _last_mark()["front_edges"] == relaxed
    if not sparse:
        assert relaxed == offered


def _rule_off(monkeypatch):
    """The bucket loop's choice as it was before it saw the front's
    out-edges: the compare never holds, the vertex count decides."""
    monkeypatch.setattr(PushEngine, "_spills",
                        lambda self, edges: edges != edges)


def _offered(eng, sg, r) -> int:
    """Out-edges of the fronts a search's relax trips entered with."""
    out = eng.converge_stats(*_start(eng, sg, r))
    return int(np.asarray(out[4]).astype(np.int64).sum())


def test_no_relax_trip_of_the_bucket_loop_truncates(monkeypatch):
    """Scale 10's top edge budget is 2,209 edges and a hub in a sparse
    front overflows it.  Such a front runs dense
    (``edge_dense_iters``), so every relax trip relaxes its front
    whole; with the out-edge test off the trip relaxes a prefix, the
    rest stays active and is offered again: more trips, more edges
    offered."""
    _g, _perm, rank, sg = _laid_out()
    starts = [int(rank[v]) for v in _roots()]

    def searches(eng):
        """-> [(edges offered, the search's mark)] a root."""
        return [(_offered(eng, sg, r), _last_mark()) for r in starts]

    eng = _engine("auto", True)
    for r in starts:
        _relaxes, _advances, offered, relaxed = _replay(eng, sg, r)
        assert relaxed == offered
    new = searches(eng)
    _rule_off(monkeypatch)
    old = searches(_engine("auto", True))
    assert all(m["edge_dense_iters"] > 0 for _e, m in new)
    assert all(m["edge_dense_iters"] == 0 for _e, m in old)
    assert all(a["iters"] <= b["iters"]
               for (_e, a), (_f, b) in zip(new, old))
    assert sum(m["iters"] for _e, m in new) \
        < sum(m["iters"] for _e, m in old)
    assert sum(e for e, _m in new) < sum(e for e, _m in old)


@pytest.mark.parametrize("root", range(3))
@pytest.mark.parametrize("delta", [0.1, "auto", 2.0])
def test_relax_trips_add_up_by_kind_and_the_answer_stands(delta, root):
    """``iters`` = ``sparse_iters`` + the trips whose front passed the
    queue's limit by vertex count + ``edge_dense_iters`` (those that
    fit it and ran dense for their out-edges), counted against the
    per-iteration record of ``converge_stats``; and the choice moves
    no distance: plain frontiers' answer, the reference's fixed point
    bit for bit, the float64 Dijkstra to rounding."""
    _g, perm, rank, sg = _laid_out()
    v = _roots()[root]
    eng = _engine(delta, True)
    label, _a, it, sizes, edges = eng.converge_stats(
        *_start(eng, sg, int(rank[v])))[:5]
    got = _last_mark()
    n = int(it)
    sizes = np.asarray(sizes)[:n].astype(np.int64)
    edges = np.asarray(edges)[:n].astype(np.int64)
    fits = sizes <= eng._sparse_mode()[1]
    spills = edges > eng.budget_rungs[-1]
    assert got["iters"] == n
    assert got["sparse_iters"] == int((fits & ~spills).sum())
    assert got["edge_dense_iters"] == int((fits & spills).sum())
    assert n == (got["sparse_iters"] + int((~fits).sum())
                 + got["edge_dense_iters"])
    dist = _in_file_ids(eng, perm, label)
    assert ref.mismatched(dist, _answers(None, True)[v]) == 0
    assert ref.mismatched(dist, _want(v)) == 0
    _is_shortest_to_rounding(dist, v)


HUBS, LEAVES, BUDGET = 12, 60, 128


def _hubs_behind_the_bound():
    """The fault ISSUE 44 names, constructed: vertex 0's ONE edge
    weighs 0.99 of the bucket width (1.0) and leads to a hub whose
    neighbours are hubs a whisker further, so the first bound falls
    just behind them all: a front of 11 vertices, under the queue's
    limit (673 // 16), whose 660 out-edges (0.5 each, so their ends
    lie beyond the bound) pass a top budget rung of 128 five times
    over.  -> (graph, the reference's destination-sorted arcs)."""
    hub = 1 + np.arange(HUBS)
    nv = 1 + HUBS + (HUBS - 1) * LEAVES
    src = np.concatenate([[0], np.full(HUBS - 1, hub[0]),
                          np.repeat(hub[1:], LEAVES)])
    dst = np.concatenate([hub, np.arange(1 + HUBS, nv)])
    w = np.concatenate([[0.99], np.full(HUBS - 1, 0.001),
                        np.full((HUBS - 1) * LEAVES, 0.5)]
                       ).astype(np.float32)
    return (Graph.from_edges(src, dst, nv, weights=w),
            edge_weights.by_destination(
                src.astype(np.uint32), dst.astype(np.uint32), w, nv))


def test_hubs_just_behind_the_bound_run_one_dense_trip(monkeypatch):
    """With the out-edge test the sliver of hubs is relaxed in ONE
    dense trip; with it off, a 128-edge prefix a trip."""
    g, (offsets, src, w) = _hubs_behind_the_bound()
    sg = ShardedGraph.build(g, 1)

    def search():
        eng = PushEngine(sg, sssp.make_program(0, True), delta=1.0,
                         edge_budget=BUDGET)
        assert eng.budget_rungs[-1] == BUDGET
        label, _a, it = eng.converge(*eng.init_state())
        return eng.unpad(label), int(it), _last_mark()

    new, new_iters, new_mark = search()
    _rule_off(monkeypatch)
    old, old_iters, old_mark = search()
    assert new_mark["edge_dense_iters"] == 1
    assert old_mark["edge_dense_iters"] == 0
    assert old_iters - new_iters >= (HUBS - 1) * LEAVES // BUDGET - 1
    assert old_mark["sparse_iters"] > new_mark["sparse_iters"]
    want = ref.fixed_point_f32(offsets, src, w, 0)[0]
    assert ref.mismatched(new, want) == ref.mismatched(old, want) == 0
    assert np.isfinite(want).all()


@pytest.mark.parametrize("delta", [0.1, "auto"])
def test_bucket_loop_on_a_mesh_of_four_gives_the_one_part_answer(delta):
    """np = 4 on the CPU's virtual devices: the choice reads the
    GLOBAL out-edge total (an estimate that errs towards dense: a
    part expands only the edges that land in it), and the distances
    are the one-part engine's bit for bit."""
    g_run, perm, rank, sg = _laid_out(num_parts=4)
    eng = sssp.build_engine(g_run, start_vertex=0, num_parts=4,
                            mesh=make_mesh(4), weighted=True,
                            delta=delta, sg=sg, pair_threshold=16)
    spilled = 0
    for v in _roots():
        label, _a, _it = eng.converge(*_start(eng, sg, int(rank[v])))
        assert ref.mismatched(_in_file_ids(eng, perm, label),
                              _answers(delta, True)[v]) == 0
        spilled += _last_mark()["edge_dense_iters"]
    assert spilled > 0


def test_mark_of_an_engine_without_delta_counts_zeros():
    _g, _perm, rank, sg = _laid_out()
    eng = _engine(None, True)
    eng.converge(*_start(eng, sg, int(rank[_roots()[0]])))
    got = _last_mark()
    assert (got["advances"], got["front_edges"],
            got["graph_edges"]) == (0, 0, 0)
    assert got["iters"] > 0


@pytest.mark.parametrize("weight_type", ["float32", "int32"])
def test_cli_sssp_weighted_round_trips_the_files_weight_type(
        weight_type, tmp_path, capsys):
    """``cli sssp -weighted -weight-type float32`` reads a file of
    float32 weights as what they are: the run passes ``-check`` and
    the graph the entry point loaded gives the reference's distances;
    a file of int32 weights loads as it always did (no flag) and
    gives the same distances as int32 (sums of small integers are
    exact in float32 too), ``HOP_INF`` where float32 says ``+inf``."""
    (src, dst, w), (offsets, by_src, by_w) = _arcs()
    root = _roots()[0]
    if weight_type == "int32":
        w = (1 + np.floor(w * 5)).astype(np.int32)
        by_w = (1 + np.floor(by_w * 5)).astype(np.float32)
    g = Graph.from_edges(src, dst, NV, weights=w)
    path = str(tmp_path / "g.lux")
    luxfmt.write_lux(path, g.row_ptrs, g.col_idx, weights=g.weights,
                     degrees=g.out_degrees)
    flags = ["-weight-type", "float32"] if weight_type == "float32" \
        else []
    rc = cli.main(["sssp", "-file", path, "-weighted", *flags,
                   "-start", str(root), "-delta", "auto", "-pair",
                   "16", "-check"])
    assert rc == 0 and "[PASS]" in capsys.readouterr().out

    def loaded(**kw):           # the entry point's own loader
        return cli._load(types.SimpleNamespace(
            file=path, verbose=False, **kw), True)
    want = ref.fixed_point_f32(offsets, by_src, by_w, root)[0]
    got, _iters = sssp.run(
        loaded(**({"weight_type": "float32"} if flags else {})),
        start_vertex=root, weighted=True, delta="auto")
    assert got.dtype == np.dtype(weight_type)
    got = np.where(sssp.unreachable(got), np.inf, got)
    assert ref.mismatched(np.asarray(got, np.float32), want) == 0
    if weight_type == "float32":
        # read as the default int32 the same file's bits are other
        # numbers, far past what int32 distances take: refused by name
        with pytest.raises(sssp.WeightRangeError):
            sssp.run(loaded(), start_vertex=root, weighted=True)
        assert cli.main(["sssp", "-file", path, "-weighted",
                         "-start", str(root)]) == 2
