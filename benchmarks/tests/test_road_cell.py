"""``ssspw.road.delta``'s own pieces: the road generator's promises at
the rehearsal size, Dijkstra against the certificate of exactness, the
three controls against the configuration's limits, the new metrics'
files and readers, and rehearsals of the cell, sound and broken."""

import types

import numpy as np
import pytest

from benchmarks import control_road, harness, roadnet_cache
from benchmarks.reference import dijkstra as ref
from benchmarks.reference import roadnet

CELL = "ssspw.road.delta"
CONFIG = harness.load_json(
    harness.HERE + "/configs/dimacs-road-sssp.json")
TRAFFIC = harness.load_json(harness.HERE + "/traffic/sssp-roots-4.json")
SMALL = {**CONFIG, **CONFIG["rehearsal"]}
UNREACHED = CONFIG["unreached"]


def _generate(seed=1, **size):
    c = {**SMALL, **size}
    return roadnet.road_edges(c["vertices"], c["arcs"], seed,
                              **c["shape"], extent_km=c["extent_km"])


@pytest.fixture(scope="module")
def small():
    """The rehearsal network: (offsets, src, w) by destination, dst,
    the generator's info."""
    u, v, w, info = _generate()
    nv = SMALL["vertices"]
    offsets, by_src, by_w = roadnet.by_destination(
        *roadnet.both_directions(u, v, w), nv)
    dst = np.repeat(np.arange(nv, dtype=np.int32), np.diff(offsets))
    return offsets, by_src, by_w, dst, roadnet.describe(offsets, info)


def test_the_configuration_states_the_sources_shapes():
    c = CONFIG
    assert c["runner"] == "batch_sssp_road" and c["app"] == "sssp"
    assert (c["weighted"], c["weight_type"], c["distance_type"],
            c["symmetrized"]) == (True, "int32", "int32", True)
    assert c["unreached"] == 2 ** 30 - 1
    assert c["graph_seed"] == 1 and c["num_parts"] == c["mesh"] == 1
    assert c["reduced"] == ["scale", "roots"]
    assert c["reduced_from"] == {"vertices": 23947347,
                                 "arcs": 58333344, "roots": 64}
    # a published DIMACS instance, the smallest of the issue's three
    # on which distances pass 2^24 and float32 labels fail the check
    assert (c["instance"], c["vertices"], c["arcs"]) == (
        "USA-road-d.CAL", 1890815, 4657742)
    assert c["roots"] == TRAFFIC["roots"] == TRAFFIC["check_searches"] == 4
    # what kron21-sssp runs: the two cells differ by graph and type
    kron = harness.load_json(harness.HERE + "/configs/kron21-sssp.json")
    assert c["engine"] == kron["engine"]
    assert set(c["engine"]) <= {k.split(".", 1)[1] for k in c["assumed"]
                                if k.startswith("engine.")}
    assert (c["guarantees"]["road_mismatched_dists"],
            c["guarantees"]["road_certificate_violations"]) == (0, 0)
    assert set(c["shape"]) | {"extent_km"} == set(roadnet.SHAPE)
    for key in ("counts", "unit", "degree_histogram", "diameter"):
        assert key in c["assumed"]


def test_generator_gives_the_instances_counts_both_ways(small):
    offsets, src, w, dst, info = small
    nv, arcs = SMALL["vertices"], SMALL["arcs"]
    assert len(offsets) == nv + 1                  # exactly nv vertices
    assert abs(len(src) - arcs) <= 0.01 * arcs     # within 1%
    assert info["vertices"] == nv and info["arcs"] == len(src)
    # every edge stored both ways with one weight: the arcs are their
    # own mirror as a multiset
    a = np.lexsort((w, src, dst))
    b = np.lexsort((w, dst, src))
    assert np.array_equal(src[a], dst[b]) and np.array_equal(
        dst[a], src[b]) and np.array_equal(w[a], w[b])
    assert not np.any(src == dst)                  # no self-loop


def test_generator_is_one_component_of_bounded_degree(small):
    offsets, src, _w, _dst, info = small
    deg = np.diff(offsets)
    assert deg.min() >= 1 and deg.max() <= 8
    share = np.bincount(deg, minlength=9) / len(deg)
    # the histogram assumed for the source: 0.20 / 0.30 / 0.40 / 0.10
    assert 0.15 <= share[1] <= 0.25 and 0.25 <= share[2] <= 0.40
    assert 0.25 <= share[3] <= 0.45 and share[4:].sum() <= 0.20
    assert info["degree_share"][2] == round(float(share[2]), 4)
    assert abs(deg.mean() - SMALL["arcs"] / SMALL["vertices"]) < 0.01
    levels = roadnet.hop_levels(offsets, src, 0)
    assert levels.min() >= 0                       # ONE component


def test_generator_is_deep_from_the_cells_roots(small):
    offsets, src, _w, _dst, _info = small
    nv = SMALL["vertices"]
    roots = np.random.default_rng([SMALL["graph_seed"], 2]).choice(
        np.flatnonzero(np.diff(offsets)), size=4, replace=False)
    for root in roots:
        assert roadnet.hop_levels(offsets, src, int(root)).max() \
            >= np.sqrt(nv)


def test_weights_are_int32_lengths_with_a_heavy_right_tail(small):
    _offsets, _src, w, _dst, info = small
    assert w.dtype == np.int32 and w.min() >= 1
    assert info["weight_min"] == int(w.min())
    # skewed: the mean lies well over the median, and the longest arcs
    # are many means long (91 means at the cell's own size, where
    # there are desert bands to draw: PERF.md section 4)
    assert w.mean() > 1.15 * np.median(w)
    assert w.max() > 4 * w.mean()


def test_ids_are_local(small):
    offsets, src, _w, dst, _info = small
    gap = np.abs(src.astype(np.int64) - dst)
    # a neighbour is a few ids away, as in a file written a county at
    # a time; a random numbering would put it nv / 3 away
    assert np.median(gap) <= 8
    assert gap.mean() < SMALL["vertices"] / 20


def test_same_seed_same_bytes_other_seed_other_graph():
    a, b, c = _generate(1), _generate(1), _generate(2)
    for x, y in zip(a[:3], b[:3]):
        assert x.tobytes() == y.tobytes()
    assert a[3] == b[3]
    assert any(x.tobytes() != y.tobytes() for x, y in zip(a[:3], c[:3]))


@pytest.mark.parametrize("nv,arcs", [(264346, 733846), (1000, 2440)])
def test_generator_hits_other_instances_counts(nv, arcs):
    u, v, w, info = roadnet.road_edges(nv, arcs, 3)
    assert len(u) == len(v) == len(w) == arcs // 2
    assert max(int(u.max()), int(v.max())) == nv - 1
    assert len(np.unique(np.concatenate([u, v]))) == nv
    assert info["sites"] + info["chain_points"] \
        + info["dead_end_stubs"] == nv


def test_cache_entry_is_the_program_file_and_the_references(tmp_path,
                                                            monkeypatch):
    from benchmarks import graphs
    from lux_tpu.graph import Graph
    monkeypatch.setattr(graphs, "GRAPHS", str(tmp_path))
    shape = {**SMALL["shape"], "extent_km": SMALL["extent_km"]}
    paths = roadnet_cache.ensure(SMALL["vertices"], SMALL["arcs"], 1,
                                 shape)
    assert roadnet_cache.ensure(SMALL["vertices"], SMALL["arcs"], 1,
                                shape) == paths
    offsets, src, w = roadnet_cache.load_reference(paths)
    g = Graph.from_file(paths["lux"], weighted=True,
                        weight_dtype=np.int32)
    assert g.nv == SMALL["vertices"] and g.ne == len(src) == offsets[-1]
    assert g.weights.dtype == np.int32 and w.dtype == np.int32
    gs, gd = g.edge_arrays()
    dst = np.repeat(np.arange(g.nv), np.diff(offsets))
    a = np.lexsort((g.weights, gs, gd))
    b = np.lexsort((w, src, dst))
    assert np.array_equal(gs[a], src[b]) and np.array_equal(
        np.asarray(g.weights)[a], w[b])
    assert paths["generated_edges"] == SMALL["arcs"] // 2
    # another shape is another entry
    assert roadnet_cache.entry_dir(4000, 9854, 1, shape) != \
        roadnet_cache.entry_dir(4000, 9854, 1, {**shape, "tile": 8})


def test_dijkstra_on_a_graph_small_enough_to_read():
    #   0 -5-> 1 -2-> 2,  0 -9-> 2,  2 -1-> 0,  3 alone with a loop
    src = np.array([0, 1, 0, 2, 3], np.int32)
    dst = np.array([1, 2, 2, 0, 3], np.int32)
    w = np.array([5, 2, 9, 1, 4], np.int32)
    offsets, by_src, by_w = roadnet.by_destination(src, dst, w, 4)
    by_dst = np.repeat(np.arange(4), np.diff(offsets))
    got = ref.dijkstra(offsets, by_src, by_w, 0, UNREACHED)
    assert got.tolist() == [0, 5, 7, UNREACHED]
    cert = ref.certificate(got, by_src, by_dst, by_w, 0, UNREACHED)
    assert cert == {"root": 0, "infeasible": 0, "unsupported": 0,
                    "violations": 0}
    # each rule on its own
    for wrong, rule in (([1, 5, 7, UNREACHED], "root"),
                        ([0, 5, 8, UNREACHED], "infeasible"),
                        ([0, 4, 6, UNREACHED], "unsupported"),
                        ([0, 5, UNREACHED, UNREACHED], "infeasible"),
                        ([0, 5, 7, 3], "unsupported")):
        cert = ref.certificate(np.array(wrong), by_src, by_dst, by_w,
                               0, UNREACHED)
        assert cert[rule] >= 1 and cert["violations"] >= 1, wrong
    with pytest.raises(ValueError, match="positive"):
        ref.certificate(got, by_src, by_dst, by_w * 0, 0, UNREACHED)


@pytest.mark.parametrize("k", range(3))
def test_dijkstra_passes_the_certificate_and_no_other_answer(small, k):
    offsets, src, w, dst, _info = small
    root = int(np.flatnonzero(np.diff(offsets))[k * 1000])
    want = ref.dijkstra(offsets, src, w, root, UNREACHED)
    assert want.dtype == np.int64 and want[root] == 0
    assert (want != UNREACHED).all()               # one component
    assert ref.certificate(want, src, dst, w, root,
                           UNREACHED)["violations"] == 0
    exact = control_road.sweeps(*ref.by_source(offsets, src, w),
                                root, np.int64, UNREACHED)[0]
    assert ref.mismatched(exact, want) == 0
    rng = np.random.default_rng(k)
    for _ in range(20):                            # any other answer
        other = want.copy()
        v = int(rng.integers(len(want)))
        other[v] += int(rng.choice([-3, -1, 1, 2, 1000]))
        assert ref.certificate(other, src, dst, w, root,
                               UNREACHED)["violations"] >= 1


def test_the_three_controls_fail_the_limits(small):
    offsets, src, w, _dst, _info = small
    roots = [int(np.flatnonzero(np.diff(offsets))[5]), 2000]
    nums = control_road.control_numbers(offsets, src, w, roots, 4,
                                        UNREACHED)
    assert nums["sound"] == [0, 0]
    assert nums["one_more"][0] == 2 and nums["one_more"][1] >= 2
    assert min(nums["one_sweep_short"]) >= 2
    # the rehearsal's ground is set so wide that distances pass 2^24
    # (as at USA-road-d.CAL's size): float32 labels round there
    assert min(nums["largest_distance"]) > 1 << 24
    assert min(nums["float32_labels"]) > 100
    assert control_road.main(["--rehearsal"]) == 0
    assert control_road.main(["--rehearsal", "--seed", "5"]) == 0


def test_a_ground_under_two_to_24_is_no_size_for_the_cell(monkeypatch):
    """On a ground so small that no distance passes 2^24 float32 sums
    of these weights are exact: that control reads 0, the other two
    still fail, and ``control_road.py`` exits 1 (what ``must fail``
    means does not depend on the data)."""
    near = {**SMALL, "extent_km": [50.0, 60.0]}
    u, v, w, _info = roadnet.road_edges(
        near["vertices"], near["arcs"], 1, **near["shape"],
        extent_km=near["extent_km"])
    offsets, src, w = roadnet.by_destination(
        *roadnet.both_directions(u, v, w), near["vertices"])
    nums = control_road.control_numbers(offsets, src, w, [7, 2000], 4,
                                        UNREACHED)
    assert max(nums["largest_distance"]) < 1 << 24
    assert nums["float32_labels"] == [0, 0] == nums["sound"]
    assert min(nums["one_more"]) >= 2
    assert min(nums["one_sweep_short"]) >= 2
    real = harness.cell_of

    def on_a_small_ground(bench, name):
        cell, c, traffic = real(bench, name)
        return cell, {**c, "rehearsal": near}, traffic
    monkeypatch.setattr(harness, "cell_of", on_a_small_ground)
    assert control_road.main(["--rehearsal"]) == 1


def test_a_control_that_passes_is_a_fault(monkeypatch):
    """``control_road.py`` exits 0 only when every control FAILS the
    limits: checks that no longer see a distance off by 1 exit 1."""
    monkeypatch.setattr(ref, "mismatched", lambda got, want: 0)
    monkeypatch.setattr(ref, "certificate",
                        lambda *a: {"violations": 0})
    assert control_road.main(["--rehearsal"]) == 1


@pytest.mark.parametrize("name,spec,unit,better,source", [
    ("engine.trips_per_search",
     {"reader": "program_count_mean", "spans": ["push.converge"],
      "when": "window", "field": ["iters", "advances"]},
     "trips", "lower", "program_counter"),
    ("engine.ms_per_trip",
     {"reader": "seconds_per_count", "counter": "loop_seconds",
      "spans": ["push.converge"], "when": "window",
      "over": ["iters", "advances"]}, "ms", "lower", "host_clock"),
    ("engine.front_vertices_per_trip",
     {"reader": "program_count", "spans": ["push.converge"],
      "when": "window", "field": "front_vertices", "over": "iters"},
     "vertices", "higher", "program_counter")])
def test_the_new_metrics_files_and_entries(name, spec, unit, better,
                                           source):
    bench = harness.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert CELL in entry["workloads"]
    assert "ssspw.kron21.delta" in entry["workloads"]
    assert (entry["moves"], entry["layer"], entry["unit"],
            entry["better"], entry["source"]) == (
        "gteps_per_chip", "engine loop", unit, better, source)
    assert harness.load_json(
        harness.HERE + f"/layer_metrics/{name}.json") == spec


def test_the_new_readers_on_marks_with_and_without_the_counts(
        monkeypatch):
    """Two searches' marks; a parent's marks lack ``front_vertices``
    (0 over the relax trips) and a program without the bucket counts
    still has ``iters``; no ring, no marks or no counts: nothing."""
    from benchmarks.readers import (program_count, program_count_mean,
                                    program_span, seconds_per_count)
    records = [{"id": i, "parent": 0, "name": "push.converge",
                "t0": 5.0 + i, "t1": 5.0 + i,
                "counts": {"iters": 90 + 20 * i, "advances": 10,
                           "front_vertices": 900 + 700 * i}}
               for i in range(2)]
    monkeypatch.setattr(program_span, "ring", lambda: records)
    run = types.SimpleNamespace(t_window=1.0, spans=[], events=[],
                                trace_window_s=None,
                                counters={"loop_seconds": 1.1})
    spec = {n: harness.load_json(
        harness.HERE + f"/layer_metrics/engine.{n}.json")
        for n in ("trips_per_search", "ms_per_trip",
                  "front_vertices_per_trip")}
    assert program_count_mean.read(spec["trips_per_search"], run) \
        == pytest.approx(110.0)
    assert seconds_per_count.read(spec["ms_per_trip"], run) \
        == pytest.approx(5.0)
    assert program_count.read(spec["front_vertices_per_trip"], run) \
        == pytest.approx(12.5)
    for r in records:
        del r["counts"]["front_vertices"], r["counts"]["advances"]
    assert program_count_mean.read(spec["trips_per_search"], run) \
        == pytest.approx(100.0)
    assert program_count.read(spec["front_vertices_per_trip"], run) \
        == 0.0
    for r in records:
        r["counts"].clear()
    assert program_count_mean.read(spec["trips_per_search"], run) is None
    assert seconds_per_count.read(spec["ms_per_trip"], run) is None
    run.counters.clear()
    assert seconds_per_count.read(spec["ms_per_trip"], run) is None
    monkeypatch.setattr(program_span, "ring", lambda: None)
    assert program_count_mean.read(spec["trips_per_search"], run) is None


def test_the_cell_is_declared_and_on_the_lists_the_issue_names():
    """This cell's own facts only."""
    bench = harness.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dimacs-road-sssp", "sssp-roots-4", 1)
    entry = {c["name"]: c for c in bench["configs"]}["dimacs-road-sssp"]
    assert entry["reduced"] == ["scale", "roots"]
    assert len(entry["source"]) <= 200 and "gapbs" in entry["source"]
    lists = {m["name"]: m.get("workloads", ())
             for group in ("end_to_end", "per_layer")
             for m in bench[group]}
    for name in LISTED:
        assert CELL in lists[name], name
    assert CELL not in lists["engine.pull_iter_share"]
    assert CELL not in lists["serve_qps"]


LISTED = (
    "gteps_per_chip", "hbm_bytes_per_edge", "ms_per_iter",
    "scope_ms.sparse", "scope_ms.sparse_expand", "scope_ms.bucket",
    "prep_s.relabel", "prep_s.pair_plan", "prep_s.sparse_view",
    "jit.compiles_in_window", "state_ms.init", "state_ms.fetch",
    "engine.sparse_iter_share", "engine.sparse_low_rung_share",
    "engine.advance_trip_share", "engine.relaxed_edge_ratio",
    "engine.edge_dense_trip_share", "delivery.pair_coverage",
    "prep.store_hit_share", "engine.trips_per_search",
    "engine.ms_per_trip", "engine.front_vertices_per_trip")
# read from the device trace: none on the CPU rehearsal; and the
# preparation store takes no graph as small as the rehearsal's
NOT_IN_A_REHEARSAL = {"hbm_bytes_per_edge", "scope_ms.sparse",
                      "scope_ms.sparse_expand", "scope_ms.bucket",
                      "prep.store_hit_share", "gteps_per_chip"}


def test_rehearsal_is_correct_and_reports_every_listed_metric():
    r = harness.run_cell(CELL, 2**31 + 11, 0.5, True, rehearsal=True)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] % 4 == 0 and r["attempted"] >= 4   # passes
    m = r["metrics"]
    for name in LISTED:
        if name not in NOT_IN_A_REHEARSAL:
            assert name in m, name
    assert m["engine.sparse_iter_share"]["value"] == 100
    assert m["engine.trips_per_search"]["value"] > np.sqrt(
        SMALL["vertices"])
    assert 1 <= m["engine.front_vertices_per_trip"]["value"] < 200
    assert m["engine.ms_per_trip"]["value"] > 0
    assert m["engine.relaxed_edge_ratio"]["value"] >= 1
    assert m["jit.compiles_in_window"]["value"] == 0
    r = harness.run_cell(CELL, 7, 0.5, False, rehearsal=True)
    assert r["correct"] is True
    assert r["metrics"]["gteps_per_chip"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0


def test_the_start_state_is_the_programs_own():
    """``starts_in`` hands ``batch_sssp.search``'s float32 start state
    to the engine as the program's own: a vertex no arc reaches is
    placed as ``program.identity`` exactly (1,073,741,823 is no
    float32 value) and comes back as it."""
    from benchmarks.runners import batch_sssp_road as runner
    from lux_tpu.apps import sssp
    from lux_tpu.graph import Graph, ShardedGraph
    #   0 -5- 1 -2- 2, both ways; 3 has no arc at all
    src = np.array([0, 1, 1, 2], np.uint32)
    dst = np.array([1, 0, 2, 1], np.uint32)
    w = np.array([5, 5, 2, 2], np.int32)
    g = Graph.from_edges(src, dst, 4, weights=w)
    sg = ShardedGraph.build(g, 1)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=1,
                            weighted=True, sg=sg, delta="auto")
    identity = np.asarray(eng.program.identity)
    assert (identity.dtype, int(identity)) == (np.int32, UNREACHED)
    placed = []
    real = eng.place
    eng.place = lambda label, active: placed.append(label) or real(
        label, active)
    st = types.SimpleNamespace(eng=runner.starts_in(eng, CONFIG),
                               sg=sg, nv=4, rank=None)
    _s, _iters, answer = runner.search(None, st, 0)
    (label,) = placed
    # what make_program().init builds, type and bits
    want, _active = eng.program.init(sg)
    assert label.dtype == want.dtype == np.int32
    assert np.array_equal(label, want)
    assert sg.from_padded(label).tolist() == [0] + [UNREACHED] * 3
    assert answer.dtype == np.int32
    assert answer.tolist() == [0, 5, 7, UNREACHED]


def _run(**kw):
    return harness.run_cell(CELL, 2**31 + 9, 0.5, False,
                            rehearsal=True, **kw)


def test_a_search_cut_short_is_not_correct(monkeypatch):
    from benchmarks.runners import batch_sssp
    real = batch_sssp.search
    cut = {}

    def short(run, st, root, max_iters=None):
        if root not in cut:                     # learnt once a root
            _s, iters, full = real(None, st, root)
            # the last relax iterations of a converged search may only
            # find that nothing improves: cut right before the last
            # one that lowers a distance
            k = iters - 1
            while np.array_equal(real(None, st, root, k)[2], full):
                k -= 1
            cut[root] = k
        return real(run, st, root, cut[root])
    # ``window`` is batch_sssp's and calls its module's ``search``
    monkeypatch.setattr(batch_sssp, "search", short)
    r = _run()
    assert r["correct"] is False and r["failed"] == r["attempted"]


def test_an_answer_altered_by_one_is_not_correct(monkeypatch):
    from benchmarks.runners import batch_sssp_road
    real = batch_sssp_road.verify

    def altered(run, st):
        _root, _iters, answer = st.searches[-1]
        v = int(np.flatnonzero(answer > 0)[17])
        answer[v] += 1
        return real(run, st)
    monkeypatch.setattr(batch_sssp_road, "verify", altered)
    r = _run()
    assert r["correct"] is False and r["failed"] >= 1


def test_a_program_that_sums_in_float32_is_refused_at_once(monkeypatch):
    """The parent's program: no ``distance_dtype``, float32 labels
    whatever the weights.  The runner refuses before it generates
    anything."""
    from benchmarks import roadnet_cache as cache
    from lux_tpu.apps import sssp
    monkeypatch.delattr(sssp, "distance_dtype")
    monkeypatch.setattr(cache, "ensure", lambda *a: pytest.fail(
        "generated a graph for a program that cannot run the cell"))
    with pytest.raises(harness.BenchmarkError, match="float32"):
        _run()


def test_float32_labels_on_these_weights_are_not_correct(monkeypatch):
    """The same, past the refusal: a program that gives these weights
    float32 distances does not pass as the configuration's."""
    from lux_tpu.apps import sssp
    monkeypatch.setattr(sssp, "distance_dtype",
                        lambda weights: np.dtype(np.float32))
    with pytest.raises(harness.BenchmarkError, match="float32"):
        _run()
