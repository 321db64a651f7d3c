"""``ssspw.kron21.delta``'s own pieces: the weights' generator, the
weighted cache entry, the float32 reference against a float64
Dijkstra, the control against the configuration's limits, the three
new metrics' files, and rehearsals with the timed path broken
underneath."""

import types

import numpy as np
import pytest

from benchmarks import control_sssp, harness, kron_weighted_cache
from benchmarks.reference import edge_weights, kronecker
from benchmarks.reference import sssp as ref

CELL = "ssspw.kron21.delta"
CONFIG = harness.load_json(harness.HERE + "/configs/kron21-sssp.json")
TRAFFIC = harness.load_json(harness.HERE + "/traffic/sssp-roots.json")


@pytest.fixture(scope="module")
def small():
    """Scale 10 x 16 symmetrized: (offsets, src, w) by destination."""
    src, dst = kronecker.kronecker_edges(10, 16, 7)
    w = edge_weights.tuple_weights(len(src), 7)
    src, dst, w = edge_weights.both_directions(src, dst, w)
    return edge_weights.by_destination(src, dst, w, 1 << 10)


def test_the_configuration_states_the_sources_shapes():
    c = CONFIG
    assert (c["scale"], c["edge_factor"], c["symmetrized"]) == (
        21, 16, True)
    assert c["weighted"] is True and c["weight_type"] == "float32"
    assert c["graph_seed"] == 1 and c["num_parts"] == c["mesh"] == 1
    assert c["reduced"] == ["scale", "roots"]
    assert c["reduced_from"] == {"scale": 26, "roots": 64}
    assert c["roots"] == TRAFFIC["roots"] == 8
    assert TRAFFIC["check_searches"] == 4
    assert c["engine"] == {"pair_threshold": 16, "pair_min_fill": 24,
                           "enable_sparse": True, "delta": "auto"}
    assert set(c["engine"]) <= {k.split(".", 1)[1] for k in c["assumed"]
                                if k.startswith("engine.")}
    assert (c["guarantees"]["sssp_mismatched_dists"],
            c["guarantees"]["sssp_edges_violated"]) == (0, 0)
    # the instance kernel 2 searches
    bfs = harness.load_json(harness.HERE + "/configs/kron21-bfs.json")
    assert all(c[k] == bfs[k] for k in (
        "scale", "edge_factor", "symmetrized", "graph_seed"))


def test_weights_are_seeded_uniform_and_multiples_of_2_to_minus_24():
    w = edge_weights.tuple_weights(200_000, 3)
    assert w.dtype == np.float32 and w.shape == (200_000,)
    assert np.array_equal(w, edge_weights.tuple_weights(200_000, 3))
    assert not np.array_equal(w, edge_weights.tuple_weights(200_000, 4))
    assert 0 <= w.min() and w.max() < 1
    assert abs(float(w.mean()) - 0.5) < 0.005
    assert abs(float(np.mean(w < 0.1)) - 0.1) < 0.005
    scaled = w.astype(np.float64) * (1 << 24)
    assert np.array_equal(scaled, np.round(scaled))


def test_both_stored_directions_of_a_tuple_carry_its_weight():
    src, dst = kronecker.kronecker_edges(8, 16, 2)
    w = edge_weights.tuple_weights(len(src), 2)
    s2, d2, w2 = edge_weights.both_directions(src, dst, w)
    m = len(src)
    assert len(s2) == len(d2) == len(w2) == 2 * m     # nothing dropped
    assert np.array_equal(s2[:m], d2[m:]) and np.array_equal(
        d2[:m], s2[m:]) and np.array_equal(w2[:m], w2[m:])
    # as a multiset of weighted arcs the list is its own mirror
    def sorted_arcs(a, b):
        order = np.lexsort((w2, b, a))
        return a[order], b[order], w2[order]
    for x, y in zip(sorted_arcs(s2, d2), sorted_arcs(d2, s2)):
        assert np.array_equal(x, y)


def test_by_destination_is_the_same_arcs_sorted():
    src, dst = kronecker.kronecker_edges(8, 16, 2)
    w = edge_weights.tuple_weights(len(src), 2)
    nv = 1 << 8
    offsets, by_src, by_w = edge_weights.by_destination(src, dst, w, nv)
    assert offsets[0] == 0 and offsets[-1] == len(src)
    by_dst = np.repeat(np.arange(nv), np.diff(offsets))
    key = np.lexsort((w, src, dst))
    got = np.lexsort((by_w, by_src, by_dst))
    for a, b in ((by_dst, dst), (by_src, src), (by_w, w)):
        assert np.array_equal(a[got], b[key])
    assert np.all(np.diff(by_dst) >= 0)


@pytest.mark.parametrize("root", [0, 1, 2])
def test_fixed_point_is_the_shortest_path_to_rounding(small, root):
    offsets, src, w = small
    root = int(np.flatnonzero(np.diff(offsets))[root * 100])
    got, sweeps = ref.fixed_point_f32(offsets, src, w, root)
    true = ref.dijkstra_f64(offsets, src, w, root)
    assert got.dtype == np.float32 and sweeps > 3
    assert np.array_equal(np.isfinite(got), np.isfinite(true))
    far = np.isfinite(true) & (true > 0)
    gap = np.abs(got[far].astype(np.float64) - true[far]) / true[far]
    assert far.sum() > 500 and gap.max() <= 1e-6
    dst = np.repeat(np.arange(len(got), dtype=np.int32),
                    np.diff(offsets))
    assert ref.edges_violated(got, src, dst, w) == 0
    assert ref.roots_nonzero(got, root) == 0 and got[root] == 0


def test_fixed_point_on_a_graph_small_enough_to_read():
    #   0 -0.5-> 1 -0.25-> 2,  0 -1.0-> 2,  3 alone with a self-loop
    src = np.array([0, 1, 0, 3], np.uint32)
    dst = np.array([1, 2, 2, 3], np.uint32)
    w = np.array([0.5, 0.25, 1.0, 0.125], np.float32)
    offsets, by_src, by_w = edge_weights.by_destination(src, dst, w, 4)
    got, sweeps, short = ref.fixed_point_f32(offsets, by_src, by_w, 0,
                                             before_last=True)
    assert got.tolist() == [0.0, 0.5, 0.75, np.inf] and sweeps == 3
    assert short.tolist() == [0.0, 0.5, 1.0, np.inf]
    assert ref.dijkstra_f64(offsets, by_src, by_w, 0).tolist() == \
        got.tolist()
    by_dst = np.repeat(np.arange(4), np.diff(offsets))
    assert ref.edges_violated(short, by_src, by_dst, by_w) == 1
    assert ref.mismatched(short, got) == 1
    assert ref.roots_nonzero(got, 0) == 0 == ref.roots_nonzero(got, 3) - 1


def test_the_control_fails_the_limits(small):
    offsets, src, w = small
    root = int(np.flatnonzero(np.diff(offsets))[5])
    nums = control_sssp.control_numbers(offsets, src, w, root, 4)
    assert nums["sound"] == [0, 0]
    assert nums["one_ulp"][0] == 1 and nums["one_ulp"][1] >= 1
    assert min(nums["one_sweep_short"]) >= 1
    assert min(nums["bfloat16_weights"]) > 100
    assert control_sssp.main(["--seed", "5", "--rehearsal"]) == 0


def test_a_control_that_passes_is_a_fault(monkeypatch):
    """``control_sssp.py`` exits 0 only when every control FAILS the
    limits: a comparison that no longer sees one ulp exits 1."""
    monkeypatch.setattr(ref, "mismatched", lambda got, want: 0)
    monkeypatch.setattr(ref, "edges_violated",
                        lambda d, src, dst, w: 0)
    assert control_sssp.main(["--seed", "5", "--rehearsal"]) == 1


@pytest.mark.parametrize("name,spec,unit", [
    ("engine.advance_trip_share",
     {"reader": "program_count", "spans": ["push.converge"],
      "when": "window", "field": "advances",
      "over": ["iters", "advances"], "percent": True}, "%"),
    ("engine.relaxed_edge_ratio",
     {"reader": "program_count", "spans": ["push.converge"],
      "when": "window", "field": "front_edges",
      "over": "graph_edges"}, "x"),
    ("scope_ms.bucket", {"reader": "scope_ms",
                         "scopes": ["lux_bucket"]}, "ms/iter")])
def test_the_new_metrics_are_data_for_readers_that_are_there(
        name, spec, unit):
    bench = harness.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert CELL in entry["workloads"]
    assert (entry["moves"], entry["layer"], entry["unit"],
            entry["better"]) == ("gteps_per_chip", "engine loop", unit,
                                 "lower")
    assert harness.load_json(
        harness.HERE + f"/layer_metrics/{name}.json") == spec


def test_a_parent_without_the_counts_reports_nothing(monkeypatch):
    """Marks without the bucket counts (the parent's) give the readers
    a divisor that counts no advance, or 0: the ratio is left out, and
    the share reads 0 over the iterations alone."""
    from benchmarks.readers import program_count, program_span
    records = [{"id": 1, "parent": 0, "name": "push.converge",
                "t0": 5.0, "t1": 5.0,
                "counts": {"iters": 9, "sparse_iters": 3}}]
    monkeypatch.setattr(program_span, "ring", lambda: records)
    run = types.SimpleNamespace(t_window=1.0, spans=[], events=[],
                                trace_window_s=None)
    ratio = harness.load_json(
        harness.HERE + "/layer_metrics/engine.relaxed_edge_ratio.json")
    share = harness.load_json(
        harness.HERE + "/layer_metrics/engine.advance_trip_share.json")
    assert program_count.read(ratio, run) is None
    assert program_count.read(share, run) == 0.0
    records[0]["counts"].update(advances=3, front_edges=150,
                                graph_edges=100)
    assert program_count.read(ratio, run) == pytest.approx(1.5)
    assert program_count.read(share, run) == pytest.approx(25.0)


def test_the_cell_is_declared_and_on_the_lists_the_issue_names():
    """This cell's own facts only: what other cells and metrics the
    benchmark has, or gains later, is not this test's to hold."""
    bench = harness.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kron21-sssp", "sssp-roots", 1)
    lists = {m["name"]: m.get("workloads", ())
             for group in ("end_to_end", "per_layer")
             for m in bench[group]}
    for name in (
            "gteps_per_chip", "hbm_bytes_per_edge", "ms_per_iter",
            "scope_ms.dense", "scope_ms.sparse", "scope_ms.combine",
            "gather_hbm_roofline", "prep_s.relabel", "prep_s.pair_plan",
            "prep_s.sparse_view", "jit.compiles_in_window",
            "state_ms.init", "state_ms.fetch",
            "engine.sparse_iter_share", "engine.sparse_low_rung_share",
            "delivery.pair_coverage", "prep.store_hit_share"):
        assert CELL in lists[name], name
    # no bottom-up step under the delta schedule
    assert CELL not in lists["engine.pull_iter_share"]


def _run(**kw):
    return harness.run_cell(CELL, 2**31 + 9, 0.5, False,
                            rehearsal=True, **kw)


def test_rehearsal_is_correct_and_reports_the_new_counts():
    r = harness.run_cell(CELL, 2**31 + 11, 0.5, True, rehearsal=True)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] % 8 == 0 and r["attempted"] >= 8   # passes
    m = r["metrics"]
    assert 0 < m["engine.advance_trip_share"]["value"] < 100
    assert m["engine.relaxed_edge_ratio"]["value"] > 1
    assert m["jit.compiles_in_window"]["value"] == 0


def test_a_search_cut_short_is_not_correct(monkeypatch):
    from benchmarks.runners import batch_sssp
    real = batch_sssp.search
    cut = {}

    def short(run, st, root, max_iters=None):
        if root not in cut:                     # learnt once a root
            _s, iters, full = real(None, st, root)
            # the last relax iterations of a converged search only
            # find that nothing improves (a bucket's leftovers), so
            # the loop is cut right before the last one that lowers a
            # distance: one relax iteration before the fixed point
            k = iters - 1
            while np.array_equal(real(None, st, root, k)[2], full):
                k -= 1
            cut[root] = k
        return real(run, st, root, cut[root])
    monkeypatch.setattr(batch_sssp, "search", short)
    r = _run()
    assert r["correct"] is False and r["failed"] == 4   # every sample


def test_an_answer_altered_by_one_ulp_is_not_correct(monkeypatch):
    from benchmarks.runners import batch_sssp
    real = batch_sssp.verify

    def altered(run, st):
        for _root, _iters, answer in st.searches:
            v = int(np.flatnonzero(np.isfinite(answer)
                                   & (answer > 0))[0])
            answer[v] = np.nextafter(answer[v], np.float32(np.inf))
        return real(run, st)
    monkeypatch.setattr(batch_sssp, "verify", altered)
    r = _run()
    assert r["correct"] is False and r["failed"] == 4   # every sample


def test_a_width_that_is_not_finite_is_refused(monkeypatch):
    """The cell is the bucket schedule's: ``auto`` resolving to no
    width (plain frontiers) is an error, not another measurement."""
    from lux_tpu.apps import sssp
    monkeypatch.setattr(sssp, "default_delta", lambda g: None)
    with pytest.raises(RuntimeError, match="no finite bucket width"):
        _run()


def test_cache_entry_holds_the_programs_file_and_the_references_arcs(
        tmp_path, monkeypatch):
    from benchmarks import graphs
    from lux_tpu.graph import Graph
    monkeypatch.setattr(graphs, "GRAPHS", str(tmp_path))
    paths = kron_weighted_cache.ensure(9, 16, True, 5)
    assert paths["generated_edges"] == 16 << 9
    g = Graph.from_file(paths["lux"], weighted=True,
                        weight_dtype=np.float32)
    assert (g.nv, g.ne) == (1 << 9, 2 * (16 << 9))
    assert g.weights.dtype == np.float32
    offsets, by_src, by_w = kron_weighted_cache.load_reference(paths)
    # the program's file and the reference's arrays: the same arcs
    assert np.array_equal(np.asarray(g.row_ptrs, np.int64), offsets[1:])
    assert np.array_equal(np.asarray(g.col_idx), by_src)
    by_dst = np.repeat(np.arange(g.nv), np.diff(offsets))
    a = np.lexsort((np.asarray(g.weights), by_src, by_dst))
    b = np.lexsort((by_w, by_src, by_dst))
    assert np.array_equal(np.asarray(g.weights)[a], by_w[b])
    # the structure is the unweighted entry's: kernel 2's instance
    plain = graphs.ensure(9, 16, True, 5)
    h = Graph.from_file(plain["lux"], weighted=None)
    assert np.array_equal(h.col_idx, g.col_idx) and np.array_equal(
        h.row_ptrs, g.row_ptrs)
    assert kron_weighted_cache.ensure(9, 16, True, 5) == paths
