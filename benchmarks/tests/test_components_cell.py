"""``cc.indochina``'s own pieces: the web-crawl generator, the
max-label reference against brute-force reachability, the control
against the configuration's limit, the two new fill metrics' files,
and rehearsals with the timed path broken underneath."""

import numpy as np
import pytest

from benchmarks import control_components, harness, webgraph_cache
from benchmarks.reference import components as ref
from benchmarks.reference import webgraph as gen

CONFIG = harness.load_json(
    harness.HERE + "/configs/indochina-components.json")
SHAPE = {k: CONFIG[k] for k in gen.PARAMETERS}
SMALL = (CONFIG["rehearsal"]["vertices"], CONFIG["rehearsal"]["arcs"])


@pytest.fixture(scope="module")
def small():
    return gen.web_arcs(*SMALL, 7, **SHAPE)


def test_the_configuration_states_the_sources_counts():
    c = CONFIG
    assert (c["vertices"], c["arcs"]) == (7414866, 194109311)
    assert c["stored_edges"] == c["arcs"] and c["directed"] is True
    assert c["reduced"] == [] and c["num_parts"] == 1
    assert c["guarantees"]["cc_mismatched_labels"] == 0
    # every parameter of the generator is stated, and under assumed
    assert set(gen.PARAMETERS) <= set(c) and \
        set(gen.PARAMETERS) <= set(c["assumed"])
    assert c["engine"] == {"pair_threshold": 16, "pair_min_fill": 24,
                           "enable_sparse": True}


@pytest.mark.parametrize("vertices,arcs", [
    SMALL, (4000, 104720), (500, 13090), (60, 600)])
def test_generator_gives_the_two_counts_to_the_arc(vertices, arcs):
    src, dst = gen.web_arcs(vertices, arcs, 3, **SHAPE)
    key = src.astype(np.int64) * vertices + dst
    assert src.dtype == dst.dtype == np.int32
    assert len(key) == arcs == len(np.unique(key))  # no duplicate
    assert np.all(np.diff(key) > 0)             # sorted by (src, dst)
    assert not np.any(src == dst)               # no self-loop
    assert 0 <= min(src.min(), dst.min())
    assert max(src.max(), dst.max()) < vertices


def test_generator_is_seeded_and_has_a_crawls_shape(small):
    src, dst = small
    again = gen.web_arcs(*SMALL, 7, **SHAPE)
    assert np.array_equal(src, again[0]) and np.array_equal(dst,
                                                            again[1])
    other = gen.web_arcs(*SMALL, 8, **SHAPE)
    assert not np.array_equal(other[1], dst)
    nv = SMALL[0]
    info = gen.describe(src, dst, nv, components=True)
    assert info["arcs"] == SMALL[1]
    # hubs on the in side, a bounded out side, pages without links,
    # mutual links, and a bow-tie: a core that is not everything
    assert info["max_in_degree"] > 20 * info["mean_out_degree"]
    assert info["max_out_degree"] <= CONFIG["out_degree_max"]
    assert 0.10 < info["no_out_link_share"] < 0.20
    assert 0.05 < info["reciprocal_arc_share"] < 0.5
    assert 0.3 < info["largest_scc_share"] < 0.9
    assert info["largest_wcc_share"] > 0.9
    # locality: most arcs stay within the host (ids are URL order)
    starts = gen.host_starts(nv, 7, CONFIG["host_size_median"],
                             CONFIG["host_size_sigma"])
    host = np.searchsorted(starts, np.arange(nv), side="right") - 1
    inside = np.mean(host[src] == host[dst])
    assert 0.85 < inside < 0.95


def test_too_many_arcs_and_too_few_are_refused():
    with pytest.raises(ValueError):
        gen.web_arcs(10, 91, 1, **SHAPE)
    with pytest.raises(ValueError):
        gen.web_arcs(5000, 5000, 1, **SHAPE)   # under the trees' own


def test_by_destination_is_the_same_arcs_sorted(small):
    src, dst = small
    nv = SMALL[0]
    offsets, by_src = gen.by_destination(src, dst, nv)
    assert offsets[0] == 0 and offsets[-1] == len(src) == len(by_src)
    by_dst = np.repeat(np.arange(nv), np.diff(offsets))
    order = np.lexsort((src, dst))
    assert np.array_equal(by_dst, dst[order])
    assert np.array_equal(by_src, src[order])


@pytest.mark.parametrize("graph", ["web", "sparse"])
def test_reference_agrees_with_brute_force_reachability(graph):
    nv = 200
    if graph == "web":
        src, dst = gen.web_arcs(nv, 1500, 5, **SHAPE)
    else:               # few arcs: many sources, many labels
        rng = np.random.default_rng(6)
        src, dst = (rng.integers(0, nv, 260).astype(np.int32)
                    for _ in range(2))
    offsets, by_src = gen.by_destination(src, dst, nv)
    label0 = np.random.default_rng(3).permutation(nv)
    got, sweeps = ref.fixed_point(offsets, by_src, label0)
    reach = np.eye(nv, dtype=bool)              # reach[u, v]: u to v
    reach[src, dst] = True
    for k in range(nv):
        reach |= reach[:, [k]] & reach[[k], :]
    want = np.array([label0[reach[:, v]].max() for v in range(nv)])
    assert ref.mismatched(got, want) == 0 and sweeps > 1
    assert len(np.unique(want)) > (1 if graph == "web" else 30)


def test_reference_refuses_what_is_no_permutation():
    ref.check_permutation([2, 0, 1], 3)
    for bad in ([0, 0, 1], [0, 1, 3], [0, 1], [-1, 0, 1]):
        with pytest.raises(ValueError):
            ref.check_permutation(bad, 3)


def test_the_control_fails_the_limit(small):
    src, dst = small
    offsets, by_src = gen.by_destination(src, dst, SMALL[0])
    label0 = np.random.default_rng(4).permutation(SMALL[0])
    nums = control_components.control_numbers(offsets, by_src, label0, 4)
    limit = CONFIG["guarantees"]["cc_mismatched_labels"]
    assert nums["one_label"] == 1 > limit
    assert nums["one_sweep_short"] >= 1 > limit
    assert control_components.main(["--seed", "5", "--rehearsal"]) == 0


@pytest.mark.parametrize("name,field,over", [
    ("engine.queue_slot_fill", "queue_items", "queue_slots"),
    ("engine.budget_slot_fill", "budget_edges", "budget_slots")])
def test_the_fill_metrics_are_data_for_the_reader_that_is_there(
        name, field, over):
    bench = harness.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry["workloads"] == ["bfs.kron21", "bfs.kron23.mesh4",
                                  "cc.indochina"]
    assert (entry["moves"], entry["layer"], entry["unit"]) == (
        "gteps_per_chip", "engine loop", "%")
    spec = harness.load_json(
        harness.HERE + f"/layer_metrics/{name}.json")
    assert spec == {"reader": "program_count",
                    "spans": ["push.converge"], "when": "window",
                    "field": field, "over": over, "percent": True}


def test_a_parent_without_the_counts_reports_nothing(monkeypatch):
    """Marks without the four counts (the parent's) give the reader a
    divisor of 0: the metric is left out, not 0."""
    import types

    from benchmarks.readers import program_count, program_span
    records = [{"id": 1, "parent": 0, "name": "push.converge",
                "t0": 5.0, "t1": 5.0,
                "counts": {"iters": 9, "sparse_iters": 3,
                           "low_rung_iters": 3, "pull_iters": 0}}]
    monkeypatch.setattr(program_span, "ring", lambda: records)
    run = types.SimpleNamespace(t_window=1.0, spans=[], events=[],
                                trace_window_s=None)
    spec = harness.load_json(
        harness.HERE + "/layer_metrics/engine.queue_slot_fill.json")
    assert program_count.read(spec, run) is None
    records[0]["counts"].update(queue_items=30, queue_slots=120)
    assert program_count.read(spec, run) == pytest.approx(25.0)


def _run(**kw):
    return harness.run_cell("cc.indochina", 2**31 + 9, 0.5, False,
                            rehearsal=True, **kw)


def test_a_loop_one_iteration_short_is_not_correct(monkeypatch):
    from lux_tpu.engine.push import PushEngine
    real = PushEngine.converge
    full = {}

    def short(self, label, active, max_iters=None):
        if "iters" not in full:                 # learn the length once
            probe = real(self, *self.init_state())
            full["iters"] = int(probe[2])
        return real(self, label, active, full["iters"] - 2)
    # the last iteration of a converged loop only finds the frontier
    # empty, so the loop is cut before the last one that relaxes
    monkeypatch.setattr(PushEngine, "converge", short)
    r = _run()
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0


def test_a_state_returned_unchanged_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from lux_tpu.engine.push import PushEngine
    monkeypatch.setattr(
        PushEngine, "converge",
        lambda self, label, active, max_iters=None:
        (label, active, jnp.int32(1)))
    r = _run()
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0


def test_cache_entry_holds_the_programs_file_and_the_references_arcs(
        tmp_path, monkeypatch):
    from benchmarks import graphs
    from lux_tpu.graph import Graph
    monkeypatch.setattr(graphs, "GRAPHS", str(tmp_path))
    paths = webgraph_cache.ensure(700, 9000, 5, SHAPE)
    assert paths["generated_edges"] == 9000
    g = Graph.from_file(paths["lux"], weighted=None)
    assert (g.nv, g.ne) == (700, 9000) and g.weights is None
    offsets, by_src = webgraph_cache.load_reference(paths)
    # the program's file and the reference's arrays: the same arcs
    # (the converter orders a destination's arcs by source too)
    assert np.array_equal(np.asarray(g.row_ptrs, np.int64), offsets[1:])
    assert np.array_equal(np.asarray(g.col_idx), by_src)
    shape = harness.load_json(paths["shape"])
    assert shape["arcs"] == 9000 and "largest_scc_share" in shape
    assert webgraph_cache.ensure(700, 9000, 5, SHAPE) == paths
    # another model is another entry
    other = webgraph_cache.entry_dir(700, 9000, 5,
                                     {**SHAPE, "leaf_share": 0.5})
    assert other != webgraph_cache.entry_dir(700, 9000, 5, SHAPE)
