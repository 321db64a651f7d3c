"""The references ``kron20-serve-mixed`` brings (personalized PageRank,
seeded reachability) against hand-worked answers, dense linear algebra
and the frontier search; the program's three serving kinds against
them at a small size; the bfloat16 control fails the limits."""

import numpy as np
import pytest

from benchmarks import control_mixed, harness
from benchmarks.reference import adjacency, bfs, kronecker, ppr, reach

LIMITS = harness.load_json(
    harness.os.path.join(harness.HERE, "configs",
                         "kron20-serve-mixed.json"))["guarantees"]


def graph(scale=10, edge_factor=16, seed=1):
    src, dst = kronecker.kronecker_edges(scale, edge_factor, seed)
    return (src, dst) + adjacency.by_source(src, dst, 1 << scale)


def test_ppr_on_a_hand_worked_graph():
    """0 -> 1, 0 -> 2, 1 -> 2, 2 has no out-edge; source 0, which
    keeps 1 - 0.15 throughout.  First iteration: 0 holds 1 and sends
    1/2 along each edge, so 1 and 2 get 0.15 * 0.5.  Second: 0 holds
    0.85 and sends 0.425 each way, 1 sends its 0.075 to 2, so 1 gets
    0.15 * 0.425 and 2 gets 0.15 * (0.425 + 0.075)."""
    offsets, neighbours = adjacency.by_source([0, 0, 1], [1, 2, 2], 3)
    one = ppr.personalized_pagerank(offsets, neighbours, 0, 1)
    np.testing.assert_allclose(one, [0.85, 0.075, 0.075], rtol=1e-15)
    two = ppr.personalized_pagerank(offsets, neighbours, 0, 2)
    np.testing.assert_allclose(two, [0.85, 0.06375, 0.075], rtol=1e-15)
    zero = ppr.personalized_pagerank(offsets, neighbours, 0, 0)
    np.testing.assert_array_equal(zero, [1.0, 0.0, 0.0])


def test_ppr_is_the_dense_recurrence():
    src, dst, offsets, neighbours = graph(scale=7, edge_factor=8)
    nv = 1 << 7
    deg = np.diff(offsets)
    m = np.zeros((nv, nv))
    np.add.at(m, (dst, src), 1.0 / np.maximum(deg[src], 1))
    source = int(np.flatnonzero(deg > 0)[3])
    reset = np.zeros(nv)
    reset[source] = 1.0
    rank = reset.copy()
    for _ in range(9):
        rank = (1 - ppr.ALPHA) * reset + ppr.ALPHA * m @ rank
    got = ppr.personalized_pagerank(offsets, neighbours, source, 9)
    np.testing.assert_allclose(got, rank, rtol=1e-12, atol=1e-300)
    # and its fixed point solves the linear system
    fixed = np.linalg.solve(np.eye(nv) - ppr.ALPHA * m,
                            (1 - ppr.ALPHA) * reset)
    far = ppr.personalized_pagerank(offsets, neighbours, source, 60)
    np.testing.assert_allclose(far, fixed, rtol=1e-9, atol=1e-30)


def test_reach_is_the_frontier_search():
    _s, _d, offsets, neighbours = graph()
    for seed in np.flatnonzero(np.diff(offsets) > 0)[[0, 7, 99]]:
        levels = bfs.bfs_levels(offsets, neighbours, int(seed))
        labels = reach.reach_labels(offsets, neighbours, int(seed))
        assert labels.dtype == np.int32 and labels[seed] == seed
        np.testing.assert_array_equal(labels == seed, levels >= 0)
        np.testing.assert_array_equal(labels[levels < 0], -1)
    # 0 -> 1 -> 2, 3 apart
    offsets, neighbours = adjacency.by_source([0, 1], [1, 2], 4)
    np.testing.assert_array_equal(
        reach.reach_labels(offsets, neighbours, 1), [-1, 1, 1, -1])


def test_compare_ranks_holds_only_the_mass_above_the_floor():
    want = np.array([0.85, 0.1, 1e-13, 0.0])
    got = np.array([0.85, 0.1001, 0.0, 0.0])
    nums = ppr.compare_ranks(got, want)
    assert nums["ppr_max_rel_err"] == pytest.approx(1e-3)
    assert nums["ppr_l1_rel_err"] == pytest.approx(
        (1e-4 + 1e-13) / want.sum())


@pytest.mark.parametrize("seed", [1, 2])
def test_the_program_against_the_references(seed):
    """The program's three serving kinds at a small size on seeded
    graphs: exact hops and reachability, personalized PageRank inside
    the cell's limits; the apps' own oracles agree with the
    references."""
    from lux_tpu import serve
    from lux_tpu.apps import components, pagerank
    from lux_tpu.graph import Graph

    src, dst, offsets, neighbours = graph(scale=9, seed=seed)
    nv = 1 << 9
    g = Graph.from_edges(src, dst, nv)
    srv = serve.Server(g, batch=4, num_parts=1)
    sources = [int(s) for s in np.random.default_rng(seed).choice(
        np.flatnonzero(np.diff(offsets) > 0), size=5, replace=False)]
    for kind in serve.KINDS:
        for s in sources:
            srv.submit(kind, source=s)
    responses = srv.run()
    assert len(responses) == 15
    for r in responses:
        if r.kind == "sssp":
            want = bfs.bfs_levels(offsets, neighbours, r.source)
            np.testing.assert_array_equal(
                bfs.hops_to_levels(r.answer, nv), want)
        elif r.kind == "components":
            want = reach.reach_labels(offsets, neighbours, r.source)
            np.testing.assert_array_equal(r.answer, want)
            np.testing.assert_array_equal(
                components.reference_components_batched(
                    g, [r.source])[:, 0], want)
        else:
            want = ppr.personalized_pagerank(offsets, neighbours,
                                             r.source, r.iters)
            nums = ppr.compare_ranks(
                ppr.to_ranks(r.answer, offsets), want)
            assert nums["ppr_l1_rel_err"] <= LIMITS["ppr_l1_rel_err"]
            assert nums["ppr_max_rel_err"] <= LIMITS["ppr_max_rel_err"]
            oracle = pagerank.reference_pagerank_batched(
                g, pagerank.one_hot_resets(nv, [r.source]), r.iters)
            np.testing.assert_allclose(
                ppr.to_ranks(oracle[:, 0], offsets), want, rtol=1e-12,
                atol=1e-300)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bfloat16_control_fails_every_limit(seed):
    _s, _d, offsets, neighbours = graph(scale=12, seed=seed)
    sources = np.flatnonzero(np.diff(offsets) > 0)[[5, 50, 500]]
    nums = control_mixed.control_numbers(offsets, neighbours, sources,
                                         12)
    for name, value in nums.items():
        assert not value <= LIMITS[name], (name, value)
