"""Each reference against a graph small enough to work by hand."""

import ml_dtypes
import numpy as np
import pytest

from benchmarks import control
from benchmarks.reference import adjacency, bfs, kronecker, pagerank


def _adj(edges, nv):
    src = np.array([e[0] for e in edges], dtype=np.uint32)
    dst = np.array([e[1] for e in edges], dtype=np.uint32)
    return adjacency.by_source(src, dst, nv)


def test_pagerank_one_iteration_by_hand():
    # 0 -> 1, 0 -> 2, 1 -> 2; vertex 2 has no out-edge, 3 is isolated
    offsets, nb = _adj([(0, 1), (0, 2), (1, 2)], 4)
    r = pagerank.pagerank(offsets, nb, 1)
    base, a = 0.85 / 4, 0.15
    assert r == pytest.approx([base,
                               base + a * (0.25 / 2),
                               base + a * (0.25 / 2 + 0.25),
                               base])


def test_pagerank_two_iterations_by_hand():
    offsets, nb = _adj([(0, 1), (1, 0), (1, 2)], 3)
    r1 = [0.85 / 3 + 0.15 * (1 / 3) / 2,        # from 1 (degree 2)
          0.85 / 3 + 0.15 * (1 / 3),            # from 0 (degree 1)
          0.85 / 3 + 0.15 * (1 / 3) / 2]
    r2 = [0.85 / 3 + 0.15 * r1[1] / 2,
          0.85 / 3 + 0.15 * r1[0],
          0.85 / 3 + 0.15 * r1[1] / 2]
    assert pagerank.pagerank(offsets, nb, 2) == pytest.approx(r2)


def test_bfs_levels_by_hand():
    # a path 0 - 1 - 2, a branch 1 - 3, vertex 4 unreachable
    und = [(0, 1), (1, 2), (1, 3)]
    offsets, nb = _adj(und + [(b, a) for a, b in und], 5)
    assert bfs.bfs_levels(offsets, nb, 0).tolist() == [0, 1, 2, 2, -1]
    assert bfs.bfs_levels(offsets, nb, 2).tolist() == [2, 1, 0, 2, -1]
    assert bfs.bfs_levels(offsets, nb, 4).tolist() == [-1, -1, -1, -1, 0]


def test_bfs_follows_direction():
    offsets, nb = _adj([(0, 1), (2, 1)], 3)
    assert bfs.bfs_levels(offsets, nb, 0).tolist() == [0, 1, -1]


def test_hops_to_levels_maps_the_sentinel():
    hops = np.array([0, 3, 2**30, 7], dtype=np.int32)
    assert bfs.hops_to_levels(hops, 8).tolist() == [0, 3, -1, 7]


@pytest.fixture(scope="module")
def small_graph():
    src, dst = kronecker.kronecker_edges(12, 16, seed=5)
    return adjacency.by_source(src, dst, 1 << 12)


def test_bfloat16_ranks_fail_the_comparison(small_graph):
    """The control: a bfloat16 state has to fail a limit of the
    configuration, a float32 state has to pass both."""
    from benchmarks import harness
    limits = harness.load_json(
        harness.HERE + "/configs/kron21-pagerank.json")["guarantees"]
    offsets, nb = small_graph
    low = control.rank_control(offsets, nb, 20)
    assert any(low[k] > limits[k] for k in low), (low, limits)
    want = pagerank.pagerank(offsets, nb, 20)
    f32 = pagerank.pagerank(offsets, nb, 20, state_dtype=np.float32)
    sound = pagerank.compare_ranks(f32, want)
    assert all(sound[k] <= limits[k] for k in sound), (sound, limits)
    # and ranks that are merely ROUNDED to bfloat16 at the end fail too
    rounded = want.astype(ml_dtypes.bfloat16).astype(np.float64)
    assert pagerank.compare_ranks(rounded, want)[
        "rank_max_rel_err"] > limits["rank_max_rel_err"]


def test_one_level_off_by_one_fails_the_comparison(small_graph):
    offsets, nb = small_graph
    root = int(np.flatnonzero(np.diff(offsets) > 0)[0])
    assert control.level_control(offsets, nb, root) == 1 > 0
