import numpy as np

from benchmarks.reference import adjacency, kronecker


def test_edge_count_and_range():
    src, dst = kronecker.kronecker_edges(10, 16, seed=7)
    assert src.shape == dst.shape == (16 << 10,)
    assert src.dtype == dst.dtype == np.uint32
    assert int(src.max()) < 1 << 10 and int(dst.max()) < 1 << 10


def test_same_seed_same_list_other_seed_other_list():
    a = kronecker.kronecker_edges(9, 16, seed=2**31 + 11)
    b = kronecker.kronecker_edges(9, 16, seed=2**31 + 11)
    c = kronecker.kronecker_edges(9, 16, seed=2**31 + 12)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


def test_degree_skew():
    """A = 0.57 concentrates edges: the top 1% of vertices hold far
    more than 1% of the out-edges, and some vertices hold none."""
    src, _dst = kronecker.kronecker_edges(12, 16, seed=1)
    deg = np.sort(np.bincount(src, minlength=1 << 12))[::-1]
    assert deg[:41].sum() > 0.15 * deg.sum()
    assert (deg == 0).sum() > 0.1 * (1 << 12)
    assert deg.max() > 20 * deg.mean()


def test_labels_are_permuted():
    """Without the permutation vertex 0 would be the heaviest."""
    src, _ = kronecker.kronecker_edges(12, 16, seed=3)
    deg = np.bincount(src, minlength=1 << 12)
    assert int(np.argmax(deg)) != 0


def test_adjacency_by_source():
    src = np.array([2, 0, 2, 1, 2], dtype=np.uint32)
    dst = np.array([1, 1, 0, 2, 1], dtype=np.uint32)
    offsets, nb = adjacency.by_source(src, dst, 4)
    assert offsets.tolist() == [0, 1, 2, 5, 5]
    assert nb.tolist() == [1, 2, 0, 1, 1]       # duplicates kept
