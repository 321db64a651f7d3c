"""Failure-detection guards and multi-host helpers."""

import numpy as np
import pytest

from lux_tpu import debug
from lux_tpu.apps import pagerank, sssp
from lux_tpu.convert import uniform_random_edges
from lux_tpu.graph import Graph


def test_check_finite_passes_and_fails():
    debug.check_finite((np.ones(3), np.zeros(2, np.int32)))
    with pytest.raises(debug.DivergenceError, match="non-finite"):
        debug.check_finite((np.array([1.0, np.nan]),), where="x")


def test_run_guarded_matches_plain():
    src, dst = uniform_random_edges(80, 500, seed=71)
    g = Graph.from_edges(src, dst, 80)
    eng = pagerank.build_engine(g, num_parts=2)
    want = eng.unpad(eng.run(eng.init_state(), 9))
    got = eng.unpad(debug.run_guarded(eng, eng.init_state(), 9,
                                      segment=4))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_converge_guarded_matches_plain():
    src, dst = uniform_random_edges(150, 1100, seed=72)
    g = Graph.from_edges(src, dst, 150)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=2)
    want, _ = sssp.run(g, start_vertex=0, num_parts=2)
    got, iters = debug.converge_guarded(eng, segment=3)
    reach = ~sssp.unreachable(got)
    np.testing.assert_array_equal(got[reach], want[reach])
    assert iters > 0


def test_converge_guarded_weighted_inf_ok():
    """+inf sentinel distances must NOT trip the divergence guard."""
    src, dst, w = uniform_random_edges(100, 600, seed=73, weighted=True)
    # float weights: float32 distances, +inf the sentinel
    g = Graph.from_edges(src, dst, 100, weights=w.astype(np.float32))
    eng = sssp.build_engine(g, start_vertex=0, num_parts=2,
                            weighted=True)
    got, _ = debug.converge_guarded(eng, segment=2)
    want = sssp.reference_sssp(g, start_vertex=0, weighted=True)
    np.testing.assert_allclose(got, want.astype(np.float32), rtol=1e-6)


def test_converge_guarded_chain_no_false_stall():
    """A path graph keeps frontier size 1 every iteration — progress
    must be detected from labels, not counts."""
    n = 40
    src = np.arange(n - 1, dtype=np.uint32)
    dst = np.arange(1, n, dtype=np.uint32)
    g = Graph.from_edges(src, dst, n)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=1)
    got, iters = debug.converge_guarded(eng, segment=3,
                                        stall_segments=3)
    assert got[n - 1] == n - 1 and iters >= n - 1


def test_multihost_single_process():
    from lux_tpu.parallel import multihost
    multihost.initialize()          # no-op without a coordinator
    mesh = multihost.global_mesh(4)
    assert mesh.devices.size == 4
    assert list(multihost.process_parts(8)) == list(range(8))
