"""Unit tests for the sparse-frontier machinery (engine/frontier.py)
and the push engine's adaptive/truncation behavior.

The reference has no tests; its closest correctness machinery is the
-check fixed-point audit (reference sssp_gpu.cu:773-798).  These tests
go further: exact oracles plus adversarial capacity limits.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from lux_tpu.engine import frontier as fr
from lux_tpu.graph import Graph
from lux_tpu.apps import sssp, components


def test_compact_mask_basic():
    mask = jnp.asarray(np.array([0, 1, 0, 0, 1, 1, 0, 0], bool))
    labels = jnp.arange(8, dtype=jnp.int32) * 10
    ids, vals, count = fr.compact_mask(mask, labels, capacity=4)
    assert int(count) == 3
    assert ids.tolist() == [1, 4, 5, 8]          # 8 = vpad = invalid
    assert vals.tolist()[:3] == [10, 40, 50]


def test_compact_mask_truncates():
    mask = jnp.ones((8,), bool)
    labels = jnp.arange(8, dtype=jnp.int32)
    ids, vals, count = fr.compact_mask(mask, labels, capacity=3)
    assert int(count) == 8                        # true count reported
    assert ids.tolist() == [0, 1, 2]              # queue truncated


def _compress(row_ptr):
    """nv-wide END-offset row pointers -> (src_ids, src_off) compressed
    index, for readable test construction."""
    rp = np.asarray(row_ptr, np.int64)
    deg = np.diff(rp)
    present = np.nonzero(deg > 0)[0]
    off = np.concatenate(([0], np.cumsum(deg[present])))
    return (jnp.asarray(present.astype(np.int32)),
            jnp.asarray(off.astype(np.int32)))


def test_expand_frontier_owners():
    # vertices 0..3 with out-degrees 2, 0, 3, 1
    sids, soff = _compress([0, 2, 2, 5, 6])
    ids = jnp.asarray(np.array([2, 0, 4, 4], np.int32))   # nv=4 invalid
    vals = jnp.asarray(np.array([7, 9, 0, 0], np.int32))
    edge_idx, src_val, in_range, total, off = fr.expand_frontier(
        ids, vals, sids, soff, nv=4, edge_budget=8)
    assert int(total) == 5                        # deg(2) + deg(0)
    assert np.asarray(off).tolist() == [3, 5, 5, 5]
    ok = np.asarray(in_range)
    assert ok.tolist() == [True] * 5 + [False] * 3
    # first item (vertex 2) owns edges 2,3,4; second (vertex 0) 0,1
    assert np.asarray(edge_idx)[:5].tolist() == [2, 3, 4, 0, 1]
    assert np.asarray(src_val)[:5].tolist() == [7, 7, 7, 9, 9]


def test_expand_frontier_absent_source():
    # queue ids not present in this part's compressed index (zero
    # out-edges here) must expand to nothing
    sids, soff = _compress([0, 2, 2, 5, 6])       # vertex 1 absent
    ids = jnp.asarray(np.array([1, 3, 4, 4], np.int32))
    vals = jnp.asarray(np.array([5, 8, 0, 0], np.int32))
    edge_idx, src_val, in_range, total, off = fr.expand_frontier(
        ids, vals, sids, soff, nv=4, edge_budget=8)
    assert np.asarray(off).tolist() == [0, 1, 1, 1]
    assert int(total) == 1
    assert np.asarray(edge_idx)[:1].tolist() == [5]
    assert np.asarray(src_val)[:1].tolist() == [8]


def test_expand_frontier_gap_before_first_item():
    # invalid slots before the only real item (the flat multi-part
    # queue shape) must not confuse ownership
    sids, soff = _compress([0, 1, 3, 3])          # nv=3
    ids = jnp.asarray(np.array([3, 3, 1, 3], np.int32))
    vals = jnp.asarray(np.array([0, 0, 5, 0], np.int32))
    edge_idx, src_val, in_range, total, _off = fr.expand_frontier(
        ids, vals, sids, soff, nv=3, edge_budget=4)
    assert int(total) == 2
    assert np.asarray(edge_idx)[:2].tolist() == [1, 2]
    assert np.asarray(src_val)[:2].tolist() == [5, 5]


def test_expand_frontier_budget_truncation():
    sids, soff = _compress([0, 3, 6])             # nv=2, deg 3+3
    ids = jnp.asarray(np.array([0, 1], np.int32))
    vals = jnp.asarray(np.array([1, 2], np.int32))
    edge_idx, src_val, in_range, total, _off = fr.expand_frontier(
        ids, vals, sids, soff, nv=2, edge_budget=4)
    assert int(total) == 6                        # exceeds budget
    assert np.asarray(in_range).tolist() == [True] * 4
    assert np.asarray(edge_idx).tolist() == [0, 1, 2, 3]


def _random_graph(nv, ne, seed, weighted=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nv, ne)
    dst = rng.integers(0, nv, ne)
    w = rng.integers(1, 10, ne).astype(np.int32) if weighted else None
    return Graph.from_edges(src, dst, nv, weights=w)


@pytest.mark.parametrize("num_parts", [1, 3])
def test_sssp_tiny_edge_budget_still_converges(num_parts):
    """Truncation safety: an edge budget far below frontier demand must
    still reach the exact fixed point (pending queue suffix stays
    active)."""
    g = _random_graph(60, 240, seed=3)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=num_parts)
    # rebuild with a crippled budget (still >= max single in-part degree)
    from lux_tpu.engine.push import PushEngine
    max_deg = eng.sg.max_in_deg()
    eng2 = PushEngine(eng.sg, eng.program, edge_budget=max_deg + 2)
    dist, iters = eng2.run(max_iters=500)
    ref = sssp.reference_sssp(g, 0)
    np.testing.assert_array_equal(dist.astype(np.int64), ref)


def test_sssp_sparse_matches_dense_path():
    g = _random_graph(200, 900, seed=5)
    dense = sssp.build_engine(g, 0, num_parts=2)
    dense_eng = dense
    from lux_tpu.engine.push import PushEngine
    no_sparse = PushEngine(dense.sg, dense.program, enable_sparse=False)
    d1, _ = dense_eng.run(max_iters=300)
    d2, _ = no_sparse.run(max_iters=300)
    np.testing.assert_array_equal(d1, d2)


def test_components_sparse_enabled():
    g = _random_graph(120, 300, seed=9)
    labels, _ = components.run(g, num_parts=2, max_iters=300)
    ref = components.reference_components(g)
    np.testing.assert_array_equal(labels, ref)


# ---------------------------------------------------------------------
# the ladder: static rungs, the rule that picks one, and the two halves
# of the expansion on every rung


@pytest.mark.parametrize("top,divisors,want", [
    (1600, (16, 4), (100, 400, 1600)),
    (1600, (4, 16), (100, 400, 1600)),      # ascending whatever order
    (1600, (), (1600,)),                    # the top rung alone
    (356, (16,), (22, 356)),
    (3, (16, 4), (1, 3)),                   # floor at 1, distinct
    (1, (16, 4), (1,)),
])
def test_rungs(top, divisors, want):
    got = fr.rungs(top, divisors)
    assert got == want and got[-1] == top


LADDER = (100, 400, 1600)


@pytest.mark.parametrize("need,want", [
    (0, 0), (99, 0), (100, 0), (101, 1),
    (399, 1), (400, 1), (401, 2),
    (1599, 2), (1600, 2), (1601, 2), (10**9, 2),
])
def test_rung_index_smallest_rung_that_holds(need, want):
    """At, just under and just over every rung; what no rung holds
    goes to the top one (which truncates)."""
    got = fr.rung_index(jnp.int32(need), LADDER)
    assert got.dtype == jnp.int32 and int(got) == want
    assert int(fr.rung_index(jnp.int32(need), LADDER[-1:])) == 0


def _star_queue(degs):
    """A part whose source v has ``degs[v]`` out-edges, and the queue
    of ALL its sources with labels 10, 11, ..."""
    degs = np.asarray(degs)
    nv = degs.size
    sids, soff = _compress(np.concatenate([[0], np.cumsum(degs)]))
    ids = jnp.arange(nv, dtype=jnp.int32)
    vals = jnp.arange(nv, dtype=jnp.int32) + 10
    return ids, vals, sids, soff, nv


@pytest.mark.parametrize("use_mxu", [False, True])
@pytest.mark.parametrize("budget", [5, 6, 7, 24, 25])
def test_expand_extents_on_a_lower_rung_is_the_top_rungs_prefix(
        budget, use_mxu):
    """A frontier of 6 out-edges (one absent source, one of degree 0
    among them) expanded on budgets under, at and over its total: the
    slots a budget has are the TOP budget's first slots, bit for bit,
    so a rung that holds the total loses nothing; one that does not is
    a prefix (the truncation the top rung alone may meet)."""
    ids, vals, sids, soff, nv = _star_queue([2, 0, 3, 1])
    ids = jnp.concatenate([ids, jnp.asarray([nv], jnp.int32)])  # pad
    vals = jnp.concatenate([vals, jnp.asarray([0], jnp.int32)])
    top = fr.expand_frontier(ids, vals, sids, soff, nv=nv,
                             edge_budget=25, use_mxu=use_mxu)
    begin, off, total = fr.frontier_extents(ids, sids, soff, nv)
    assert int(total) == int(top[3]) == 6
    np.testing.assert_array_equal(np.asarray(off), np.asarray(top[4]))
    edge_idx, src_val, in_range, _owner = fr.expand_extents(
        vals, begin, off, budget, use_mxu=use_mxu)
    k = min(budget, 6)
    assert np.asarray(in_range).tolist() == [True] * k + \
        [False] * (budget - k)
    for got, want in zip((edge_idx, src_val), top[:2]):
        np.testing.assert_array_equal(np.asarray(got)[:k],
                                      np.asarray(want)[:k])
    assert np.asarray(edge_idx)[:k].tolist() == list(range(6))[:k]
    assert np.asarray(src_val)[:k].tolist() == \
        [10, 10, 12, 12, 12, 13][:k]


@pytest.mark.parametrize("capacity", [2, 3, 4, 8])
def test_compact_mask_on_a_lower_rung_is_the_top_rungs_prefix(capacity):
    """Three set bits compacted on queues under, at and over the
    count: the slots a queue has are the top queue's first slots."""
    mask = jnp.asarray([False, True, False, False, True, True, False])
    labels = jnp.arange(7, dtype=jnp.int32) * 3
    top_ids, top_vals, top_cnt = fr.compact_mask(mask, labels, 8)
    ids, vals, cnt = fr.compact_mask(mask, labels, capacity)
    assert int(cnt) == int(top_cnt) == 3
    k = min(capacity, 3)
    assert np.asarray(ids)[:k].tolist() == [1, 4, 5][:k]
    np.testing.assert_array_equal(np.asarray(vals)[:k],
                                  np.asarray(top_vals)[:k])
    assert (np.asarray(ids)[k:] == 7).all()


@pytest.mark.parametrize("use_mxu", [False, True])
@pytest.mark.parametrize("degs,budget", [
    ([2, 0, 3, 1], 25), ([2, 0, 3, 1], 6), ([2, 0, 3, 1], 4),
    ([0, 0, 5], 8), ([1, 1, 1, 1, 1, 1, 1], 7), ([9, 0, 0, 2, 4], 12)])
def test_expand_extents_owner_is_the_repeated_queue_index(degs, budget,
                                                          use_mxu):
    """The owning queue INDEX of every in-range slot against NumPy's
    ``np.repeat`` of the queue positions by their degrees (what the
    bottom-up step reduces a slot's candidate into), with an absent
    source in the queue and budgets over, at and under the total; the
    slot's value is the owner's."""
    ids, vals, sids, soff, nv = _star_queue(degs)
    ids = jnp.concatenate([jnp.asarray([nv], jnp.int32), ids])  # absent
    vals = jnp.concatenate([jnp.asarray([0], jnp.int32), vals])
    begin, off, total = fr.frontier_extents(ids, sids, soff, nv)
    _edge_idx, src_val, in_range, owner = fr.expand_extents(
        vals, begin, off, budget, use_mxu=use_mxu)
    want = np.repeat(np.arange(len(degs) + 1), [0, *degs])
    k = min(budget, int(total))
    assert int(total) == want.size and int(np.asarray(in_range).sum()) == k
    np.testing.assert_array_equal(np.asarray(owner)[:k], want[:k])
    np.testing.assert_array_equal(np.asarray(src_val)[:k],
                                  np.asarray(vals)[want[:k]])
    assert (np.asarray(owner) >= 0).all() and \
        (np.asarray(owner) <= len(degs)).all()
