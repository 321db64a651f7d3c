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
    begin, off, total = fr.frontier_extents(ids, fr.row_table(sids),
                                            soff, nv)
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
    begin, off, total = fr.frontier_extents(ids, fr.row_table(sids),
                                            soff, nv)
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


# ---------------------------------------------------------------------
# the budget stage without a fetch from the queue: what a slot needs of
# its item runs along the slots as prefix sums (PR 46)

_PQ = 16        # queue length of the property test: one compiled shape


def _label_cases(dtype, n, rng):
    """Labels whose bit patterns would show any rounding or lost
    carry: both ends of int32; inf, -0.0, subnormals, nan in float32."""
    if dtype == np.int32:
        edge = np.array([-2**31, 2**31 - 1, -1, 0, 1, -2**31 + 1],
                        np.int32)
        rand = rng.integers(-2**31, 2**31, n).astype(np.int32)
    else:
        edge = np.array([np.inf, -np.inf, -0.0, 0.0, 1e-45, -1e-45,
                         1.1754942e-38, np.nan, 3.4028235e38],
                        np.float32)
        rand = rng.integers(0, 2**32, n, dtype=np.uint64) \
            .astype(np.uint32).view(np.float32)
    mix = np.where(rng.random(n) < 0.5, rng.choice(edge, n), rand)
    return mix.astype(dtype)


# degrees of the queue's items (0 = a source with no out-edge here or
# an absent one), _PQ of them: zero-degree items leading, trailing and
# in runs, one item alone, no edge at all
_DEG_CASES = {
    "mixed": [2, 0, 3, 1, 0, 0, 4, 1, 0, 5, 0, 0, 0, 2, 1, 0],
    "leading-zeros": [0, 0, 0, 3, 1, 2, 0, 1, 1, 1, 0, 2, 0, 0, 4, 1],
    "trailing-zeros": [1, 2, 3, 4, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
    "one-hub": [0, 0, 0, 0, 0, 0, 0, 19, 0, 0, 0, 0, 0, 0, 0, 0],
    "all-ones": [1] * 16,
    "empty": [0] * 16,
}


@pytest.mark.parametrize("use_mxu", [False, True])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("case", list(_DEG_CASES))
def test_expand_extents_is_np_repeat_of_the_queue(case, dtype, use_mxu):
    """``expand_extents`` against ``np.repeat`` of the queue positions
    by their degrees, on budgets under, at and over the total (several
    items past the budget among them): ``in_range`` on every slot, and
    ``edge_idx``, ``owner`` and the label's BITS on the in-range
    ones."""
    deg = np.asarray(_DEG_CASES[case])
    assert deg.size == _PQ
    rng = np.random.default_rng(sum(map(ord, case)))
    begin = np.where(deg > 0, rng.integers(0, 2**31 - 64, _PQ), 0) \
        .astype(np.int32)
    off = np.cumsum(deg).astype(np.int32)
    total = int(off[-1])
    vals = _label_cases(dtype, _PQ, rng)
    owner_want = np.repeat(np.arange(_PQ), deg)
    edge_want = np.concatenate(
        [b + np.arange(d) for b, d in zip(begin, deg)] + [[]]) \
        .astype(np.int32)
    for budget in sorted({1, max(1, total - 9), max(1, total - 1),
                          max(1, total), total + 1, total + 40}):
        edge_idx, src_val, in_range, owner = fr.expand_extents(
            jnp.asarray(vals), jnp.asarray(begin), jnp.asarray(off),
            budget, use_mxu=use_mxu)
        k = min(budget, total)
        assert src_val.dtype == vals.dtype and owner.dtype == jnp.int32
        assert np.asarray(in_range).tolist() == \
            [True] * k + [False] * (budget - k)
        np.testing.assert_array_equal(np.asarray(owner)[:k],
                                      owner_want[:k])
        np.testing.assert_array_equal(np.asarray(edge_idx)[:k],
                                      edge_want[:k])
        assert not np.asarray(edge_idx)[k:].any()
        np.testing.assert_array_equal(
            np.asarray(src_val)[:k].view(np.uint32),
            vals[owner_want[:k]].view(np.uint32))
        # a slot past the total still names a queue item: the two-way
        # slot indexes the labels by it whatever in_range says
        assert (np.asarray(owner) >= 0).all() and \
            (np.asarray(owner) < _PQ).all()


@pytest.mark.parametrize("dtype", [np.int8, np.uint16, np.float16])
def test_expand_extents_fetches_a_label_that_is_not_32_bits(dtype):
    """Only a 32-bit label can ride a prefix sum of int32 words; any
    other width is fetched by the owner, as before."""
    deg = np.asarray(_DEG_CASES["mixed"])
    vals = (np.arange(_PQ) * 7 + 1).astype(dtype)
    _e, src_val, in_range, _o = fr.expand_extents(
        jnp.asarray(vals), jnp.zeros((_PQ,), jnp.int32),
        jnp.asarray(np.cumsum(deg).astype(np.int32)), 24)
    assert src_val.dtype == vals.dtype
    k = int(np.asarray(in_range).sum())
    np.testing.assert_array_equal(np.asarray(src_val)[:k],
                                  np.repeat(vals, deg)[:k])


def _expand_by_fetch(vals, begin, off, edge_budget, use_mxu=False):
    """The plain form the engine is held to: a slot's owner by binary
    search in the END offsets, everything else fetched by the owner."""
    slot = jnp.arange(edge_budget, dtype=jnp.int32)
    owner = jnp.minimum(
        jnp.searchsorted(off, slot, side="right"),
        off.shape[0] - 1).astype(jnp.int32)
    deg = jnp.diff(off, prepend=jnp.zeros((1,), off.dtype))
    in_range = slot < jnp.minimum(off[-1], edge_budget)
    edge_idx = slot - (off - deg)[owner] + begin[owner]
    return (jnp.where(in_range, edge_idx, 0).astype(jnp.int32),
            None if vals is None else vals[owner], in_range, owner)


_COUNTS = ("iters", "sparse_iters", "low_rung_iters", "queue_items",
           "queue_slots", "budget_edges", "budget_slots")


@pytest.mark.parametrize("kind", ["sssp-float32", "bfs", "components"])
def test_engine_answers_and_counters_are_the_fetching_forms(
        kind, monkeypatch):
    """The same small graph through the engine as it is and through
    one whose budget stage fetches from the queue (``_expand_by_fetch``
    patched in before the build): labels bit for bit, and the same
    ``iters``, rung counts and slot fills, with sparse iterations on
    BOTH budget rungs; the labels are the oracle's too."""
    from lux_tpu import telemetry
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.graph import ShardedGraph
    rng = np.random.default_rng(46)
    # (symmetric and thin for components: the engine builds its
    # two-way slot, and the late frontiers shrink through both rungs)
    nv, ne = 3000, 3000 if kind == "components" else 9000
    src, dst = rng.integers(0, nv, ne), rng.integers(0, nv, ne)
    if kind == "components":
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    w = rng.random(src.size).astype(np.float32) \
        if kind == "sssp-float32" else None
    g = Graph.from_edges(src, dst, nv, weights=w)
    prog = components.make_program() if kind == "components" \
        else sssp.make_program(0, w is not None)
    sg = ShardedGraph.build(g, 1)

    def solve():
        eng = PushEngine(sg, prog, edge_budget=1600)
        assert eng.budget_rungs == (100, 1600)
        label, _active, _it = eng.converge(*eng.init_state())
        mark = [r for r in telemetry.spans()
                if r["name"] == "push.converge"][-1]["counts"]
        return eng.unpad(label), {k: mark[k] for k in _COUNTS}

    got, counts = solve()
    monkeypatch.setattr(fr, "expand_extents", _expand_by_fetch)
    want, want_counts = solve()
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got.view(np.uint32),
                                  want.view(np.uint32))
    assert counts == want_counts
    assert 0 < counts["low_rung_iters"] < counts["sparse_iters"]
    assert 0 < counts["budget_edges"] <= counts["budget_slots"]
    if kind == "components":
        np.testing.assert_array_equal(
            got, components.reference_components(g))
    elif kind == "bfs":
        np.testing.assert_array_equal(got.astype(np.int64),
                                      sssp.reference_sssp(g, 0))


@pytest.mark.parametrize("use_mxu", [False, True])
def test_expand_extents_without_labels_lays_no_label_channel(use_mxu):
    """The two-way slot reads its label off the labels' array, so the
    engine hands ``expand_extents`` no labels: the other outputs are
    what they are with labels, and the marks vector is one channel
    shorter (the channel is not computed and dropped, it is not
    there)."""
    import jax
    deg = np.asarray(_DEG_CASES["mixed"])
    begin = jnp.asarray(np.where(deg > 0, 100 * np.arange(_PQ), 0)
                        .astype(np.int32))
    off = jnp.asarray(np.cumsum(deg).astype(np.int32))
    vals = jnp.arange(_PQ, dtype=jnp.int32)
    with_labels = fr.expand_extents(vals, begin, off, 24,
                                    use_mxu=use_mxu)
    bare = fr.expand_extents(None, begin, off, 24, use_mxu=use_mxu)
    assert bare[1] is None
    for got, want in zip(bare[::2] + bare[3:], with_labels[::2]
                         + with_labels[3:]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def slots(v):
        jaxpr = jax.make_jaxpr(lambda b, o: fr.expand_extents(
            v, b, o, 24, use_mxu=use_mxu)[::2])(begin, off)
        return sum(e.invars[0].aval.shape[-1] for e in jaxpr.eqns
                   if e.primitive.name == "scatter-add")
    assert slots(vals) - slots(None) == fr.SLOT_ALIGN


# ---------------------------------------------------------------------
# the queue stage's searches fetch a row of splitters a step (PR 48)

_F = fr.ROW_FANOUT
_ROW_LENGTHS = (1, _F - 1, _F, _F + 1, _F * _F - 1, _F * _F + 1, 4097,
                70001)


def row_search(table, queries):
    """``jnp.searchsorted(table, queries, side="left")`` as int32 on a
    non-decreasing int32 ``table`` [N]: ``table_search`` on the tree
    built on the spot."""
    return fr.table_search(fr.row_table(table), table.shape[0], queries)


def _row_tables(kind, n, rng):
    """Non-decreasing int32 tables of length ``n`` as the queue stage
    meets them: the running count of a mask (long plateaus: sparse,
    empty and full masks) and sorted ids under an ``nv``-padded tail
    (``src_ids``); and tables that reach both ends of int32."""
    if kind == "ranks-sparse":
        return np.cumsum(rng.random(n) < 0.01).astype(np.int32)
    if kind == "ranks-empty":
        return np.zeros(n, np.int32)
    if kind == "ranks-full":
        return np.arange(1, n + 1, dtype=np.int32)
    if kind == "ids-padded":
        real = max(1, n - n // 3)
        ids = np.sort(rng.choice(4 * n, size=real, replace=False))
        return np.concatenate(
            [ids, np.full(n - real, 4 * n)]).astype(np.int32)
    assert kind == "int32-ends"
    t = np.sort(rng.integers(-2**31, 2**31, n)).astype(np.int32)
    t[:1 + n // 7] = -2**31
    t[n - 1 - n // 7:] = 2**31 - 1
    return t


def _row_queries(table, rng):
    """Queries under the least entry, equal to entries, between them
    and over the greatest, unsorted, and both ends of int32."""
    lo, hi = int(table[0]), int(table[-1])
    q = np.concatenate([
        rng.choice(table, 200), rng.choice(table, 50).astype(np.int64) + 1,
        rng.choice(table, 50).astype(np.int64) - 1,
        rng.integers(lo - 3, hi + 4, 200),
        [lo - 1, lo, hi, hi + 1, -2**31, 2**31 - 1]])
    return np.clip(q, -2**31, 2**31 - 1).astype(np.int32)


@pytest.mark.parametrize("kind", ["ranks-sparse", "ranks-empty",
                                  "ranks-full", "ids-padded",
                                  "int32-ends"])
@pytest.mark.parametrize("n", _ROW_LENGTHS)
def test_row_search_is_searchsorted_left(n, kind):
    """``row_search`` against ``np.searchsorted(side="left")``, bit
    for bit: table lengths around the fan-out's powers, plateaus,
    padded tails, queries outside and inside the table; and under a
    ``vmap`` over two parts' tables with one shared vector of
    queries, as the engine runs it."""
    import jax
    rng = np.random.default_rng(n * 31 + len(kind))
    table = _row_tables(kind, n, rng)
    queries = _row_queries(table, rng)
    want = np.searchsorted(table, queries, side="left")
    got = row_search(jnp.asarray(table), jnp.asarray(queries))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
    rows = fr.row_table(jnp.asarray(table))
    # the host's tree is the device's, and holds the table first
    np.testing.assert_array_equal(fr.row_table(table), np.asarray(rows))
    np.testing.assert_array_equal(
        np.asarray(rows).reshape(-1)[:n], table)
    assert rows.shape == (sum(c for _f, c in fr.row_plan(n)), _F)
    other = np.sort(np.roll(table, n // 2) // 2 + 1).astype(np.int32)
    both = jax.vmap(row_search, in_axes=(0, None))(
        jnp.asarray(np.stack([table, other])), jnp.asarray(queries))
    np.testing.assert_array_equal(np.asarray(both[0]), want)
    np.testing.assert_array_equal(
        np.asarray(both[1]),
        np.searchsorted(other, queries, side="left"))


def _ranks_by_cumsum(mask):
    """``mask_ranks`` as it was: the ranks themselves."""
    ranks = jnp.cumsum(mask.astype(jnp.int32))
    return ranks, ranks[-1]


def _pick_by_binary(ranks, labels, capacity):
    """``pick_queue`` as it was: a binary search a queue slot."""
    vpad = ranks.shape[0]
    want = jnp.arange(capacity, dtype=jnp.int32) + 1
    ids = jnp.searchsorted(ranks, want, side="left").astype(jnp.int32)
    ids = jnp.where(want <= ranks[-1], ids, vpad)
    return ids, jnp.take(labels, jnp.minimum(ids, vpad - 1), axis=0)


def _extents_by_binary(ids, src_ids, src_off, nv):
    """``frontier_extents`` as it was, over the sorted ids [S]."""
    S = src_off.shape[0] - 1
    pos = jnp.searchsorted(src_ids, ids, side="left")
    posc = jnp.minimum(pos, S - 1).astype(jnp.int32)
    present = (jnp.take(src_ids, posc, axis=0) == ids) & (ids < nv)
    begin = jnp.where(present, jnp.take(src_off, posc, axis=0), 0)
    end = jnp.where(present, jnp.take(src_off, posc + 1, axis=0), 0)
    off = jnp.cumsum((end - begin).astype(jnp.int32))
    return begin, off, off[-1]


def _extents_by_binary_in_tree(ids, rows, src_off, nv):
    """The same over the engine's ``row_table``, whose first entries
    are the sorted ids."""
    return _extents_by_binary(
        ids, rows.reshape(-1)[:src_off.shape[0] - 1], src_off, nv)


@pytest.mark.parametrize("density", [0.0, 0.002, 0.05, 1.0])
@pytest.mark.parametrize("capacity", [37, 300])   # both queue rungs
def test_queue_stage_is_its_binary_search_forms(capacity, density):
    """``pick_queue`` and ``frontier_extents`` against the
    ``jnp.searchsorted`` forms they replaced, bit for bit, on a lower
    and a top queue rung: masks from empty to full (a count under and
    over the queue), labels at both ends of int32, ids absent from
    the source index, invalid (``nv``) and unsorted."""
    rng = np.random.default_rng(capacity + int(1000 * density))
    vpad, nv = 5000, 4990
    mask = rng.random(vpad) < density
    labels = _label_cases(np.int32, vpad, rng)
    ranks, count = _ranks_by_cumsum(jnp.asarray(mask))
    rows, got_count = fr.mask_ranks(jnp.asarray(mask))
    assert int(got_count) == int(count) == int(mask.sum())
    want = _pick_by_binary(ranks, jnp.asarray(labels), capacity)
    got = fr.pick_queue(rows, jnp.asarray(labels), capacity)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a compressed source index: two thirds of the vertices have
    # out-edges here; the queue holds sources, strangers and pads
    deg = np.where(rng.random(nv) < 0.66, rng.integers(1, 9, nv), 0)
    sids, soff = _compress(np.concatenate([[0], np.cumsum(deg)]))
    S = sids.shape[0] + 7                          # a padded tail
    sids = jnp.concatenate([sids, jnp.full((7,), nv, jnp.int32)])
    soff = jnp.concatenate([soff, jnp.full((7,), soff[-1], jnp.int32)])
    gids = np.where(np.asarray(got[0]) < vpad,
                    np.minimum(np.asarray(got[0]), nv), nv)
    gids = jnp.asarray(rng.permutation(gids).astype(np.int32))
    want = _extents_by_binary(gids, sids, soff, nv)
    assert soff.shape[0] == S + 1
    for a, b in zip(fr.frontier_extents(gids, fr.row_table(sids), soff,
                                        nv), want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_BUCKET_COUNTS = ("advances", "front_edges", "front_vertices",
                  "edge_dense_iters")


@pytest.mark.parametrize("parts", [1, 2, 4])
@pytest.mark.parametrize("kind", ["delta-int32", "delta-float32",
                                  "bfs-two-way", "components"])
def test_engine_answers_and_counters_are_the_binary_search_forms(
        kind, parts, monkeypatch):
    """The same small graph through the engine as it is and through
    one whose queue stage runs the binary searches it ran (patched in
    before the build), on 1, 2 and 4 parts of the CPU mesh: labels bit
    for bit, the same ``iters``, rung counts and slot fills (the queue
    a trip builds is the same queue), the bucket loop's counts on the
    delta engines and ``pull_iters`` on the BFS's bottom-up step,
    with sparse iterations on BOTH queue rungs."""
    from lux_tpu import telemetry
    from lux_tpu.engine.push import PushEngine
    from lux_tpu.graph import ShardedGraph
    from lux_tpu.parallel.mesh import make_mesh
    rng = np.random.default_rng(48)
    nv, ne = 6000, 9000
    src, dst = rng.integers(0, nv, ne), rng.integers(0, nv, ne)
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    half = {"delta-int32": rng.integers(1, 1000, ne).astype(np.int32),
            "delta-float32": rng.random(ne).astype(np.float32)}.get(kind)
    w = None if half is None else np.concatenate([half, half])
    g = Graph.from_edges(src, dst, nv, weights=w)
    prog = components.make_program() if kind == "components" \
        else sssp.make_program(0, w is not None) if w is None \
        else sssp.make_program(0, True, sssp.distance_dtype(w))
    delta = None if w is None else sssp.default_delta(g) / 4
    if kind == "delta-int32":
        delta = int(delta)
    sg = ShardedGraph.build(g, parts)
    mesh = make_mesh(parts) if parts > 1 else None
    keys = _COUNTS + ("pull_iters",) + \
        (_BUCKET_COUNTS if delta is not None else ())

    def solve():
        eng = PushEngine(sg, prog, mesh=mesh, delta=delta)
        assert len(eng.queue_rungs) == 2
        label, _active, _it = eng.converge(*eng.init_state())
        mark = [r for r in telemetry.spans()
                if r["name"] == "push.converge"][-1]["counts"]
        return eng, eng.unpad(label), {k: mark[k] for k in keys}

    eng, got, counts = solve()
    assert eng.arrays["src_ids"].ndim == 3          # [P, T, F] trees
    monkeypatch.setattr(fr, "mask_ranks", _ranks_by_cumsum)
    monkeypatch.setattr(fr, "pick_queue", _pick_by_binary)
    monkeypatch.setattr(fr, "frontier_extents",
                        _extents_by_binary_in_tree)
    _eng, want, want_counts = solve()
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got.view(np.uint32),
                                  want.view(np.uint32))
    assert counts == want_counts
    assert 0 < counts["sparse_iters"]
    assert 0 < counts["queue_items"] <= counts["queue_slots"]
    # both queue rungs ran: slots of the low rung alone would be a
    # multiple of it
    low, top = eng.queue_rungs
    assert counts["queue_slots"] % (low * parts) or \
        counts["queue_slots"] // (low * parts) > counts["sparse_iters"]
    if kind == "bfs-two-way":
        assert eng.pull and counts["pull_iters"] > 0
    if kind == "components":
        np.testing.assert_array_equal(
            got, components.reference_components(g))
    else:
        ref = sssp.reference_sssp(g, 0, weighted=w is not None)
        if kind == "delta-float32":
            np.testing.assert_allclose(got, ref, rtol=1e-5)
        else:
            np.testing.assert_array_equal(got.astype(np.int64), ref)
