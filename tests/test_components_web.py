"""The unbatched components engine on a generated web-shape graph
(the cell ``cc.indochina`` at small sizes: directed, pair relabel on,
sparse view on) against the benchmark's plain reference, and the
ladder's fill counts (``queue_items`` / ``queue_slots`` /
``budget_edges`` / ``budget_slots`` on the ``push.converge`` mark)
against graphs small enough to count by hand and against
``converge_stats``' per-iteration series under the rung rule."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import components as ref
from benchmarks.reference import webgraph
from lux_tpu import telemetry
from lux_tpu.apps import components, sssp
from lux_tpu.engine import frontier as fr
from lux_tpu.graph import Graph, ShardedGraph, pair_relabel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "indochina-components.json")) as _f:
    CONFIG = json.load(_f)
SHAPE = {k: CONFIG[k] for k in webgraph.PARAMETERS}
ENGINE = CONFIG["engine"]
FILLS = ("queue_items", "queue_slots", "budget_edges", "budget_slots")
# a crawl whose solve runs dense iterations, then sparse ones on both
# queue rungs and both budget rungs (asserted below)
WEB_NV, WEB_ARCS, WEB_SEED = 10000, 261800, 16


def _last_mark():
    return [r for r in telemetry.spans()
            if r["name"] == "push.converge"][-1]["counts"]


@functools.lru_cache(maxsize=None)
def _web():
    src, dst = webgraph.web_arcs(WEB_NV, WEB_ARCS, WEB_SEED, **SHAPE)
    return src, dst


def _engine(app, num_parts, **kw):
    """Built as benchmarks/runners/batch_components.py builds the
    cell's: degree-relabelled, the configuration's engine options."""
    src, dst = _web()
    g = Graph.from_edges(src, dst, WEB_NV)
    g_run, perm, starts = pair_relabel(
        g, num_parts, pair_threshold=ENGINE["pair_threshold"])
    sg = ShardedGraph.build(g_run, num_parts, starts=starts,
                            pair_threshold=ENGINE["pair_threshold"])
    return app.build_engine(g_run, num_parts=num_parts, sg=sg,
                            **ENGINE, **kw), perm


@pytest.mark.parametrize("num_parts", [1, 2])
def test_components_on_a_web_graph_equal_the_reference(num_parts):
    eng, perm = _engine(components, num_parts)
    assert not eng.pull                     # directed: no bottom-up step
    label, _active, it = eng.converge(*eng.init_state())
    mark = _last_mark()
    got = np.empty(WEB_NV, np.int64)
    got[perm] = eng.unpad(label)            # by the generator's ids
    rank = np.empty(WEB_NV, np.int64)
    rank[ref.check_permutation(perm, WEB_NV)] = np.arange(WEB_NV)
    offsets, by_src = webgraph.by_destination(*_web(), WEB_NV)
    want, _sweeps = ref.fixed_point(offsets, by_src, rank)
    assert ref.mismatched(got, want) == 0
    assert len(np.unique(want)) > 10        # no one-label answer
    assert 0 < mark["sparse_iters"] < mark["iters"] == int(it)
    assert 0 < mark["queue_items"] <= mark["queue_slots"]
    assert 0 < mark["budget_edges"] <= mark["budget_slots"]
    # dense iterations, then sparse ones below and on the top budget,
    # and on both queue rungs: more slots than the low rung alone,
    # fewer than the top alone
    assert 0 < mark["low_rung_iters"] < mark["sparse_iters"]
    q0, q1 = eng.queue_rungs
    assert q0 * num_parts * mark["sparse_iters"] < mark["queue_slots"] \
        < q1 * num_parts * mark["sparse_iters"]


def _series_counts(eng, enter, edges):
    """The four counts the ladder's rule gives for a run on ONE part
    whose iterations were entered by ``enter`` vertices with ``edges``
    out-edges."""
    _usable, limit, _pull = eng._sparse_mode()
    want = dict.fromkeys(FILLS, 0)
    n_sparse = 0
    for count, total in zip(enter, edges):
        if count > limit:
            continue
        n_sparse += 1
        want["queue_items"] += count
        want["queue_slots"] += next(
            r for r in eng.queue_rungs if count <= r)
        want["budget_edges"] += min(total, eng.budget_rungs[-1])
        want["budget_slots"] += next(
            (r for r in eng.budget_rungs if total <= r),
            eng.budget_rungs[-1])
    return want, n_sparse


@pytest.mark.parametrize("reduce", ["min", "max"])
def test_the_counts_are_what_the_loop_did(reduce):
    """``converge``'s counts against ``converge_stats``' per-iteration
    frontier sizes and out-edge totals under the rung rule."""
    if reduce == "max":
        eng, _perm = _engine(components, 1)
        first = WEB_NV
    else:
        eng, perm = _engine(sssp, 1, start_vertex=0, weighted=False)
        first = 1
    _l, _a, it, fsz, fed, _fp, _ep = eng.converge_stats(
        *eng.init_state())
    it = int(it)
    enter = [first] + np.asarray(fsz)[:it - 1].tolist()
    edges = np.asarray(fed)[:it].tolist()
    want, n_sparse = _series_counts(eng, enter, edges)
    assert 0 < n_sparse
    for variant in ("converge", "converge_stats"):
        out = getattr(eng, variant)(*eng.init_state())
        mark = _last_mark()
        assert int(out[2]) == it == mark["iters"]
        assert mark["sparse_iters"] == n_sparse
        assert {k: mark[k] for k in FILLS} == want, variant


def _path_engine(reduce, num_parts):
    """A directed path of 40 vertices and its one-vertex start: BFS
    from the tail for ``min``; for ``max`` the arcs run from the
    highest id down and only the highest starts active."""
    n = 40
    a, b = np.arange(n - 1), np.arange(1, n)
    if reduce == "min":
        g = Graph.from_edges(a, b, n)
        eng = sssp.build_engine(g, start_vertex=0,
                                num_parts=num_parts, weighted=False)
        return eng, eng.init_state(), n
    g = Graph.from_edges(b, a, n)
    eng = components.build_engine(g, num_parts=num_parts)
    active = np.zeros(n, bool)
    active[n - 1] = True
    state = eng.place(eng.sg.to_padded(np.arange(n, dtype=np.int32)),
                      eng.sg.to_padded(active))
    return eng, state, n


@pytest.mark.parametrize("num_parts", [1, 2])
@pytest.mark.parametrize("reduce", ["min", "max"])
def test_a_path_is_one_item_and_one_edge_a_sparse_iteration(
        reduce, num_parts):
    eng, state, n = _path_engine(reduce, num_parts)
    label, _a, it = eng.converge(*state)
    mark = _last_mark()
    # n - 1 hops and the iteration that finds the last vertex without
    # an out-edge; every one sparse, on the lowest rungs
    assert int(it) == n == mark["sparse_iters"] == mark["low_rung_iters"]
    assert (mark["queue_items"], mark["budget_edges"]) == (n, n - 1)
    assert mark["queue_slots"] == n * eng.queue_rungs[0] * num_parts
    assert mark["budget_slots"] == n * eng.budget_rungs[0] * num_parts
    want = np.arange(n) if reduce == "min" else np.full(n, n - 1)
    np.testing.assert_array_equal(eng.unpad(label), want)


def test_no_ladder_no_counts():
    """A dense-only engine and a batched engine have no ladder: the
    four counts are plain zeros on their marks."""
    src, dst = _web()
    g = Graph.from_edges(src[:40000], dst[:40000], WEB_NV)
    for eng in (components.build_engine(g, enable_sparse=False),
                components.build_engine(g, sources=[3, 5])):
        eng.converge(*eng.init_state())
        raw = telemetry._RING[-1]["counts"]
        assert all(raw[k] == 0 and type(raw[k]) is int for k in FILLS)
        assert _last_mark()["sparse_iters"] == 0


def test_a_dense_only_run_of_a_ladder_engine_counts_nothing():
    eng, _perm = _engine(components, 1)
    eng.converge(*eng.init_state(), max_iters=1)    # all active: dense
    mark = _last_mark()
    assert (mark["iters"], mark["sparse_iters"]) == (1, 0)
    assert [mark[k] for k in FILLS] == [0, 0, 0, 0]


def test_wide_add_carries_past_two_to_the_32():
    """The carry's two-word sum: 400 iterations on a 12.1 M-slot rung
    (the cell's top budget) pass 2^32 and fold back exactly."""
    per_iter, iters = 12_131_832, 400
    assert per_iter * iters > 2 ** 32

    def body(_i, acc):
        return fr.wide_add(*acc, jnp.asarray(
            [per_iter, 1, 0, 2 ** 32 - 1], jnp.uint32))

    zeros = jnp.zeros((4,), jnp.uint32)
    words = jax.jit(lambda: jnp.stack(jax.lax.fori_loop(
        0, iters, body, (zeros, zeros)), axis=-1))()
    got = [fr.Folded(words, i).item() for i in range(4)]
    assert got == [per_iter * iters, iters, 0, (2 ** 32 - 1) * iters]
    assert all(type(v) is int for v in got)
    assert fr.Folded(words, 0).is_ready()
