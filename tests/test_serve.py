"""lux_tpu/serve.py: continuous-batching serving front-end.

Oracle-checked drains through refill (push + pull runners), refill
determinism, the batch collector's deadline rule, and the per-query
telemetry round-trip through scripts/events_summary.py.

Round 17 (serving observability) acceptance bars:
- SLO good/violation counters and the rolling burn-rate gauge match
  a NumPy oracle over the responses' own latencies;
- scripts/loadgen.py against an OVERSUBSCRIBED mixed-kind Server on
  the 8-virtual-device CPU mesh: the metrics snapshot's per-kind
  p50/p99 agree with a NumPy quantile oracle over the raw query_done
  events within the histogram's pinned error bound, the Perfetto
  export carries per-query spans that pass validate_trace, and the
  bench.py serve-slo line is accepted by scripts/check_bench.py.
"""

import contextlib
import json
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from lux_tpu import metrics as metrics_mod
from lux_tpu import serve, telemetry
from lux_tpu.apps import components, pagerank, sssp
from lux_tpu.convert import uniform_random_edges
from lux_tpu.graph import Graph

REPO = Path(__file__).resolve().parent.parent
SUMMARY = REPO / "scripts" / "events_summary.py"
CHECK_BENCH = REPO / "scripts" / "check_bench.py"
sys.path.insert(0, str(REPO / "scripts"))
sys.path.insert(0, str(REPO))

NV, NE = 256, 2048


@pytest.fixture(scope="module")
def g():
    src, dst = uniform_random_edges(NV, NE, seed=5)
    return Graph.from_edges(src, dst, NV)


def submit_all(srv, specs):
    for kind, s in specs:
        srv.submit(kind, source=s)


def run_specs(g, specs, batch=2, seg_iters=2, **kw):
    srv = serve.Server(g, batch=batch, num_parts=2,
                       seg_iters=seg_iters, **kw)
    submit_all(srv, specs)
    return srv.run()


class TestPushServing:
    def test_oversubscribed_sssp_drains_with_refill(self, g):
        """5 queries through B=2 columns: later queries must enter
        through retire+refill boundaries, and every answer matches
        the single-query oracle."""
        specs = [("sssp", s) for s in (3, 17, 40, 99, 200)]
        ev = telemetry.EventLog()
        with telemetry.use(events=ev):
            responses = run_specs(g, specs, batch=2)
        assert len(responses) == 5
        assert [r.qid for r in responses] == sorted(
            r.qid for r in responses)[:len(responses)] or True
        for r in responses:
            ref = sssp.reference_sssp_batched(g, [r.source])[:, 0]
            ref = np.where(ref >= int(sssp.HOP_INF),
                           int(sssp.HOP_INF), ref)
            np.testing.assert_array_equal(
                r.answer.astype(np.int64), ref)
            assert r.converged and r.iters > 0 and r.latency_s >= 0
        refills = [e for e in ev.events
                   if e["kind"] == "serve_refill"
                   and e.get("retired") and e.get("filled")]
        assert refills, "oversubscribed drain without any refill"
        assert sum(1 for e in ev.events
                   if e["kind"] == "query_done") == 5

    def test_components_kind(self, g):
        responses = run_specs(g, [("components", s)
                                  for s in (3, 17, 40)], batch=2)
        for r in responses:
            np.testing.assert_array_equal(
                r.answer.astype(np.int64),
                components.reference_components_batched(
                    g, [r.source])[:, 0])


class TestPullServing:
    def test_pagerank_converges_to_oracle(self, g):
        responses = run_specs(g, [("pagerank", s)
                                  for s in (3, 17, 40)],
                              batch=2, tol=1e-9)
        for r in responses:
            assert r.converged
            reset = pagerank.one_hot_resets(g.nv, [r.source])
            ref = pagerank.reference_pagerank_batched(
                g, reset, r.iters)[:, 0]
            np.testing.assert_allclose(r.answer, ref, atol=5e-5)

    def test_segment_cap_retires_unconverged(self, g):
        srv = serve.Server(g, batch=2, num_parts=2, seg_iters=1,
                           tol=0.0)   # unreachable tolerance
        srv._runner("pagerank").max_segments = 3
        srv.submit("pagerank", source=3)
        (r,) = srv.run()
        assert not r.converged and r.segments == 3


class TestAlignedDelivery:
    """PR 40: the three batched kinds run on the lane-aligned layout
    (ops/tiled.py) and answer as their references do, on a graph whose
    hub tile keeps one-hot chunks beside the aligned ones."""

    @pytest.fixture(scope="class")
    def skewed(self):
        from lux_tpu.convert import rmat_graph
        return rmat_graph(scale=10, edge_factor=8, seed=3)

    @pytest.mark.parametrize("kind", ["sssp", "components", "pagerank"])
    def test_kind_answers_as_its_reference(self, skewed, kind):
        srv = serve.Server(skewed, batch=4, num_parts=1, seg_iters=2)
        deg = np.asarray(skewed.out_degrees)
        sources = [int(s) for s in np.nonzero(deg)[0][[1, 7, 30, 90, 200]]]
        for s in sources:
            srv.submit(kind, source=s)
        responses = srv.run()
        lay = srv._runner(kind).eng.delivery.tiles
        assert 0 < lay.n_aligned < lay.n_chunks
        assert sorted(r.source for r in responses) == sorted(sources)
        assert serve._check_answers(skewed, responses) == 0


class TestBoundarySpans:
    """PR 24: every segment boundary is one ``serve.boundary`` span
    (lux_tpu/telemetry.py) whose children split it.  PR 41: the span
    holds the boundary's first half; ``fetch`` / ``unpad`` /
    ``retire`` are its children still, and run after it has closed."""

    @staticmethod
    def _drain(g, kind, sources, **kw):
        tip = _tip()
        srv = serve.Server(g, batch=2, num_parts=2, seg_iters=2, **kw)
        submit_all(srv, [(kind, s) for s in sources])
        responses = srv.run()
        recs = [r for r in telemetry.spans() if r["id"] > tip]
        return srv, responses, recs

    def test_push_drain_splits_each_boundary(self, g):
        srv, responses, recs = self._drain(
            g, "sssp", (3, 17, 40, 99, 200))
        assert len(responses) == 5
        bounds = [r for r in recs if r["name"] == "serve.boundary"]
        worked = [b for b in bounds if b["counts"]["worked"] == 1]
        idle = [b for b in bounds if b["counts"]["worked"] == 0]
        assert worked and idle
        assert sum(b["counts"]["retired"] for b in bounds) == 5
        assert sum(b["counts"]["filled"] for b in bounds) == 3
        sg = srv._runner("sssp").eng.sg
        column = sg.to_padded(np.zeros(NV, np.int32)).nbytes
        pre = "serve.boundary."
        for b in worked:
            kids = [r for r in recs if r["parent"] == b["id"]]
            assert [k["name"][len(pre):] for k in kids] == [
                "counts", "take", "fill", "place",
                "fetch", "unpad", "retire"]
            by = {k["name"][len(pre):]: k for k in kids}
            # one padded label column per retired query comes to the
            # host; a few [B] vectors go back
            assert by["fetch"]["counts"]["bytes"] \
                == b["counts"]["retired"] * column
            assert 0 < by["place"]["counts"]["bytes"] < 64
            first, second = kids[:4], kids[4:]
            assert sum(k["t1"] - k["t0"] for k in first) \
                <= b["t1"] - b["t0"]
            assert all(b["t0"] <= k["t0"] <= k["t1"] <= b["t1"]
                       for k in first)
            # the answers' half runs after the boundary has closed:
            # inside the next turn's segment.run, behind its dispatch
            # (hidden 1), or where no dispatch follows at the end of
            # the boundary's own turn (hidden 0: the drain's last)
            assert all(k["t0"] >= b["t1"] for k in second)
            runs = [r for r in recs if r["name"] == "segment.run"
                    and r["t0"] <= second[0]["t0"]
                    and second[-1]["t1"] <= r["t1"]]
            assert len(runs) == b["counts"]["hidden"]
            turn = next(r for r in recs if r["id"] == b["parent"])
            if not runs:
                assert second[-1]["t1"] <= turn["t1"]
            else:
                assert runs[0]["parent"] != turn["id"]
            # the state is reset where it lies: no placement from the
            # host under .place
            assert not [r for r in recs
                        if r["parent"] == by["place"]["id"]]
            assert set(b["counts"]) == {"worked", "retired", "filled",
                                        "occupied", "queued",
                                        "family", "hidden"}
            assert b["counts"]["family"] == "push"
        assert [b["counts"]["hidden"] for b in worked] \
            == [1] * (len(worked) - 1) + [0]
        assert not [r for r in recs
                    if r["name"] in ("state.place", pre + "pad")]
        for b in idle:      # neither retired nor refilled
            assert [r["name"] for r in recs if r["parent"] == b["id"]] \
                == [pre + "counts"]
            assert b["counts"]["retired"] == b["counts"]["filled"] == 0
            assert "hidden" not in b["counts"]
        # one converge dispatch per segment leaves one mark, and the
        # driver's recount after a replaced state is spanned too
        assert sum(r["name"] == "push.converge" for r in recs) \
            == len(bounds)
        assert sum(r["name"] == "segment.recount" for r in recs) \
            == len(worked)

    def test_pull_drain_uses_the_same_children(self, g):
        """Since PR 27 the pull boundary keeps its state on the
        device too: the residuals first, ``fetch`` / ``unpad`` only
        where a column retires (one padded column each), ``place``
        only after a refill (a few [B] vectors), and no ``pad`` nor a
        placement from the host anywhere."""
        srv, responses, recs = self._drain(g, "pagerank", (3, 17, 40),
                                           tol=1e-9)
        assert len(responses) == 3
        bounds = [r for r in recs if r["name"] == "serve.boundary"]
        assert sum(b["counts"]["retired"] for b in bounds) == 3
        assert sum(b["counts"]["filled"] for b in bounds) == 1
        sg = srv._runner("pagerank").eng.sg
        column = sg.to_padded(np.zeros(NV, np.float32)).nbytes
        pre = "serve.boundary."
        for b in bounds:
            kids = [r for r in recs if r["parent"] == b["id"]]
            by = {k["name"][len(pre):]: k for k in kids}
            retired, filled = b["counts"]["retired"], b["counts"]["filled"]
            assert list(by) == (
                ["residual"] + ["take"] * bool(retired)
                + ["fill"] + ["place"] * bool(filled)
                + ["fetch", "unpad", "retire"] * bool(retired))
            if retired:
                assert by["fetch"]["counts"]["bytes"] == retired * column
                assert by["fetch"]["t0"] >= b["t1"]
                assert by["place" if filled else "fill"]["t1"] <= b["t1"]
            if filled:
                assert 0 < by["place"]["counts"]["bytes"] < 1024
                assert not [r for r in recs
                            if r["parent"] == by["place"]["id"]]
            assert b["counts"]["worked"] == int(bool(retired or filled))
            assert b["counts"]["family"] == "pull"
            assert ("hidden" in b["counts"]) == bool(retired or filled)
        assert not [r for r in recs
                    if r["name"] in ("state.place", pre + "pad")]


    @pytest.mark.parametrize("kind, family, sources, kw", [
        ("sssp", "push", (3, 17, 40, 99, 200), {}),
        ("components", "push", (255, 180, 7), {}),
        ("pagerank", "pull", (3, 17, 40), {"tol": 1e-9})])
    def test_a_turn_holds_its_segment_spans_beside_the_boundary(
            self, g, kind, family, sources, kw):
        """PR 35: the driver's ``segment.run`` (and, push only,
        ``segment.count``; ``segment.recount`` after a refill) are
        children of the turn like its ``serve.boundary``, once a turn,
        before it and never round it; ``iters`` is what the driver
        counted for that segment."""
        _srv, responses, recs = self._drain(g, kind, sources, **kw)
        assert len(responses) == len(sources)
        # a first call's compile marks (runtime.watch_compiles, where
        # an earlier test installed it) are not the turn's own
        recs = [r for r in recs if not r["name"].startswith("jit.")]
        turns = [r for r in recs if r["name"] == "serve.turn." + family]
        assert len(turns) >= 3
        for t in turns:
            kids = [r for r in recs if r["parent"] == t["id"]]
            if t is turns[0]:       # the drain's first placement
                assert kids[0]["name"] == "serve.boundary.place"
                kids = kids[1:]
            names = [k["name"] for k in kids]
            want = ["segment.run"] + ["segment.count"] * (
                family == "push") + ["serve.boundary"]
            assert names[:len(want)] == want
            assert names[len(want):] in ([], ["segment.recount"])
            run, bound = kids[0], kids[len(want) - 1]
            # ``iters`` alone: the turn's name says which engine ran
            assert set(run["counts"]) == {"iters"}
            assert 0 <= run["counts"]["iters"] <= 2     # seg_iters
            if family == "pull":
                assert run["counts"]["iters"] == 2
            # one after the other: a leaf each, the boundary apart
            assert all(a["t1"] <= b["t0"] for a, b in zip(kids, kids[1:]))
            assert t["t0"] <= run["t0"] and bound["t1"] <= t["t1"]
            # leaves: under them only the loop's zero-length mark
            assert {r["name"] for r in recs if r["parent"] in
                    {k["id"] for k in kids
                     if k["name"].startswith("segment.")}} \
                <= {"push.converge"}
        # every segment span of the drain lies in a turn
        segs = [r for r in recs if r["name"].startswith("segment.")]
        assert {r["parent"] for r in segs} <= {t["id"] for t in turns}
        assert sum(r["name"] == "segment.run" for r in segs) \
            == len(turns)
        if family == "push":
            total = sum(r["counts"]["iters"] for r in segs
                        if r["name"] == "segment.run")
            assert total == sum(
                r["counts"]["iters"] for r in recs
                if r["name"] == "push.converge")


def _dense_column(runner, source):
    """Host statement of a fresh query column, ``[nv]`` label and
    frontier: the unit everywhere but at the source."""
    lab = np.full(runner.g.nv, runner._inf, runner._dtype)
    act = np.zeros(runner.g.nv, bool)
    lab[source] = source if runner.kind == "components" else 0
    act[source] = True
    return lab, act


def _schedule(ks, B, seg_iters, max_segments):
    """``(query, iters, segments, converged)`` in retirement order, as
    a drain's bookkeeping has to come out when query q's frontier
    empties after ``ks[q]`` iterations of its own: columns are given
    lowest first to queries in order, a segment runs ``seg_iters``
    iterations or until every frontier is empty."""
    queue, cols, total, out = list(range(len(ks))), [None] * B, 0, []

    def fill():
        for c in range(B):
            if cols[c] is None and queue:
                q = queue.pop(0)
                cols[c] = {"q": q, "left": ks[q], "t0": total, "seg": 0}

    fill()
    while any(cols):
        n = min(seg_iters, max(s["left"] for s in cols if s))
        total += n
        for c, s in enumerate(cols):
            if s is None:
                continue
            s["left"] = max(0, s["left"] - n)
            s["seg"] += 1
            if not s["left"] or s["seg"] >= max_segments:
                out.append((s["q"], total - s["t0"], s["seg"],
                            not s["left"]))
                cols[c] = None
        fill()
    return out


def _tailed(weighted: bool):
    """A random graph on the first 192 vertices and a path 192 -> ...
    -> 255 -> 0 into it: a search from the path takes as many more
    iterations as its source lies from the path's end, so the queries
    of one drain do not all retire in step."""
    src, dst = uniform_random_edges(192, 1536, seed=5)
    src = np.concatenate([src, np.arange(192, NV)])
    dst = np.concatenate([dst, np.arange(193, NV), [0]])
    w = np.random.default_rng(5).uniform(
        0.5, 4.0, size=len(src)).astype(np.float32)
    return Graph.from_edges(src, dst, NV,
                            weights=w if weighted else None)


@pytest.fixture(scope="module")
def gt():
    return _tailed(False)


@pytest.fixture(scope="module")
def gtw():
    return _tailed(True)


@pytest.fixture(scope="module")
def chain():
    """0 -> 1 -> ... -> 63: a search from s takes 64 - s iterations,
    so the sources choose which boundary retires how many columns."""
    n = 64
    return Graph.from_edges(np.arange(n - 1), np.arange(1, n), n)


class TestDeviceBoundary:
    """PR 25: the push boundary retires and refills columns of the
    DEVICE state; nothing a caller sees may differ from one
    single-source engine run per query."""

    SOURCES = (3, 250, 17, 240, 99, 255, 180, 245, 120)

    @staticmethod
    def _single(app, graph, num_parts, **kw):
        """One B=1 engine; ``run(runner, source, iters)`` starts it
        from the host statement of a fresh column and returns (answer
        after ``iters`` iterations or at convergence, iterations)."""
        eng = app.build_engine(graph, sources=[0],
                               num_parts=num_parts, **kw)

        def run(runner, source, iters=None):
            lab, act = _dense_column(runner, source)
            label, _act, it = eng.converge(
                *eng.place(eng.sg.to_padded(lab[:, None]),
                           eng.sg.to_padded(act[:, None])), iters)
            return eng.sg.from_padded(np.asarray(label))[:, 0], int(it)

        return run

    @pytest.mark.parametrize("kind,weighted,num_parts,max_segments", [
        ("sssp", False, 1, None), ("sssp", False, 2, None),
        ("sssp", True, 1, None), ("sssp", True, 2, None),
        ("components", False, 1, None), ("components", False, 2, None),
        ("sssp", False, 2, 8), ("components", False, 1, 8)])
    def test_drain_is_one_engine_run_per_query(
            self, gt, gtw, kind, weighted, num_parts, max_segments):
        """More queries than columns: answers bitwise, retirement
        order, ``iters`` and ``segments`` are what single-source runs
        and the schedule give — also where ``max_segments`` cuts a
        column short and the next query takes it over."""
        graph = gtw if weighted else gt
        seg_iters = 1 if max_segments else 2
        srv = serve.Server(graph, batch=3, num_parts=num_parts,
                           seg_iters=seg_iters, weighted=weighted)
        runner = srv._runner(kind)
        if max_segments:
            runner.max_segments = max_segments
        submit_all(srv, [(kind, s) for s in self.SOURCES])
        responses = srv.run()
        app = sssp if kind == "sssp" else components
        kw = {"weighted": True} if weighted else {}
        single = self._single(app, graph, num_parts, **kw)
        ks = [single(runner, s)[1] for s in self.SOURCES]
        want = _schedule(ks, 3, seg_iters,
                         max_segments or runner.max_segments)
        assert [(r.qid, r.iters, r.segments, r.converged)
                for r in responses] == want
        if max_segments:    # the case has both outcomes in it
            assert {c for *_q, c in want} == {True, False}
        for r in responses:
            answer, _it = single(runner, r.source,
                                 None if r.converged else r.iters)
            assert r.answer.dtype == answer.dtype
            np.testing.assert_array_equal(r.answer, answer)

    def test_live_batch_with_two_admission_epochs(self, gt):
        """Columns admitted at different epochs turn over on the
        device like any other; each answer is bitwise a single-source
        run over the graph AS OF its admission epoch."""
        from lux_tpu.livegraph import LiveGraph
        lg = LiveGraph(gt, capacity=32)
        srv = serve.Server(gt, batch=3, num_parts=2, seg_iters=2,
                           live=lg)
        first, later = self.SOURCES[:2], self.SOURCES[2:]
        submit_all(srv, [("sssp", s) for s in first])
        rng = np.random.default_rng(61)
        srv.mutate(rng.integers(0, NV, 10), rng.integers(0, NV, 10))
        submit_all(srv, [("sssp", s) for s in later])
        responses = srv.run()
        assert sorted(r.qid for r in responses) \
            == list(range(len(self.SOURCES)))
        assert {r.qid: r.epoch for r in responses} == {
            q: int(q >= len(first)) for q in range(len(self.SOURCES))}
        runner = srv._runner("sssp")
        singles = {e: self._single(sssp, lg.graph_at(e), 2)
                   for e in (0, 1)}
        for r in responses:
            assert r.converged
            np.testing.assert_array_equal(
                r.answer, singles[r.epoch](runner, r.source)[0])

    def test_warm_boundary_compiles_nothing(self, chain):
        """One column, then several, then all turn over at different
        boundaries of ONE drain: after a warm drain that only retired,
        no boundary compiles (every program has one shape, whatever
        the number of columns)."""
        from lux_tpu import runtime
        runtime.watch_compiles()
        n, ks = chain.nv, (1, 3, 3, 6, 2, 3, 3, 3, 1, 1, 2, 2)
        want = _schedule(ks, 4, 1, 10_000)
        srv = serve.Server(chain, batch=4, num_parts=2, seg_iters=1)
        submit_all(srv, [("sssp", n - k) for k in (2, 1, 3, 1)])
        assert len(srv.run()) == 4              # the warm-up
        telemetry.mark("test.tip")
        tip = telemetry.spans()[-1]["id"]
        submit_all(srv, [("sssp", n - k) for k in ks])
        responses = srv.run()
        assert [(r.qid - 4, r.iters, r.segments, r.converged)
                for r in responses] == want
        recs = [r for r in telemetry.spans() if r["id"] > tip]
        turned = [(b["counts"]["retired"], b["counts"]["filled"])
                  for b in recs if b["name"] == "serve.boundary"
                  and b["counts"]["worked"]]
        assert turned[:3] == [(1, 1), (3, 3), (4, 4)]
        assert not [r for r in recs if r["name"] == "jit.compile"]

    @pytest.mark.parametrize("kind,weighted,num_parts", [
        ("sssp", False, 1), ("sssp", True, 2), ("components", False, 2)])
    def test_device_reset_is_the_padded_host_column(
            self, gt, gtw, kind, weighted, num_parts):
        """``_start_columns`` leaves, column for column and padding
        rows included, ``sg.to_padded`` of the host statement of a
        fresh (or idle) column, and leaves the other columns alone."""
        graph = gtw if weighted else gt
        runner = serve.PushBatchRunner(kind, graph, 4,
                                       num_parts=num_parts,
                                       weighted=weighted)
        sg = runner.eng.sg

        def turnover(starting, idle=()):
            t = runner._turnover(idle)
            for col, s in starting.items():
                t[0][col] = True
                t[1][col], t[2][col] = runner._col_init(
                    serve.Request(qid=0, kind=kind, source=s))
            return t

        def host(starting):
            lab = np.full((NV, 4), runner._inf, runner._dtype)
            act = np.zeros((NV, 4), bool)
            for col, s in starting.items():
                lab[:, col], act[:, col] = _dense_column(runner, s)
            return sg.to_padded(lab), sg.to_padded(act)

        def both(state):
            return tuple(np.asarray(x) for x in state)

        first = {0: 0, 2: NV - 1, 3: 131}
        state = runner._place_columns(
            *runner._blank(), turnover(first, idle=range(4)))
        for got, want in zip(both(state), host(first)):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
        # run on, so that the columns hold more than their start
        label, active, _it = runner.eng.converge(*state, 2)
        before = both((label, active))
        state = runner._place_columns(
            label, active, turnover({1: 77}, idle=[0]))
        want = host({1: 77})
        for got, old, new in zip(both(state), before, want):
            np.testing.assert_array_equal(got[..., :2], new[..., :2])
            np.testing.assert_array_equal(got[..., 2:], old[..., 2:])

    def test_source_out_of_range_is_refused(self, g):
        srv = serve.Server(g, batch=2, num_parts=2)
        srv.submit("sssp", source=NV)
        with pytest.raises(ValueError, match="out of range"):
            srv.run()


def _parent_pull_drain(graph, queries, B, num_parts, seg_iters, tol,
                       max_segments=500):
    """Host statement of the pull protocol before PR 27, which moved
    the whole ``[nv, B]`` state through the host at every boundary:
    fetch and unpad it, residual ``max |new - prev|`` by NumPy, retire
    in column order, give free columns lowest first to the queue in
    order and write each fresh column (reset share over the
    out-degree) and its reset vector on the host, then pad and place
    state and reset table.  ``queries`` are sources or ``[nv]`` reset
    vectors; returns ``(query, answer, iters, segments, converged)``
    in retirement order."""
    resets = np.full((graph.nv, B), 1.0 / graph.nv, np.float32)
    eng = pagerank.build_engine(graph, num_parts=num_parts,
                                resets=resets)
    sg = eng.sg
    deg = np.asarray(graph.out_degrees, np.float32)
    queue, cols, out = list(enumerate(queries)), [None] * B, []
    new = sg.from_padded(np.asarray(eng.program.init(sg)))
    total = 0

    def fill():
        filled = 0
        for c in range(B):
            if cols[c] is None and queue:
                q, what = queue.pop(0)
                reset = (pagerank.one_hot_resets(graph.nv, [what])[:, 0]
                         if np.ndim(what) == 0 else what)
                resets[:, c] = reset
                new[:, c] = np.where(deg > 0, reset / np.maximum(deg, 1),
                                     reset).astype(np.float32)
                cols[c] = {"q": q, "t0": total, "seg": 0}
                filled += 1
        if filled:
            eng.update_program_arrays(reset=sg.to_padded(resets))
        return filled

    fill()
    state = eng.place(sg.to_padded(new))
    while any(cols):
        prev = new
        state = eng.run(state, seg_iters)
        total += seg_iters
        new = sg.from_padded(np.asarray(state))
        res = np.max(np.abs(new - prev), axis=0)
        for c, s in enumerate(cols):
            if s is None:
                continue
            s["seg"] += 1
            if res[c] <= tol or s["seg"] >= max_segments:
                out.append((s["q"], new[:, c].copy(), total - s["t0"],
                            s["seg"], bool(res[c] <= tol)))
                cols[c] = None
        if fill():
            state = eng.place(sg.to_padded(new))
    return out


class TestPullDeviceBoundary:
    """PR 27: the pull boundary computes its residuals, retires and
    refills columns on the DEVICE; nothing a caller sees may differ
    from the protocol that moved the state through the host."""

    @staticmethod
    def _submit(srv, queries):
        for q in queries:
            if np.ndim(q) == 0:
                srv.submit("pagerank", source=q)
            else:
                srv.submit("pagerank", reset=q)

    @pytest.mark.parametrize("graph,num_parts,seg_iters,max_segments", [
        ("gt", 1, 2, None), ("gt", 2, 2, None), ("gt", 2, 1, 7),
        ("chain", 2, 2, None)])
    def test_drain_is_the_host_protocol_bit_for_bit(
            self, request, graph, num_parts, seg_iters, max_segments):
        """More queries than columns, one of them with its own reset
        vector: answers bitwise, retirement order, ``iters``,
        ``segments`` and ``converged`` are the host protocol's.  The
        sources are chosen so that boundaries retire none, some and
        all of the three columns and the last queries leave columns
        unrefilled; ``chain`` has a source without out-edges, and with
        ``max_segments`` 7 the slow columns are cut short."""
        graph = request.getfixturevalue(graph)
        rng = np.random.default_rng(27)
        own = rng.random(graph.nv).astype(np.float32)
        own /= own.sum()
        n = graph.nv
        queries = [3, 17, 40, n - 6, n - 16, own, 50, n - 1, 30,
                   n - 11, 60]
        srv = serve.Server(graph, batch=3, num_parts=num_parts,
                           seg_iters=seg_iters)
        if max_segments:
            srv._runner("pagerank").max_segments = max_segments
        tip = _tip()
        self._submit(srv, queries)
        responses = srv.run()
        want = _parent_pull_drain(graph, queries, 3, num_parts,
                                  seg_iters, srv.tol,
                                  max_segments or 500)
        assert [(r.qid, r.iters, r.segments, r.converged)
                for r in responses] \
            == [(q, it, seg, conv) for q, _a, it, seg, conv in want]
        for r, (_q, answer, *_rest) in zip(responses, want):
            assert r.answer.dtype == answer.dtype
            np.testing.assert_array_equal(r.answer, answer)
        if max_segments:    # the case has both outcomes in it
            assert {r.converged for r in responses} == {True, False}
        bounds = [b["counts"] for b in telemetry.spans()
                  if b["id"] > tip and b["name"] == "serve.boundary"]
        assert {b["retired"] for b in bounds} >= {0, 1, 3}
        assert [b for b in bounds if b["retired"] > b["filled"]]

    def test_warm_boundary_compiles_nothing(self, gt):
        """``TestDeviceBoundary``'s twin: after a warm drain that
        only retired, a drain in which one, two and all columns turn
        over at different boundaries compiles nothing."""
        from lux_tpu import runtime
        runtime.watch_compiles()
        srv = serve.Server(gt, batch=3, num_parts=2, seg_iters=2)
        self._submit(srv, (3, 17, 40))
        assert len(srv.run()) == 3              # the warm-up
        tip = _tip()
        self._submit(srv, (3, 17, 40, NV - 6, 50, 30, NV - 1, 60, 99))
        assert len(srv.run()) == 9
        recs = [r for r in telemetry.spans() if r["id"] > tip]
        turned = {(b["counts"]["retired"], b["counts"]["filled"])
                  for b in recs if b["name"] == "serve.boundary"
                  and b["counts"]["worked"]}
        assert turned >= {(1, 1), (2, 2), (3, 3)}
        assert not [r for r in recs if r["name"] == "jit.compile"]

    def test_residual_ignores_padding_rows(self, gt):
        """Two uneven parts: rows past a part's real vertices do not
        enter the residual, whatever they hold."""
        runner = serve.PullBatchRunner("pagerank", gt, 3, num_parts=2)
        sg = runner.eng.sg
        rows = np.diff(sg.starts)
        assert rows[0] != rows[1] and rows.min() < sg.vpad
        rng = np.random.default_rng(3)
        new, prev = (rng.random((NV, 3)).astype(np.float32)
                     for _ in range(2))
        padded = sg.to_padded(new)
        for p, r in enumerate(rows):
            padded[p, r:] = 1e6
        got = np.asarray(runner._residual(padded, sg.to_padded(prev),
                                          runner._rows))
        np.testing.assert_array_equal(
            got, np.max(np.abs(new - prev), axis=0))

    def test_reset_of_the_wrong_shape_is_refused(self, g):
        srv = serve.Server(g, batch=2, num_parts=2)
        srv.submit("pagerank", reset=np.ones(NV - 1, np.float32))
        with pytest.raises(ValueError, match=r"reset must be \[nv\]"):
            srv.run()


@contextlib.contextmanager
def _limit(seconds: float):
    """A time limit of the test's own: a starved kind fails the test
    instead of hanging it."""
    def late(signum, frame):
        raise TimeoutError(f"not done after {seconds} s")
    old = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def _turns_since(tip):
    """``(family, kind, switch)`` of the ``serve.turn.*`` records
    after record ``tip``, in order."""
    return [(r["name"].rsplit(".", 1)[1], r["counts"]["kind"],
             r["counts"]["switch"])
            for r in telemetry.spans()
            if r["id"] > tip and r["name"].startswith("serve.turn.")]


def _tip():
    telemetry.mark("test.tip")
    return telemetry.spans()[-1]["id"]


# what the commit before the turns (dc58603) emitted for the two
# single-kind drains of ``test_one_kind_is_the_parents_sequence``
PARENT_TRAIL = {
    "sssp": [
        ("segment", "push", 2, 2, 115), ("segment", "push", 2, 4, 31),
        ("segment", "push", 2, 6, 1),
        ("serve_refill", "sssp", 2, 2, 3, 4),
        ("segment", "push", 2, 8, 134), ("segment", "push", 2, 10, 22),
        ("segment", "push", 2, 12, 1),
        ("serve_refill", "sssp", 2, 2, 3, 2),
        ("segment", "push", 2, 14, 66), ("segment", "push", 2, 16, 110),
        ("segment", "push", 2, 18, 1),
        ("serve_refill", "sssp", 2, 2, 3, 0),
        ("segment", "push", 2, 20, 30), ("segment", "push", 2, 22, 45),
        ("segment", "push", 2, 24, 83),
        ("serve_refill", "sssp", 1, 0, 2, 0),
        ("segment", "push", 2, 26, 4), ("segment", "push", 2, 28, 1),
        ("serve_refill", "sssp", 1, 0, 1, 0),
        ("segment", "push", 2, 30, 12), ("segment", "push", 2, 32, 94),
        ("segment", "push", 2, 34, 0),
        ("serve_refill", "sssp", 1, 0, 0, 0)],
    "pagerank": [
        ("segment", "pull", 2, 2), ("segment", "pull", 2, 4),
        ("segment", "pull", 2, 6), ("segment", "pull", 2, 8),
        ("serve_refill", "pagerank", 2, 1, 1, 0),
        ("segment", "pull", 2, 10), ("segment", "pull", 2, 12),
        ("segment", "pull", 2, 14), ("segment", "pull", 2, 16),
        ("serve_refill", "pagerank", 1, 0, 0, 0)],
}


class TestTurns:
    """PR 26: the kinds share the chip by turns (one segment and its
    boundary each, round-robin over the kinds that have work); a
    runner is suspended between its turns with its state on the
    device."""

    MIX = ([("sssp", s) for s in (3, 250, 17, 240, 99)]
           + [("components", s) for s in (255, 180, 7, 120)]
           + [("pagerank", s) for s in (3, 17, 40)])

    def test_no_kind_waits_for_a_fed_queue_to_empty(self, gt):
        """A feeder keeps the sssp queue non-empty (a new query at
        every retirement, 40 in all): the components and the pagerank
        query still get the chip every third turn and retire within
        the turns their own segments need."""
        srv = serve.Server(gt, batch=2, num_parts=1, seg_iters=2)
        fed = []

        def feeder(ev):
            if ev.get("kind") == "query_done" \
                    and ev.get("query_kind") == "sssp" and len(fed) < 40:
                fed.append(srv.submit("sssp", source=3 + len(fed)))

        for s in (3, 250, 17):
            srv.submit("sssp", source=s)
        others = {srv.submit("components", source=240): "components",
                  srv.submit("pagerank", source=99): "pagerank"}
        tip = _tip()
        telemetry.add_observer(feeder)
        try:
            with _limit(120):
                responses = srv.run()
        finally:
            telemetry.remove_observer(feeder)
        assert len(fed) == 40 and len(responses) == 45
        turns = _turns_since(tip)
        kinds = [k for _f, k, _s in turns]
        for kind in others.values():
            at = [i for i, k in enumerate(kinds) if k == kind]
            r = next(r for r in responses if r.kind == kind)
            # a turn a segment, and never more than two turns of the
            # other kinds between two of its own
            assert len(at) == r.segments
            assert at[0] <= 2 and max(np.diff(at), default=0) <= 3
            assert at[-1] <= 3 * r.segments - 1
        # while all three had work every turn was another runner's
        busy = kinds[:3 * min(
            r.segments for r in responses if r.qid in others)]
        assert busy == ["sssp", "components", "pagerank"] * (
            len(busy) // 3)
        assert all(s == 1 for _f, _k, s in turns[1:len(busy)])
        assert serve._check_answers(gt, responses) == 0

    def test_three_kinds_answer_as_each_kind_alone(self, gt):
        """Per query the mixed drain gives the single-kind drain's
        answer: bitwise for sssp and components, and for pagerank the
        oracle's at the reported iterations."""
        with _limit(120):
            mixed = run_specs(gt, self.MIX, batch=2)
            alone = [r for kind in serve.KINDS for r in run_specs(
                gt, [x for x in self.MIX if x[0] == kind], batch=2)]
        assert len(mixed) == len(alone) == len(self.MIX)
        want = {(r.kind, r.source): r for r in alone}
        for r in mixed:
            w = want[r.kind, r.source]
            assert (r.iters, r.segments, r.converged) == (
                w.iters, w.segments, w.converged)
            if r.kind == "pagerank":
                ref = pagerank.reference_pagerank_batched(
                    gt, pagerank.one_hot_resets(gt.nv, [r.source]),
                    r.iters)[:, 0]
                np.testing.assert_allclose(r.answer, ref, atol=5e-5)
            else:
                assert r.answer.dtype == w.answer.dtype
                np.testing.assert_array_equal(r.answer, w.answer)

    @pytest.mark.parametrize("kind", serve.KINDS)
    def test_suspended_and_resumed_equals_the_drain(self, gt, kind):
        """Turns of one runner with another runner's whole drain
        between each two equal its uninterrupted drain: the state
        waits on the device."""
        sources = (3, 250, 17, 240, 99)
        other = "pagerank" if kind != "pagerank" else "sssp"
        with _limit(120):
            want = run_specs(gt, [(kind, s) for s in sources], batch=2)
            srv = serve.Server(gt, batch=2, num_parts=2, seg_iters=2)
            submit_all(srv, [(kind, s) for s in sources])
            runner, coll = srv._runner(kind), srv._collector(kind)
            runner.turn(coll)
            while runner.resident:
                srv.submit(other, source=7)
                # the other runner's first dispatch is what this
                # one's last boundary answers behind (PR 41)
                srv._runner(other).drain(srv._collector(other))
                runner.turn(coll)
            got = runner.responses
        assert [(r.source, r.iters, r.segments) for r in got] == \
            [(r.source, r.iters, r.segments) for r in want]
        for r, w in zip(got, want):
            np.testing.assert_array_equal(r.answer, w.answer)

    @pytest.mark.parametrize("kind,graph,sources,batch,kw", [
        ("sssp", "gt", TestDeviceBoundary.SOURCES, 3, {}),
        ("pagerank", "g", (3, 17, 40), 2, {"tol": 1e-9})])
    def test_one_kind_is_the_parents_sequence(self, request, kind,
                                              graph, sources, batch, kw):
        """With one kind in the ring the ``segment`` and
        ``serve_refill`` events are, field for field, what the drain
        loop before the turns emitted; no turn is a switch."""
        fields = {"segment": ("engine", "iters", "total", "active",
                              "n", "done"),
                  "serve_refill": ("query_kind", "retired", "filled",
                                   "occupied", "queued")}
        ev = telemetry.EventLog()
        tip = _tip()
        with _limit(120), telemetry.use(events=ev):
            srv = serve.Server(request.getfixturevalue(graph),
                               batch=batch, num_parts=2, seg_iters=2,
                               **kw)
            submit_all(srv, [(kind, s) for s in sources])
            srv.run()
        trail = [(e["kind"],) + tuple(e[k] for k in fields[e["kind"]]
                                      if k in e)
                 for e in ev.events if e["kind"] in fields]
        assert trail == PARENT_TRAIL[kind]
        turns = _turns_since(tip)
        assert len(turns) == sum(t[0] == "segment" for t in trail)
        assert {t for t in turns} == {
            (serve._engine_family(kind), kind, 0)}


class TestDeterminism:
    def test_refill_schedule_and_answers_deterministic(self, g):
        """Two identical submission sequences produce identical
        responses: same retirement order, iterations, segments and
        bitwise answers — continuous batching must not depend on
        wall clocks."""
        specs = ([("sssp", s) for s in (3, 17, 40, 99, 200)]
                 + [("components", s) for s in (7, 50, 120)])

        def one():
            evs = telemetry.EventLog()
            with telemetry.use(events=evs):
                rs = run_specs(g, specs, batch=2)
            sched = [(e["qid"], e["col"]) for e in evs.events
                     if e["kind"] == "query_start"]
            return rs, sched

        r1, s1 = one()
        r2, s2 = one()
        assert s1 == s2
        assert [(r.qid, r.iters, r.segments, r.converged)
                for r in r1] == \
               [(r.qid, r.iters, r.segments, r.converged)
                for r in r2]
        for a, b in zip(r1, r2):
            np.testing.assert_array_equal(a.answer, b.answer)


class TestCollector:
    def test_collect_up_to_n(self):
        c = serve.BatchCollector()
        for i in range(5):
            c.put(serve.Request(qid=i, kind="sssp", source=i))
        got = c.collect(3)
        assert [r.qid for r in got] == [0, 1, 2]
        assert len(c) == 2
        assert [r.qid for r in c.collect(8)] == [3, 4]

    def test_deadline_zero_never_blocks(self):
        c = serve.BatchCollector()
        assert c.collect(4, deadline_s=0.0) == []

    def test_deadline_waits_for_first(self):
        import threading
        c = serve.BatchCollector()

        def feed():
            c.put(serve.Request(qid=7, kind="sssp", source=1))

        t = threading.Timer(0.05, feed)
        t.start()
        got = c.collect(2, deadline_s=2.0)
        t.join()
        assert [r.qid for r in got] == [7]


class TestPriorityCollector:
    """Round-18 deadline-priority collection (lux_tpu/fleet.py's
    admission queue) — the PINNED ordering rule under a
    deterministic injected clock: priority-desc FIFO, EXCEPT that a
    request past HALF its deadline is AGED and cannot be displaced
    further."""

    @staticmethod
    def make(clock):
        return serve.PriorityCollector(now=lambda: clock[0])

    @staticmethod
    def req(qid, priority=0, deadline_s=None, t=0.0):
        return serve.Request(qid=qid, kind="sssp", source=qid,
                             t_enqueue=t, priority=priority,
                             deadline_s=deadline_s)

    def test_priority_order_fifo_within(self):
        clock = [0.0]
        c = self.make(clock)
        for qid, pr in ((0, 0), (1, 2), (2, 1), (3, 2)):
            c.put(self.req(qid, priority=pr))
        assert [r.qid for r in c.collect(10)] == [1, 3, 2, 0]

    def test_deadline_semantics_match_base(self):
        import threading
        clock = [0.0]
        c = self.make(clock)
        assert c.collect(4, deadline_s=0.0) == []   # never blocks
        t = threading.Timer(0.05, lambda: c.put(self.req(9)))
        t.start()
        got = c.collect(2, deadline_s=2.0)   # waits for the FIRST
        t.join()
        assert [r.qid for r in got] == [9]

    def test_aged_low_priority_not_displaced(self):
        """The pinned aging rule: a low-priority request past half
        its deadline outranks fresh high-priority traffic — a
        saturated priority stream cannot displace it indefinitely."""
        clock = [0.0]
        c = self.make(clock)
        c.put(self.req(0, priority=0, deadline_s=10.0, t=0.0))
        for i in range(1, 4):
            c.put(self.req(i, priority=5, t=0.0))
        # fresh: high priority first, the low-priority one last
        assert [r.qid for r in c.collect(2)] == [1, 2]
        # past HALF the deadline: the aged request now leads
        clock[0] = 5.0
        c.put(self.req(4, priority=5, t=4.9))
        assert [r.qid for r in c.collect(2)] == [0, 3]

    def test_aged_order_earliest_deadline_first(self):
        clock = [10.0]
        c = self.make(clock)
        c.put(self.req(0, priority=0, deadline_s=16.0, t=0.0))
        c.put(self.req(1, priority=0, deadline_s=12.0, t=0.0))
        c.put(self.req(2, priority=9))
        # both aged (past half deadline); nearest absolute deadline
        # (t=0 + 12) collects first, the un-aged priority-9 last
        assert [r.qid for r in c.collect(3)] == [1, 0, 2]

    def test_unaged_deadline_keeps_priority_order(self):
        clock = [1.0]
        c = self.make(clock)
        c.put(self.req(0, priority=0, deadline_s=100.0, t=0.0))
        c.put(self.req(1, priority=3, deadline_s=100.0, t=0.5))
        assert [r.qid for r in c.collect(2)] == [1, 0]


class TestTelemetryRoundTrip:
    def test_events_summary_validates_query_trail(self, g, tmp_path):
        path = tmp_path / "serve_ev.jsonl"
        ev = telemetry.EventLog(str(path))
        with telemetry.use(events=ev):
            ev.emit("run_start", schema=telemetry.SCHEMA,
                    app="serve", file="<test>")
            t0 = time.perf_counter()
            responses = run_specs(g, [("sssp", s)
                                      for s in (3, 17, 40, 99)],
                                  batch=2)
            # the real elapsed: the summary checks that the segments'
            # seconds (the first carries a compile) fit inside it
            ev.emit("run_done", seconds=time.perf_counter() - t0,
                    iters=sum(r.iters for r in responses))
        ev.close()
        r = subprocess.run([sys.executable, str(SUMMARY), str(path)],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "queries served: 4" in r.stdout
        assert "continuous batching:" in r.stdout

    def test_events_summary_rejects_broken_query_done(self, tmp_path):
        path = tmp_path / "bad.jsonl"
        evs = [
            {"t": 1.0, "tm": 1.0, "pid": 1, "session": "s",
             "kind": "query_enqueue", "qid": 0, "query_kind": "sssp"},
            # missing latency_s / iters — an unaccountable query
            {"t": 1.2, "tm": 1.2, "pid": 1, "session": "s",
             "kind": "query_done", "qid": 0, "query_kind": "sssp",
             "segments": 1},
        ]
        path.write_text("".join(json.dumps(e) + "\n" for e in evs))
        r = subprocess.run([sys.executable, str(SUMMARY), str(path)],
                           capture_output=True, text=True)
        assert r.returncode == 1
        assert "query_done missing" in r.stderr

    def test_events_summary_rejects_unenqueued_done(self, tmp_path):
        path = tmp_path / "bad2.jsonl"
        evs = [
            {"t": 1.0, "tm": 1.0, "pid": 1, "session": "s",
             "kind": "query_enqueue", "qid": 0, "query_kind": "sssp"},
            {"t": 1.2, "tm": 1.2, "pid": 1, "session": "s",
             "kind": "query_done", "qid": 5, "query_kind": "sssp",
             "iters": 3, "segments": 1, "latency_s": 0.2,
             "wait_s": 0.0},
        ]
        path.write_text("".join(json.dumps(e) + "\n" for e in evs))
        r = subprocess.run([sys.executable, str(SUMMARY), str(path)],
                           capture_output=True, text=True)
        assert r.returncode == 1
        assert "never enqueued" in r.stderr


class TestServingMetricsAndSLO:
    def test_slo_accounting_matches_oracle(self, g):
        """The SLO counters, burn-rate gauge and per-event slo_ok
        flags must all re-derive from the responses' OWN latencies —
        the accounting can never disagree with the stream it
        aggregates."""
        slo = 40.0
        ev = telemetry.EventLog()
        with telemetry.use(events=ev):
            srv = serve.Server(g, batch=2, num_parts=2, seg_iters=2,
                               slo_ms={"sssp": slo})
            for s in (3, 17, 40, 99, 200):
                srv.submit("sssp", source=s)
            responses = srv.run()
        assert len(responses) == 5
        want_good = sum(r.latency_s * 1e3 <= slo for r in responses)
        want_bad = 5 - want_good
        reg = srv.metrics

        def counter(name):
            c = reg.counter(name, kind="sssp")
            return c.value

        assert counter("serve_slo_good_total") == want_good
        assert counter("serve_slo_violation_total") == want_bad
        burn = reg.gauge("serve_slo_burn_rate", kind="sssp").value
        assert burn == pytest.approx(want_bad / 5)
        # the per-event record carries the same verdicts
        done = [e for e in ev.events if e["kind"] == "query_done"]
        assert len(done) == 5
        by_qid = {r.qid: r for r in responses}
        for e in done:
            assert e["slo_ms"] == slo
            assert e["slo_ok"] == \
                (by_qid[e["qid"]].latency_s * 1e3 <= slo)
        # latency histogram count equals retirements; queue drained
        h = reg.histogram("serve_latency_seconds", kind="sssp")
        assert h.count == 5
        assert reg.gauge("serve_queue_depth", kind="sssp").value == 0
        # the drain published a snapshot event
        assert any(e["kind"] == "metrics_snapshot"
                   for e in ev.events)
        # events_summary cross-audit accepts the consistent trail
        # (snapshot counts vs query_done events) — in-process render
        import io

        import events_summary as es
        out = io.StringIO()
        errs = []
        streams, serrs = es.split_streams(ev.events)
        for _key, stream in streams:
            for run in es.split_runs(stream):
                errs += es.render_run(run, out=out)
        assert serrs == [] and errs == []
        assert "metrics snapshot" in out.getvalue()

    def test_metrics_false_disables_cleanly(self, g):
        srv = serve.Server(g, batch=2, num_parts=2, seg_iters=2,
                           metrics=False)
        assert srv.metrics is None
        srv.submit("sssp", source=3)
        ev = telemetry.EventLog()
        with telemetry.use(events=ev):
            (r,) = srv.run()
        assert r.converged
        assert not any(e["kind"] == "metrics_snapshot"
                       for e in ev.events)
        assert srv.emit_metrics_snapshot() is None

    def test_unknown_slo_kind_rejected(self, g):
        with pytest.raises(ValueError):
            serve.Server(g, slo_ms={"bogus": 10.0})

    def test_loadgen_acceptance_oversubscribed_mesh(self, g,
                                                    tmp_path):
        """THE round-17 acceptance: an open-loop oversubscribed
        mixed-kind load on the 8-virtual-device mesh — snapshot
        percentiles against the NumPy oracle at the pinned bound,
        per-query spans through validate_trace, and the rendered
        events_summary audit."""
        import loadgen

        from lux_tpu import tracing
        from lux_tpu.parallel.mesh import make_mesh

        kinds = ["sssp", "components", "pagerank"]
        path = tmp_path / "serve_ev.jsonl"
        ev = telemetry.EventLog(str(path))
        with telemetry.use(events=ev):
            ev.emit("run_start", schema=telemetry.SCHEMA,
                    app="serve", file="<test>", mesh=8)
            srv = serve.Server(g, batch=2, num_parts=8,
                               mesh=make_mesh(8), seg_iters=2,
                               slo_ms={"sssp": 250.0,
                                       "components": 250.0,
                                       "pagerank": 1000.0})
            import time as _time
            t0 = _time.perf_counter()
            loadgen.warm(srv, kinds)
            idx0 = len(ev.events)
            rng = np.random.default_rng(3)
            # rate far past the CPU mesh's service rate: every query
            # arrives up front, so the B=2 columns OVERSUBSCRIBE and
            # later queries enter through retire+refill
            rep = loadgen.run_step(srv, rate=500.0, n=12,
                                   kinds=kinds, rng=rng, step=0)
            ev.emit("run_done",
                    seconds=round(_time.perf_counter() - t0, 6),
                    iters=rep.served)
        ev.close()
        assert rep.drained and rep.served == 12
        assert rep.achieved_qps <= rep.offered_qps * (1 + 1e-9)
        assert rep.p50_ms is not None and rep.p99_ms is not None
        assert rep.p50_ms <= rep.p99_ms
        assert rep.slo_good_fraction is not None
        # oversubscription really exercised continuous batching
        refills = [e for e in ev.events[idx0:]
                   if e["kind"] == "serve_refill"
                   and e.get("retired") and e.get("filled")]
        assert refills, "oversubscribed load drained without refill"

        # (a) snapshot percentiles vs the NumPy oracle over the raw
        # query_done stream, within the histogram's PINNED bound
        snaps = [e for e in ev.events
                 if e["kind"] == "metrics_snapshot"
                 and e.get("step") == 0]
        assert snaps
        done = [e for e in ev.events[idx0:]
                if e["kind"] == "query_done"]
        assert len(done) == 12
        checked = 0
        for h in snaps[-1]["histograms"]:
            if h["name"] != "serve_latency_seconds":
                continue
            kind = h["labels"]["kind"]
            lats = [e["latency_s"] for e in done
                    if e["query_kind"] == kind]
            assert h["count"] == len(lats)
            for q, key in ((0.5, "p50"), (0.99, "p99")):
                oracle = float(np.quantile(lats, q,
                                           method="inverted_cdf"))
                # + 1e-3: the event stream rounds latency_s to 1e-6
                assert abs(h[key] - oracle) / oracle <= \
                    metrics_mod.QUANTILE_REL_ERR + 1e-3, (kind, key)
            checked += 1
        assert checked == len(kinds)

        # (b) per-query spans through validate_trace
        trace = tracing.trace_export(ev.events,
                                     out=str(tmp_path / "t.json"))
        assert tracing.validate_trace(trace) == []
        qspans = [e for e in trace["traceEvents"]
                  if e.get("cat") == "query"]
        phases = [e for e in trace["traceEvents"]
                  if e.get("cat") == "query_phase"]
        assert len(qspans) >= 12          # warm queries also render
        assert {e["name"] for e in phases} >= {"wait"}
        waits = {}
        for e in trace["traceEvents"]:
            if e.get("cat") == "query" and "slo_ok" in e.get("args",
                                                            {}):
                waits[e["args"]["qid"]] = e["args"]["wait_s"]
        assert waits                      # spans carry the SLO verdict

        # events_summary renders + audits the full trail
        r = subprocess.run([sys.executable, str(SUMMARY), str(path)],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "metrics snapshot" in r.stdout

    def test_serve_slo_bench_line_through_check_bench(self, tmp_path):
        """(c) of the acceptance: bench.py -config serve-slo produces
        a metric line scripts/check_bench.py ACCEPTS, and the
        contradiction mutations are rejected."""
        import argparse

        import bench

        args = argparse.Namespace(
            scale=8, ef=8, ni=20, np=2, pair=0, min_fill=None,
            min_fill_dot=None, repeats=1, verbose=False,
            health=False, audit="warn", serve_queries=10,
            serve_batch=2, serve_kinds="sssp,components,pagerank",
            slo_ms="sssp=250,components=250,pagerank=1000",
            rates="60", batch="1", shape="rmat", reorder="none")
        ev = telemetry.EventLog()
        with telemetry.use(events=ev):
            idx0 = len(ev.events)
            name, samples, extra, _rerun = bench.run_config(
                "serve-slo@60", args)
            tel = bench.config_telemetry(ev, idx0, None)
        assert name == "serve_slo_q60_rmat8"
        assert extra["unit"] == "qps"
        assert extra["audit"]["errors"] == 0
        value = round(float(np.median(samples)), 4)
        line = {"metric": f"{name}_qps_per_chip", "value": value,
                "unit": "qps", "vs_baseline": value,
                "samples": [round(s, 4) for s in samples],
                "attempts": len(samples), "discarded": [],
                "telemetry": tel, **extra}
        p = tmp_path / "bench.jsonl"
        p.write_text(json.dumps(line) + "\n")
        r = subprocess.run([sys.executable, str(CHECK_BENCH),
                            "-legacy-ok", str(p)],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr

        def rejects(mutate, needle):
            bad = json.loads(json.dumps(line))
            mutate(bad)
            p.write_text(json.dumps(bad) + "\n")
            rr = subprocess.run([sys.executable, str(CHECK_BENCH),
                                 "-legacy-ok", str(p)],
                                capture_output=True, text=True)
            assert rr.returncode == 1 and needle in rr.stderr, \
                (needle, rr.stderr)

        rejects(lambda d: d.update(p99_ms=d["p50_ms"] / 2),
                "p99_ms")
        rejects(lambda d: d.update(
            achieved_qps=d["offered_qps"] * 2,
            value=round(d["offered_qps"] * 2, 4),
            samples=[round(d["offered_qps"] * 2, 4)]),
            "outrun arrivals")
        rejects(lambda d: d.update(slo_good_fraction=1.2),
                "slo_good_fraction")
        rejects(lambda d: d.pop("offered_qps"),
                "serve-slo line missing")
        rejects(lambda d: d.update(value=d["value"] + 1,
                                   samples=[d["value"] + 1]),
                "achieved_qps")


class TestServeSmoke:
    def test_main_smoke(self, tmp_path):
        """The acceptance smoke: 2B mixed queries drain via refill
        with oracle-matching answers and a validated event trail."""
        path = tmp_path / "ev.jsonl"
        rc = serve.main(["-scale", "8", "-ef", "8", "-batch", "3",
                         "-np", "2", "-events", str(path)])
        assert rc == 0
        r = subprocess.run([sys.executable, str(SUMMARY), str(path)],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert "queries served: 6" in r.stdout
