"""PR 41: a segment boundary in two halves.

The first half steers the next segment and stays serial (counts, the
``_take_column`` dispatches, the slots freed, fill, place); the second
— the answers of columns that have left the batch — runs behind the
NEXT dispatch (``segmented``'s ``while_running`` seam), whichever
runner's it is, or is flushed where no dispatch follows.

- the order of one worked boundary, with the engine's dispatch and the
  completion fence recorded;
- responses, epochs and the answer cache equal the SERIAL order's (the
  second half run where the first ends, as before PR 41), bit for bit,
  on seeded closed loops of k-SSSP, PPR and the mixed server and on a
  live graph served at two epochs;
- the last boundary of a drain, an idle loop and a raising turn flush
  at once; a second half that fails leaves its queries for a failover;
- a query equal to one that retires at the same boundary takes no
  column;
- a suspended runner that comes back to the chip with a free column
  and a queued query starts it before its segment.
"""

import threading

import numpy as np
import pytest

from lux_tpu import serve, telemetry
from lux_tpu.convert import uniform_random_edges
from lux_tpu.graph import Graph

NV, NE = 256, 2048


@pytest.fixture(scope="module")
def g():
    src, dst = uniform_random_edges(NV, NE, seed=5)
    return Graph.from_edges(src, dst, NV)


def _server(g, **kw):
    kw = {"batch": 2, "num_parts": 2, "seg_iters": 2, **kw}
    return serve.Server(g, **kw)


def _tip() -> int:
    telemetry.mark("test.tip")
    return telemetry.spans()[-1]["id"]


def _since(tip: int, prefix: str) -> list:
    return [r for r in telemetry.spans()
            if r["id"] > tip and r["name"].startswith(prefix)]


def _serial(monkeypatch):
    """The order before PR 41: a boundary's second half runs where its
    first half deferred it, before fill and place."""
    monkeypatch.setattr(serve._AnswerWork, "defer",
                        lambda self, job: job(False))


# -- (a) the order of a worked boundary ---------------------------------

class _Fence:
    """Stands for the scalar the push driver fetches to wait for the
    segment: says when it was asked for."""

    def __init__(self, it, log):
        self.it, self.log = it, log

    def __array__(self, *a, **kw):
        self.log.append("fence")
        return np.asarray(self.it)


@pytest.mark.parametrize("kind", ["sssp", "pagerank"])
def test_the_answers_lie_between_the_dispatch_and_the_fence(
        g, kind, monkeypatch):
    srv = _server(g, tol=1e-9)
    runner = srv._runner(kind)
    log = []
    if kind == "sssp":
        real = runner.eng.converge

        def converge(label, active, n):
            log.append("dispatch")
            label, active, it = real(label, active, n)
            return label, active, _Fence(it, log)
        monkeypatch.setattr(runner.eng, "converge", converge)
    else:
        from lux_tpu import timing
        real, real_fence = runner.eng.run, timing.fence

        def run(state, n):
            log.append("dispatch")
            return real(state, n)

        def fence(x):
            log.append("fence")
            return real_fence(x)
        monkeypatch.setattr(runner.eng, "run", run)
        monkeypatch.setattr(timing, "fence", fence)

    pre = "serve.boundary."

    def on_event(ev):
        if ev.get("kind") == "span" and ev["name"].startswith(pre):
            log.append(ev["name"][len(pre):])
        elif ev.get("kind") == "span" and ev["name"] == "serve.boundary":
            log.append("|")

    for s in (3, 17, 40, 99, 200):
        srv.submit(kind, source=s)
    telemetry.add_observer(on_event)
    try:
        got = srv.run()
    finally:
        telemetry.remove_observer(on_event)
    assert len(got) == 5
    first = "counts" if kind == "sssp" else "residual"
    text = " ".join(log)
    # a boundary that retired and left columns resident: its answers
    # come after the next dispatch and before that segment's fence
    want = (f"{first} take fill place | dispatch fetch unpad retire "
            f"fence")
    assert want in text
    # nowhere does an answer hold up a dispatch that follows
    for half in ("fetch", "unpad", "retire"):
        assert f"{half} fill" not in text and f"{half} place" not in text
    # the drain's last boundary has no dispatch to hide behind
    assert text.endswith("| fetch unpad retire")


# -- (b) the serial order's answers -------------------------------------

class _ClosedLoop:
    """Callers that submit their next seeded source the moment they
    learn (``query_done``) that their last query retired: 2 a column,
    ``per_kind`` queries a kind in all."""

    def __init__(self, srv, kinds, batch, per_kind, seed):
        rng = np.random.default_rng(seed)
        self.srv = srv
        self.left = {k: [int(s) for s in
                         rng.choice(NV, size=per_kind, replace=False)]
                     for k in kinds}
        self.kind_of = {}
        self.done = []                      # query_done events
        for kind in kinds:
            for _ in range(2 * batch):
                self._submit(kind)

    def _submit(self, kind):
        if self.left[kind]:
            qid = self.srv.submit(kind, source=self.left[kind].pop(0))
            self.kind_of[qid] = kind

    def on_event(self, ev):
        if ev.get("kind") == "query_done":
            self.done.append(ev)
            self._submit(self.kind_of[ev["qid"]])

    def run(self):
        out = []
        telemetry.add_observer(self.on_event)
        try:
            while True:
                got = self.srv.run()
                if not got:
                    return out
                out += got
        finally:
            telemetry.remove_observer(self.on_event)


def _closed(g, kinds, seed):
    srv = _server(g, cache=True, tol=1e-9)
    loop = _ClosedLoop(srv, kinds, 2, 9, seed)
    return srv, loop, loop.run()


def _same_responses(got, want):
    key = ("qid", "kind", "source", "iters", "segments", "converged",
           "epoch", "cached")
    assert sorted(tuple(getattr(r, k) for k in key) for r in got) \
        == sorted(tuple(getattr(r, k) for k in key) for r in want)
    by = {r.qid: r for r in want}
    for r in got:
        assert r.answer.dtype == by[r.qid].answer.dtype
        np.testing.assert_array_equal(r.answer, by[r.qid].answer)


def _same_cache(got, want):
    assert list(got._d) == list(want._d)        # keys, LRU order too
    for k, e in got._d.items():
        w = want._d[k]
        assert (e.iters, e.epoch) == (w.iters, w.epoch)
        np.testing.assert_array_equal(e.answer, w.answer)
    assert got.bytes == want.bytes


@pytest.mark.parametrize("kinds", [
    ("sssp",), ("pagerank",), ("sssp", "components", "pagerank")],
    ids=["ksssp", "ppr", "mixed"])
def test_a_closed_loop_answers_as_the_serial_order(g, kinds,
                                                   monkeypatch):
    with monkeypatch.context() as m:
        _serial(m)
        want_srv, want_loop, want = _closed(g, kinds, seed=11)
    tip = _tip()
    got_srv, got_loop, got = _closed(g, kinds, seed=11)
    assert len(got) == 9 * len(kinds)
    _same_responses(got, want)
    _same_cache(got_srv.cache, want_srv.cache)
    # the same queries left at the same boundaries of the same turns
    assert [(e["qid"], e["col"], e["iters"], e["segments"])
            for e in got_loop.done] \
        == [(e["qid"], e["col"], e["iters"], e["segments"])
            for e in want_loop.done]
    # and the overlap engaged: every boundary that retired and was
    # followed by a dispatch had its answers made behind it
    hidden = [b["counts"]["hidden"] for b in _since(tip, "serve.boundary")
              if b["name"] == "serve.boundary" and b["counts"]["retired"]]
    assert hidden.count(0) <= len(kinds) and hidden.count(1) >= 3


def test_a_live_graph_answers_each_epoch_as_the_serial_order(
        g, monkeypatch):
    from lux_tpu.livegraph import LiveGraph

    def one():
        lg = LiveGraph(g, capacity=32)
        srv = _server(g, live=lg, cache=True)
        done = []

        def on_event(ev):
            if ev.get("kind") == "query_done":
                done.append((ev["qid"], ev["epoch"], ev["answer_epoch"]))
        for s in (3, 17, 40):
            srv.submit("sssp", source=s)
        srv.mutate([3, 17], [200, 99])          # a new epoch
        for s in (3, 99, 200):
            srv.submit("sssp", source=s)
        telemetry.add_observer(on_event)
        try:
            out = srv.run()
        finally:
            telemetry.remove_observer(on_event)
        assert lg.admitted == 0 and lg.pins == 0
        return srv, out, sorted(done)

    with monkeypatch.context() as m:
        _serial(m)
        want_srv, want, want_done = one()
    got_srv, got, got_done = one()
    assert {r.epoch for r in got} == {0, 1}
    _same_responses(got, want)
    _same_cache(got_srv.cache, want_srv.cache)
    assert got_done == want_done
    assert all(e == a for _q, e, a in got_done)     # no torn epoch


# -- (c) where no dispatch follows --------------------------------------

def test_the_last_boundary_of_a_drain_is_flushed(g):
    srv = _server(g)
    for s in (3, 17, 40):
        srv.submit("sssp", source=s)
    tip = _tip()
    got = srv.run()
    assert len(got) == 3 and not len(srv._answers)
    bounds = [b for b in _since(tip, "serve.boundary")
              if b["name"] == "serve.boundary" and b["counts"]["retired"]]
    assert bounds[-1]["counts"]["hidden"] == 0
    # flushed inside the turn that took the columns, before it ended
    turn = next(t for t in _since(tip, "serve.turn.")
                if t["id"] == bounds[-1]["parent"])
    retire = [r for r in _since(tip, "serve.boundary.retire")
              if r["parent"] == bounds[-1]["id"]]
    assert len(retire) == 1 and retire[0]["t1"] <= turn["t1"]
    assert serve._check_answers(g, got) == 0


def test_an_idle_loop_holds_no_answer_back(g):
    srv = _server(g)
    srv.submit("sssp", source=1)
    srv.run()                                   # build and compile
    tip = _tip()
    got, delivered = [], threading.Event()

    def deliver(responses):
        got.extend(responses)
        delivered.set()

    th = threading.Thread(target=srv.serve, args=(deliver,),
                          daemon=True)
    th.start()
    try:
        srv.submit("sssp", source=5)
        # the only query: after its boundary every column is idle and
        # nothing is queued, so no dispatch will ever follow
        assert delivered.wait(timeout=60)
        assert [r.source for r in got] == [5]
        assert th.is_alive()                    # the loop blocks on
        assert not len(srv._answers)
        idle = _since(tip, "serve.idle")
        deliver_spans = _since(tip, "serve.deliver")
        # handed over before the wait that follows the turn opened
        assert len(deliver_spans) == 1
        assert all(i["t0"] >= deliver_spans[0]["t1"] for i in idle[1:])
    finally:
        srv.stop()
        th.join(timeout=30)
    assert not th.is_alive()
    assert serve._check_answers(g, got) == 0


def _resident_with_answers_pending(srv, kind="sssp"):
    """Turns until a boundary has retired a column and left another
    resident: its second half is waiting for the next dispatch."""
    runner, coll = srv._runner(kind), srv._collector(kind)
    runner.turn(coll)
    while runner.resident and not len(runner.answers):
        runner.turn(coll)
    assert runner.resident and len(runner.answers) == 1
    return runner, coll


def test_a_raising_turn_flushes_what_left_before_it(g, monkeypatch):
    srv = _server(g)
    for s in (3, 17, 40, 99, 200):
        srv.submit("sssp", source=s)
    tip = _tip()
    runner, coll = _resident_with_answers_pending(srv)
    n_before = len(runner.responses)
    waiting = [x.slot.req.qid for x in runner._leaving]
    assert waiting

    def dead(*_a, **_kw):
        raise RuntimeError("device lost")
    monkeypatch.setattr(runner.eng, "converge", dead)
    with pytest.raises(RuntimeError, match="device lost"):
        runner.turn(coll)
    assert not runner.resident and not len(runner.answers)
    assert [r.qid for r in runner.responses[n_before:]] == waiting
    assert runner.unanswered() == []
    bound = [b for b in _since(tip, "serve.boundary")
             if b["name"] == "serve.boundary" and b["counts"]["retired"]]
    assert bound[-1]["counts"]["hidden"] == 0
    assert serve._check_answers(g, runner.responses) == 0


def test_a_second_half_that_fails_leaves_its_queries_to_a_failover(
        g, monkeypatch):
    srv = _server(g)
    for s in (3, 17, 40, 99, 200):
        srv.submit("sssp", source=s)
    runner, coll = _resident_with_answers_pending(srv)
    waiting = [x.slot.req.qid for x in runner._leaving]

    def torn(_x):
        raise OSError("host copy failed")
    monkeypatch.setattr(runner.eng.sg, "from_padded", torn)
    with pytest.raises(OSError):
        runner.turn(coll)           # behind this turn's dispatch
    assert not runner.resident
    lost = runner.unanswered()
    assert [q.qid for q in lost] == waiting
    assert runner.unanswered() == []            # handed over once


def test_a_fleet_replica_that_dies_answers_what_had_left(g):
    """``fleet._drain_inproc`` reads ``runner.responses`` after a
    drain that a kill plan ended: what retired before the death is in
    it, flushed by the raising turn."""
    from lux_tpu import faults
    runner = serve.PushBatchRunner("sssp", g, 2, num_parts=2,
                                   seg_iters=2)
    coll = serve.BatchCollector()
    for i, s in enumerate((3, 17, 40, 99, 200)):
        coll.put(serve.Request(qid=i, kind="sssp", source=s))
    calls = []

    def on_boundary(r):
        calls.append(len(r.responses))
        if len(calls) == 5:
            raise faults.InjectedWorkerKill("replica-0")
    runner.on_boundary = on_boundary
    with pytest.raises(faults.InjectedWorkerKill):
        runner.drain(coll)
    # every boundary's answers were made behind the dispatch before
    # the next boundary's top: nothing was pending when it died
    assert not len(runner.answers) and runner.unanswered() == []
    answered = {r.qid for r in runner.responses}
    resident = {s.req.qid for s in runner.slots if s is not None}
    queued = {q.qid for q in coll.pending_requests()}
    assert answered | resident | queued == set(range(5))
    assert not answered & (resident | queued)
    assert serve._check_answers(g, runner.responses) == 0


# -- (d) a twin of a query that retires at the same boundary ------------

def _segments_of(g, sources):
    srv = _server(g, batch=1)
    for s in sources:
        srv.submit("sssp", source=s)
    return {r.source: r.segments for r in srv.run()}


@pytest.mark.parametrize("order", ["serial", "overlapped"])
def test_a_twin_of_a_retiring_query_takes_no_column(g, order,
                                                    monkeypatch):
    segs = _segments_of(g, (3, 17, 40, 99, 200))
    by_segments = sorted(segs, key=segs.get)
    a, b = by_segments[0], by_segments[-1]      # a retires no later
    if order == "serial":
        _serial(monkeypatch)
    srv = _server(g, cache=True)
    started = []

    def on_event(ev):
        if ev.get("kind") == "query_start":
            started.append(ev["qid"])
    q_a, q_b = srv.submit("sssp", source=a), srv.submit("sssp", source=b)
    q_twin = srv.submit("sssp", source=a)       # waits for a column
    telemetry.add_observer(on_event)
    try:
        got = {r.qid: r for r in srv.run()}
    finally:
        telemetry.remove_observer(on_event)
    assert started == [q_a, q_b]                # the twin took none
    twin, first = got[q_twin], got[q_a]
    assert twin.cached and twin.segments == 0 and not first.cached
    assert twin.iters == first.iters
    np.testing.assert_array_equal(twin.answer, first.answer)
    assert twin.answer is not first.answer
    # the cache counts what it did in the serial order: the two that
    # took columns missed, the twin hit
    assert (srv.cache.hits, srv.cache.misses) == (1, 2)
    assert serve._check_answers(g, list(got.values())) == 0
    assert not srv._runner("sssp")._leaving


# -- a runner that comes back to the chip with a free column ------------

@pytest.mark.parametrize("kind", ["sssp", "components", "pagerank"])
def test_a_suspended_runner_starts_a_queued_query_before_its_segment(
        g, kind):
    other = "components" if kind != "components" else "sssp"
    alone = _server(g, batch=1, tol=1e-9)
    alone.submit(kind, source=99)
    want = alone.run()[0]

    srv = _server(g, tol=1e-9)
    runner, coll = srv._runner(kind), srv._collector(kind)
    srv.submit(kind, source=3)
    runner.turn(coll)                           # one column is free
    assert runner.resident and len(runner._free_cols()) == 1
    late = srv.submit(kind, source=99)          # while suspended
    srv.submit(other, source=7)
    srv._runner(other).drain(srv._collector(other))
    tip = _tip()
    runner.turn(coll)
    kids = [r["name"] for r in _since(tip, "")
            if r["parent"] == _since(tip, "serve.turn.")[0]["id"]]
    assert kids[:3] == ["serve.boundary.fill", "serve.boundary.place",
                        "segment.run"]
    while runner.resident:
        runner.turn(coll)
    got = {r.qid: r for r in runner.responses}[late]
    # it ran as a query that had the runner to itself: the refill put
    # it on its own trajectory (pull: the snapshot its residuals are
    # taken against was renewed with the placement)
    assert (got.iters, got.converged) == (want.iters, True)
    assert got.segments == want.segments
    if kind == "pagerank":      # a batch of 1 sums in another order
        np.testing.assert_allclose(got.answer, want.answer, rtol=1e-5)
    else:
        np.testing.assert_array_equal(got.answer, want.answer)
    assert serve._check_answers(g, list(runner.responses)) == 0
