"""``serve.Server.serve``: the serving loop that answers at retirement.

- an open loop (a submitter thread, the loop on this thread): every
  query delivered exactly once, the moment its answer is made (no
  turn starts between ``query_done`` and the hand-over), answers
  equal to the oracle, columns taken in submit order;
- ``run()`` is the loop with the stop given: the responses and their
  order are those of the drain loop before it (kept here as the
  oracle), for every kind and the mixed ring;
- with no work the loop blocks (no turn opens), wakes on a ``submit``,
  ends on the stop;
- ``submit`` from several threads: dense qids, one collector a kind,
  queue order = qid order;
- on a live graph each delivered response is released while the loop
  is still running.
"""

import sys
import threading
import time

import numpy as np
import pytest

from lux_tpu import serve, telemetry
from lux_tpu.convert import uniform_random_edges
from lux_tpu.graph import Graph

NV, NE = 256, 2048
SOURCES = (3, 17, 40, 99, 200, 7, 150, 31, 64, 222)


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


class Caller:
    """The caller's side of an open loop: what ``deliver`` received
    and when, and the program's per-query events (an observer)."""

    def __init__(self):
        self.batches = []           # (perf_counter, [qid, ...])
        self.responses = []
        self.done = {}              # qid -> perf_counter of query_done
        self.started = []           # qids in the order columns were taken

    def deliver(self, responses):
        self.batches.append((time.perf_counter(),
                             [r.qid for r in responses]))
        self.responses += responses

    def on_event(self, ev):
        if ev.get("kind") == "query_done":
            self.done[ev["qid"]] = time.perf_counter()
        elif ev.get("kind") == "query_start":
            self.started.append(ev["qid"])


def _open_loop(srv, specs, gap_s, caller):
    """Submit ``specs`` from another thread, ``gap_s`` apart, then
    give the stop; the loop runs here until it ends."""
    def submitter():
        try:
            for kind, s in specs:
                time.sleep(gap_s)
                srv.submit(kind, source=s)
        finally:
            srv.stop()

    th = threading.Thread(target=submitter, daemon=True)
    telemetry.add_observer(caller.on_event)
    try:
        th.start()
        srv.serve(caller.deliver)
    finally:
        telemetry.remove_observer(caller.on_event)
    th.join(timeout=30)
    assert not th.is_alive()


@pytest.mark.parametrize("gap_s", [0.0, 0.004, 0.03])
def test_open_loop_delivers_each_query_once_at_its_turn(g, gap_s):
    srv = _server(g)
    srv.submit("sssp", source=1)
    srv.run()                                   # build and compile
    tip = _tip()
    caller = Caller()
    specs = [("sssp", s) for s in SOURCES]
    _open_loop(srv, specs, gap_s, caller)

    qids = [r.qid for r in caller.responses]
    assert sorted(qids) == list(range(1, len(specs) + 1))
    assert len(set(qids)) == len(qids)
    assert serve._check_answers(g, caller.responses) == 0
    # columns go to the queries in the order they were submitted
    assert caller.started == sorted(caller.started)
    # each batch is one boundary's answers, handed over inside the
    # turn that made them (PR 41: the turn AFTER the one that took
    # the columns, behind its dispatch; the boundary's own turn where
    # no dispatch followed): no turn starts between a query_done and
    # the instant the caller held the response
    turns = sorted((r["t0"], r["t1"]) for r in _since(tip, "serve.turn."))
    assert turns
    for t_got, batch in caller.batches:
        inside = [(s, e) for s, e in turns if s <= t_got <= e]
        assert len(inside) == 1, "delivered outside any turn"
        t0, t1 = inside[0]
        for qid in batch:
            assert t0 <= caller.done[qid] <= t_got
    # the answers' work lies behind a dispatch wherever one followed
    bounds = [b for b in _since(tip, "serve.boundary")
              if b["name"] == "serve.boundary"
              and b["counts"]["retired"]]
    assert sum(b["counts"]["retired"] for b in bounds) == len(specs)
    turn_of = {r["id"]: r for r in _since(tip, "serve.turn.")}
    for b in bounds:
        fetch = next(r for r in _since(tip, "serve.boundary.fetch")
                     if r["parent"] == b["id"])
        own = turn_of[b["parent"]]
        assert b["counts"]["hidden"] == int(fetch["t0"] > own["t1"])
    # one hand-over span a delivery, counting what it carried
    spans = _since(tip, "serve.deliver")
    assert [s["counts"]["responses"] for s in spans] \
        == [len(b) for _t, b in caller.batches]


def _parent_run(srv):
    """``Server.run`` as it stood before the loop (commit 0817d17):
    turns round the ring until no kind has work, responses returned
    at the end.  The oracle of ``run()``'s order."""
    out = []
    served = True
    while served:
        served = False
        for kind, coll in list(srv._collectors.items()):
            runner = srv._runners.get(kind)
            if not (len(coll) or (runner is not None
                                  and runner.resident)):
                continue
            runner = srv._runner(kind)
            runner.turn(
                coll, srv.deadline_s,
                switch=srv._last_turn not in (None, runner))
            srv._last_turn = runner
            served = True
            # PR 41: a boundary's answers are made behind the NEXT
            # dispatch, whichever runner's: take what is ready
            for r in srv._runners.values():
                out += r.responses
                del r.responses[:]
    return out


MIXED = [("sssp", 3), ("components", 17), ("pagerank", 40),
         ("sssp", 99), ("sssp", 200), ("pagerank", 7),
         ("components", 150), ("sssp", 31), ("components", 64)]


@pytest.mark.parametrize("specs", [
    [("sssp", s) for s in SOURCES[:6]],
    [("components", s) for s in SOURCES[:6]],
    [("pagerank", s) for s in SOURCES[:5]],
    MIXED], ids=["sssp", "components", "pagerank", "mixed"])
@pytest.mark.parametrize("entry", ["run", "serve"])
def test_run_is_the_loop_with_the_stop_given(g, specs, entry):
    want_srv, got_srv = _server(g), _server(g)
    for kind, s in specs:
        want_srv.submit(kind, source=s)
        got_srv.submit(kind, source=s)
    want = _parent_run(want_srv)
    if entry == "run":
        got = got_srv.run()
    else:
        got = []
        got_srv.stop()
        got_srv.serve(got.extend)
    assert [(r.qid, r.kind, r.source, r.iters, r.segments, r.converged)
            for r in got] \
        == [(r.qid, r.kind, r.source, r.iters, r.segments, r.converged)
            for r in want]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.answer, b.answer)
    assert len(got) == len(specs)
    # the stop is spent: the next run() drains again
    got_srv.submit(specs[0][0], source=specs[0][1])
    assert len(got_srv.run()) == 1
    assert got_srv.run() == []


def test_the_loop_blocks_without_work_wakes_on_submit_ends_on_stop(g):
    srv = _server(g)
    srv.submit("sssp", source=1)
    srv.run()
    tip = _tip()
    got = []
    delivered = threading.Event()

    def deliver(responses):
        got.extend(responses)
        delivered.set()

    th = threading.Thread(target=srv.serve, args=(deliver,),
                          daemon=True)
    th.start()
    time.sleep(0.3)
    assert th.is_alive()
    assert not _since(tip, "serve.turn.")       # nothing to turn for
    assert not _since(tip, "serve.idle")        # the wait is still open
    srv.submit("sssp", source=5)
    assert delivered.wait(timeout=60)
    assert [r.source for r in got] == [5]
    assert len(_since(tip, "serve.idle")) == 1
    time.sleep(0.2)
    assert th.is_alive()                        # blocked again
    n_turns = len(_since(tip, "serve.turn."))
    assert n_turns >= 1
    srv.stop()
    th.join(timeout=30)
    assert not th.is_alive()
    assert len(_since(tip, "serve.turn.")) == n_turns
    idle = _since(tip, "serve.idle")
    assert len(idle) == 2                       # one span a wait
    assert idle[0]["t1"] - idle[0]["t0"] >= 0.25
    assert serve._check_answers(g, got) == 0


def test_a_stop_before_the_loop_drains_what_is_queued_and_returns(g):
    srv = _server(g)
    tip = _tip()
    srv.stop()
    srv.serve(lambda responses: pytest.fail("nothing to deliver"))
    assert not _since(tip, "serve.")            # no turn, no wait
    for s in SOURCES[:3]:
        srv.submit("sssp", source=s)
    got = []
    srv.stop()
    srv.serve(got.extend)
    assert sorted(r.source for r in got) == sorted(SOURCES[:3])


def test_what_deliver_raises_ends_the_loop_and_spends_the_stop(g):
    srv = _server(g)
    srv.submit("sssp", source=3)
    srv.stop()

    def broken(_responses):
        raise KeyError("caller")
    with pytest.raises(KeyError):
        srv.serve(broken)
    assert srv._stopping is False


@pytest.mark.parametrize("kinds", [("sssp",),
                                   ("sssp", "components", "pagerank")],
                         ids=["one-kind", "three-kinds"])
def test_concurrent_submitters_get_dense_qids_in_queue_order(g, kinds):
    srv = _server(g)
    threads, per = 8, 50
    got = [[] for _ in range(threads)]
    go = threading.Barrier(threads)

    def submitter(i):
        go.wait()
        for j in range(per):
            got[i].append(srv.submit(kinds[(i + j) % len(kinds)],
                                     source=(i * per + j) % NV))

    ths = [threading.Thread(target=submitter, args=(i,))
           for i in range(threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)     # switch threads inside submit()
    try:
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in ths)
    qids = [q for mine in got for q in mine]
    assert sorted(qids) == list(range(threads * per))
    for mine in got:
        assert mine == sorted(mine)
    assert sorted(srv._collectors) == sorted(kinds)
    queued = []
    for kind in kinds:
        reqs = srv._collectors[kind].pending_requests()
        assert all(r.kind == kind for r in reqs)
        # a kind's queue holds its queries in qid order
        assert [r.qid for r in reqs] == sorted(r.qid for r in reqs)
        queued += [r.qid for r in reqs]
    assert sorted(queued) == list(range(threads * per))


def test_a_live_graph_is_released_response_by_response(g):
    from lux_tpu.livegraph import LiveGraph
    lg = LiveGraph(g, capacity=32)
    srv = _server(g, live=lg)
    n = 6
    for s in SOURCES[:n]:
        srv.submit("sssp", source=s)
    assert lg.admitted == n
    seen = []

    def deliver(responses):
        # inside the loop: what was handed over is released already,
        # what is still queued or resident is not
        seen.append((len(responses), lg.admitted))

    srv.stop()
    srv.serve(deliver)
    assert len(seen) >= 2                       # several turns retired
    left = n
    for count, admitted in seen:
        left -= count
        assert admitted == left
    assert left == 0 and lg.admitted == 0
