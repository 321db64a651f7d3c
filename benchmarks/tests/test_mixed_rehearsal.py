"""``mixed.kron20.closed`` rehearsed on the CPU with the served path
broken underneath: an answer of each kind altered where it is
produced, and a kind served until its queue is empty.  ``correct`` has
to come out false, and the run has to end."""

import numpy as np
import pytest

from benchmarks import harness

CELL = "mixed.kron20.closed"


def _run(seed=2**31 + 7, seconds=2.0):
    return harness.run_cell(CELL, seed, seconds, False, rehearsal=True)


def test_the_sound_run_first():
    r = _run()
    assert r["correct"] is True and r["failed"] == 0
    assert {"serve_qps", "query_ms.p95", "setup_s"} <= set(r["metrics"])


@pytest.mark.parametrize("kind", ["sssp", "components", "pagerank"])
def test_an_answer_altered_where_it_is_produced_fails(monkeypatch,
                                                      kind):
    from lux_tpu import serve
    real = serve._RunnerBase._retire

    def altered(self, col, answer, total_iters, converged=True):
        if self.kind == kind:
            answer = np.array(answer)
            if kind == "sssp":          # one level off
                answer[int(np.argmax(answer == 1))] = 2
            elif kind == "components":  # one reached vertex missed
                answer[int(np.argmax(answer >= 0))] = -1
            else:                       # bfloat16's last bit, one rank
                v = int(np.argmax(answer))
                answer[v] *= np.float32(1 + 2.0 ** -8)
        return real(self, col, answer, total_iters, converged)
    monkeypatch.setattr(serve._RunnerBase, "_retire", altered)
    r = _run()
    assert r["correct"] is False and r["failed"] > 0


def _exhaustive(self):
    """``Server.run`` before the turns: each kind until its queue is
    empty."""
    out = []
    for kind, coll in list(self._collectors.items()):
        while len(coll):
            out += self._runner(kind).drain(coll, self.deadline_s)
    return out


def test_a_kind_served_to_exhaustion_fails_and_ends(monkeypatch):
    """The drain loop before the turns: each kind until its queue is
    empty.  The other kinds' callers wait until the window is over."""
    from lux_tpu import serve
    monkeypatch.setattr(serve.Server, "run", _exhaustive)
    r = _run()
    assert r["correct"] is False and r["failed"] > 0
    assert r["attempted"] > 0


def _turn_without_span(self, collector, deadline_s=0.0, switch=False):
    """``_RunnerBase.turn`` as a program without the turn spans would
    have it: the same segment and boundary, no ``serve.turn.*``."""
    n0 = len(self.responses)
    if self._segments is None:
        self._segments = self._begin(collector, deadline_s)
    if self._segments is not None:
        try:
            next(self._segments)
        except StopIteration:
            self._segments = None
        else:
            if not self._occupied():
                self._segments = None
    return self.responses[n0:]


@pytest.mark.parametrize("exhaustive", [False, True])
def test_a_program_without_the_turn_spans_fails_and_ends(
        monkeypatch, exhaustive):
    """No ``serve.turn.*`` span to read the scheduling rule from: the
    run ends with a result line and ``correct`` false, whether the
    program shares the chip all the same or is the commit before the
    turns (no span AND a kind served until its queue is empty)."""
    from lux_tpu import serve
    monkeypatch.setattr(serve._RunnerBase, "turn", _turn_without_span)
    if exhaustive:
        monkeypatch.setattr(serve.Server, "run", _exhaustive)
    r = _run()
    assert r["correct"] is False
    if not exhaustive:      # the scheduling check alone decided
        assert r["failed"] == 0
    assert {"serve_qps", "query_ms.p95", "setup_s"} <= set(r["metrics"])


def test_the_ramp_counts_boundaries_of_every_runner_and_nothing_else():
    """A step at the start and at each of the first two boundaries,
    counted in closes of ``serve.boundary``: what the push driver
    alone reports (``segment``) and what only a boundary that worked
    reports (``serve_refill``) move nothing, so a runner that starts
    or stops reporting them shifts no caller."""
    from benchmarks.runners import serve_mixed

    class Server:
        def __init__(self):
            self.got = []

        def submit(self, kind, source):
            self.got.append(kind)
            return len(self.got)

    class Run:
        seconds = 45.0
        opened = 0

        def begin_window(self):
            self.opened += 1
            return harness.clock()

        def trace_tick(self):
            pass

    server, run = Server(), Run()
    kinds = ["sssp", "components", "pagerank"]
    callers = serve_mixed.MixedCallers(
        run, server, {k: [1, 2, 3] for k in kinds},
        [[6, 5, 5], [5, 6, 5], [5, 5, 6]])
    callers._boundary_span = lambda opening: None     # no profiler here
    callers.start()
    seen = [len(server.got)]
    for _ in range(4):
        callers.on_event({"kind": "segment", "engine": "push"})
        callers.on_event({"kind": "serve_refill", "query_kind": "sssp"})
        callers.on_event({"kind": "span", "name": "serve.boundary.fill"})
        callers.on_event({"kind": "span", "name": "serve.boundary"})
        seen.append(len(server.got))
        assert (callers.t0 is not None) == (len(seen) >= 4)
    assert seen == [16, 32, 48, 48, 48]
    assert [server.got[:16].count(k) for k in kinds] == [6, 5, 5]
    assert [server.got[16:32].count(k) for k in kinds] == [5, 6, 5]
    assert [server.got.count(k) for k in kinds] == [16, 16, 16]
    assert run.opened == 1 and not callers.errors
