"""Runner ``serve_open``: an open loop against one ``serve.Server``.

Callers who do not wait for each other: a generator thread submits the
next seeded source at each instant of a fixed Poisson schedule
(``benchmarks/reference/arrivals.py``: rate and ``arrival_seed`` are
the TRAFFIC's, so every ``--seed`` offers the same load at the same
instants and orders the sources), whatever the service has done with
the queries before it.  The main thread runs the program's serving
loop, ``Server.serve(deliver)``: the responses a turn retired are
handed to ``deliver`` before the next turn starts, and while nothing is
queued or resident the loop blocks.  A program without that entry (a
commit before it) cannot tell a caller of a retirement while load
goes on: the runner refuses it before it loads anything (exit 2).

Latency is receipt (the instant ``deliver`` held the response) minus
the SCHEDULED arrival, both on the benchmark's clock: a generator that
runs late lengthens it and can never flatter it.  The lateness itself
(submit minus scheduled) is printed and is a per-layer metric.

Arrivals begin with the loop.  The first ``warm_s`` seconds are set-up;
the window opens at the first segment boundary that closes at or after
them and ends at the first boundary that closes at or after
``--seconds`` (queries retire in bursts at boundaries, as in
``serve_closed``).  The generator stops there, gives the loop its
stop, and the loop finishes outside the window.

``correct``: the hop distances of a seeded sample (``serve_closed``'s
check), every scheduled arrival submitted, every submitted query
answered exactly once, no response delivered after a later turn had
started, no query given a column before one submitted earlier.  The
last three count orders of instants and hold no time limit.
"""

from __future__ import annotations

import threading
import types

from benchmarks.harness import BenchmarkError, clock
from benchmarks.reference import arrivals
from benchmarks.runners import serve_closed
from benchmarks.runners.serve_mixed import percentile


def _param(run, name):
    """A traffic parameter; a configuration's ``rehearsal`` block may
    stand in its own (a rate the CPU sustains)."""
    return run.config[name] if name in run.config else run.traffic[name]


class Generator(threading.Thread):
    """The callers: submits at the schedule's instants until halted,
    then gives the server its stop."""

    def __init__(self, server, kind, sources, instants):
        super().__init__(daemon=True)
        self.server, self.kind, self.sources = server, kind, sources
        self.instants = instants
        self.t_start = None
        self.halt = threading.Event()
        self.t_halt = None              # set before ``halt``
        self.log = []                   # (qid, source, due, submitted)
        self.errors = []

    def run(self):
        try:
            for i, at in enumerate(self.instants):
                due = self.t_start + at
                delay = due - clock()
                if delay > 0:
                    self.halt.wait(delay)
                # whatever was due when the halt came is still sent
                if self.halt.is_set() and due > self.t_halt:
                    break
                source = int(self.sources[i % len(self.sources)])
                t = clock()
                qid = self.server.submit(self.kind, source=source)
                self.log.append((qid, source, due, t))
        except Exception as e:  # noqa: BLE001 - recorded, fails the run
            self.errors.append(repr(e))
        finally:
            self.server.stop()

    def stop_at(self, t):
        if not self.halt.is_set():
            self.t_halt = t
            self.halt.set()


class Loop:
    """What the serving thread sees: the program's events (a telemetry
    observer) and the responses handed over (``deliver``)."""

    def __init__(self, run, gen, warm_s):
        self.run, self.gen, self.warm_s = run, gen, warm_s
        self.t0 = None                  # the boundary that opens the window
        self.t_end = None               # the boundary that closed it
        self.deadline = None
        self.started = []               # qids in the order columns were taken
        self.retired = {}               # qid -> clock of ``query_done``
        self.received = {}              # qid -> clock of the hand-over
        self.responses = []
        self.twice = 0
        self.errors = []
        self._mark = None               # open profiler annotation

    # ``bench:boundary`` in the profiler's trace, drawn as
    # ``serve_closed`` draws it (it needs ``_mark`` alone): the idle
    # gaps of a traced window are named by it and the clocks are
    # paired on its ends
    boundary_label = serve_closed.Callers._boundary_span

    def _boundary_closed(self):
        now = clock()
        if self.t0 is None:
            if now >= self.gen.t_start + self.warm_s:
                self.t0 = self.run.begin_window()
                self.deadline = self.t0 + self.run.seconds
        elif self.t_end is None:
            if now >= self.deadline:
                self.t_end = now
                self.gen.stop_at(now)
            self.run.trace_tick()

    def on_event(self, ev):
        # the program swallows what an observer raises: keep it
        try:
            kind = ev.get("kind")
            if kind == "span":
                if ev.get("name") == "serve.boundary":
                    self._boundary_closed()
            elif kind == "segment":
                self.boundary_label(opening=True)
            elif kind == "serve_refill":
                self.boundary_label(opening=False)
            elif kind == "query_start":
                self.started.append(ev["qid"])
            elif kind == "query_done":
                self.retired.setdefault(ev["qid"], clock())
        except Exception as e:  # noqa: BLE001 - recorded, fails the run
            self.errors.append(repr(e))

    def deliver(self, responses):
        now = clock()
        for r in responses:
            if r.qid in self.received:
                self.twice += 1
            self.received[r.qid] = now
        self.responses += responses


def prepare(run):
    from lux_tpu import serve
    if not (callable(getattr(serve.Server, "serve", None))
            and callable(getattr(serve.Server, "stop", None))):
        raise BenchmarkError(
            "this program has no serving loop that answers at "
            "retirement (serve.Server.serve / stop): an open loop "
            "cannot learn of a retirement while arrivals go on")
    return serve_closed.prepare(run)


def window(run, st):
    from lux_tpu import telemetry

    rate = float(_param(run, "rate_qps"))
    st.rate = rate
    st.arrival_seed = int(run.traffic["arrival_seed"])
    gen = Generator(st.server, st.kind, st.sources,
                    arrivals.arrivals(rate, st.arrival_seed))
    loop = Loop(run, gen, float(_param(run, "warm_s")))
    st.gen, st.loop = gen, loop
    telemetry.add_observer(loop.on_event)
    try:
        gen.t_start = clock()
        gen.start()
        with run.span("server_run"):
            st.server.serve(loop.deliver)
    finally:
        gen.stop_at(clock())
        gen.join()
        loop.boundary_label(opening=False)
        telemetry.remove_observer(loop.on_event)
    if loop.t0 is None or loop.t_end is None:
        raise RuntimeError("the loop ended before its window did: "
                           + "; ".join(loop.errors + gen.errors))
    elapsed = loop.t_end - loop.t0
    due = {qid: t_due for qid, _s, t_due, _t in gen.log}
    st.responses = loop.responses
    st.in_window = [q for q, t in loop.retired.items()
                    if loop.t0 < t <= loop.t_end and q in loop.received]
    lat = [(loop.received[q] - due[q]) * 1e3 for q in st.in_window]
    late = [(t - t_due) * 1e3 for _q, _s, t_due, t in gen.log
            if loop.t0 < t_due <= loop.t_end]
    # the window's events only, for the readers
    run.events = [e for e in run.events
                  if loop.t0 < e["clock"] <= loop.t_end]
    if lat:
        run.metrics["serve_qps"] = len(lat) / elapsed
        run.metrics["query_ms.p95"] = percentile(sorted(lat), 0.95)
    if late:
        run.counters["late_ms_p99"] = percentile(sorted(late), 0.99)
    queued = [e["queued"] for e in run.events
              if e["kind"] == "serve_refill"]
    print(f"window: offered {rate} qps, {len(late)} arrivals and "
          f"{len(lat)} retirements inside {elapsed:.3f} s; latency "
          f"samples {len(lat)}, median "
          f"{percentile(sorted(lat), 0.5):.1f} ms; generator "
          f"lateness over {len(late)} arrivals p99 "
          f"{run.counters.get('late_ms_p99', float('nan')):.3f} ms, "
          f"most {max(late, default=float('nan')):.3f} ms; queue after "
          f"a refill: most {max(queued, default=0)}, last "
          f"{queued[-1] if queued else 0}", flush=True)


def verify(run, st):
    """``serve_closed``'s check of the answers (every submitted query
    answered; a seeded sample of those retired in the window, the one
    with most iterations among them, against the reference's hop
    distances), then the open loop's own counts."""
    from lux_tpu import telemetry

    gen, loop = st.gen, st.loop
    st.callers = types.SimpleNamespace(
        submitted={qid: (source, t) for qid, source, _d, t in gen.log},
        errors=gen.errors + loop.errors)
    serve_closed.verify(run, st)
    limits = run.config["guarantees"]
    sent = [t_due - gen.t_start for _q, _s, t_due, _t in gen.log]
    run.check("arrivals_missed", arrivals.arrivals_missed(
        st.rate, st.arrival_seed, gen.t_halt - gen.t_start, sent),
        limits["arrivals_missed"])
    qids = [qid for qid, _s, _d, _t in gen.log]
    unanswered = sum(1 for q in qids if q not in loop.received)
    run.check("answered_not_once", unanswered + loop.twice,
              limits["answered_not_once"])
    answered = [q for q in qids
                if q in loop.received and q in loop.retired]
    turns = [r["t0"] for r in telemetry.spans()
             if r["name"].startswith("serve.turn.")]
    run.check("delivered_late", arrivals.delivered_late(
        [loop.retired[q] for q in answered],
        [loop.received[q] for q in answered], turns),
        limits["delivered_late"])
    position = {qid: i for i, qid in enumerate(qids)}
    run.check("fifo_inversions", arrivals.fifo_inversions(
        [position[q] for q in loop.started if q in position]),
        limits["fifo_inversions"])
