"""Runner ``serve_closed``: a closed loop of callers against one
``serve.Server``.

Each caller submits its next seeded source the moment it learns that
its last query retired.  It learns it the only way the program offers
under sustained load: ``Server.run()`` hands responses back only when
a drain ends, so retirement is seen through ``telemetry.add_observer``
on ``query_done``.  The observer runs on the serving thread, so a
caller's next query is in the queue before the same boundary refills:
callers with no think time and no network, one process, one thread.
Latency is submit-to-retire on the benchmark's clock.

A boundary that retires or refills costs the host some 0.3-0.5 s, one
that does neither about 10 ms (my chip runs, PR 23).  Callers that all
start at once stay in step (every query here takes two segments), so
only every second boundary works, until the first slower query puts a
column out of step; from then on every boundary works and throughput
is 7% lower.  Which regime a run saw depended on the order of its
sources.  A service that has run for a while is out of step, so the
loop is brought there before the window: the callers start over the
first boundaries (``ramp``: how many at each), counted as set-up, and
the window begins at the boundary after the last of them.

Queries retire in bursts, at segment boundaries, so a window cut at a
fixed instant would count a whole burst or none of it.  The window
therefore ends at the first boundary at or after ``--seconds`` (as a
batch runner finishes the solve it is in): the rate is every query
retired in the window over all of the window's time.
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks import graphs
from benchmarks.harness import clock
from benchmarks.reference import bfs as ref
from benchmarks.runners import common


class Callers:
    """The closed loop.  ``on_event`` is a telemetry observer."""

    def __init__(self, run, server, kind, sources, ramp):
        self.run, self.server, self.kind = run, server, kind
        self.sources = sources          # gone round as often as needed
        self.ramp = list(ramp)          # callers to start, per boundary
        self.t0 = None                  # the boundary that opens the window
        self.submitted = {}             # qid -> (source, submit clock)
        self.retired = {}               # qid -> retire clock
        self.deadline = None
        self.t_end = None               # the boundary that closed the window
        self.errors = []
        self._next = 0
        self._mark = None               # open profiler annotation

    def _submit(self):
        source = int(self.sources[self._next % len(self.sources)])
        self._next += 1
        t = clock()
        qid = self.server.submit(self.kind, source=source)
        self.submitted[qid] = (source, t)

    def start(self):
        self._ramp_step()

    def _ramp_step(self):
        for _ in range(self.ramp.pop(0)):
            self._submit()

    def _boundary_span(self, opening: bool):
        """The host's retire/refill work between two device segments
        as a span in the profiler's trace (``bench:boundary``), from
        the program's own events: the idle gaps of a traced window are
        named by it."""
        import jax
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
            self._mark = None
        if opening:
            self._mark = jax.profiler.TraceAnnotation("bench:boundary")
            self._mark.__enter__()

    def on_event(self, ev):
        # the program swallows what an observer raises: keep it
        try:
            kind = ev.get("kind")
            if kind == "segment":
                self._boundary_span(opening=True)
                if self.ramp:
                    self._ramp_step()
            elif kind == "serve_refill":
                self._boundary_span(opening=False)
                if self.t0 is None:
                    if not self.ramp:
                        self.t0 = self.run.begin_window()
                        self.deadline = self.t0 + self.run.seconds
                    return
                now = clock()
                if self.t_end is None and now >= self.deadline:
                    self.t_end = now
                self.run.trace_tick()
            if kind != "query_done":
                return
            qid = ev["qid"]
            if qid not in self.submitted or qid in self.retired:
                return
            self.retired[qid] = clock()
            if self.t_end is None:
                self._submit()
        except Exception as e:  # noqa: BLE001 - recorded, fails the run
            self.errors.append(repr(e))


def prepare(run):
    from lux_tpu import serve
    from lux_tpu.graph import Graph

    st = types.SimpleNamespace()
    c = run.config
    paths = common.cached_graph(run)
    offsets = np.load(paths["ref_offsets"])
    st.kind = c["kind"]
    st.batch = int(c["batch"])
    warm = common.fixed_vertices(run, offsets, 4, st.batch)
    # the same sources for every seed, in an order drawn from the seed;
    # the window goes round the list about once
    st.sources = common.seeded_order(run, 5, common.fixed_vertices(
        run, offsets, 5, int(run.traffic["sources"])))
    del offsets
    with run.span("load_layout"):
        g = Graph.from_file(paths["lux"], weighted=None)
    run.graph = {"nv": int(g.nv), "stored_edges": int(g.ne),
                 "generated_edges": int(paths["generated_edges"])}
    seg = c.get("seg_iters")
    # the engine is built lazily inside the first drain; the first
    # query_start event marks where building ends and compiling begins
    t_build = clock()
    st.server = serve.Server(
        g, batch=st.batch, num_parts=int(c["num_parts"]),
        seg_iters=serve.DEFAULT_SEG_ITERS if seg is None else int(seg))
    for s in warm:
        st.server.submit(st.kind, source=int(s))
    n_events = len(run.events)
    st.server.run()
    t_done = clock()
    starts = [e["clock"] for e in run.events[n_events:]
              if e["kind"] == "query_start"]
    t_first = starts[0] if starts else t_done
    run.spans.append(("engine_build", t_build, t_first))
    run.spans.append(("compile_warm", t_first, t_done))
    del run.events[:]
    return st


def window(run, st):
    from lux_tpu import telemetry

    ramp = [int(n) for n in run.traffic["ramp"]]
    if sum(ramp) != int(run.traffic["callers"]):
        raise ValueError("the traffic's ramp must add up to its callers")
    callers = Callers(run, st.server, st.kind, st.sources, ramp)
    st.callers = callers
    telemetry.add_observer(callers.on_event)
    st.responses = []
    try:
        callers.start()
        with run.span("server_run"):
            while len(st.responses) < len(callers.submitted):
                got = st.server.run()
                if not got:
                    break
                st.responses += got
    finally:
        callers._boundary_span(opening=False)
        telemetry.remove_observer(callers.on_event)
    if callers.t0 is None:
        raise RuntimeError("the drain ended before the ramp did: "
                           + "; ".join(callers.errors))
    end = callers.t_end if callers.t_end is not None else clock()
    elapsed = end - callers.t0
    st.in_window = [q for q in callers.retired
                    if callers.t0 < callers.retired[q] <= end]
    lat = sorted((callers.retired[q] - callers.submitted[q][1]) * 1e3
                 for q in st.in_window)
    # the boundary events of the window only, for the readers
    run.events = [e for e in run.events
                  if callers.t0 < e["clock"] <= end]
    traced_end = (callers.t0 + run.trace_window_s
                  if run.trace_window_s else end)
    run.counters["traced_iters"] = sum(
        int(e.get("iters", 0)) for e in run.events
        if e["kind"] == "segment" and e["clock"] <= traced_end)
    if lat:
        run.metrics["serve_qps"] = len(lat) / elapsed
        # nearest-rank 95th percentile of the raw client-side samples
        run.metrics["query_ms.p95"] = lat[
            min(len(lat) - 1, int(np.ceil(0.95 * len(lat))) - 1)]
    print(f"window: {len(lat)} of {len(callers.submitted)} queries "
          f"retired inside {elapsed:.3f} s; latency samples "
          f"{len(lat)}, median "
          f"{lat[len(lat) // 2] if lat else float('nan'):.1f} ms",
          flush=True)


def verify(run, st):
    """Every query answered once; a seeded sample of those retired in
    the window, the one with most iterations among them, against the
    reference's hop distances."""
    callers = st.callers
    by_qid = {r.qid: r for r in st.responses}
    run.attempted = len(callers.submitted)
    run.failed = sum(1 for q in callers.submitted if q not in by_qid)
    run.failed += len(callers.errors)
    for e in callers.errors:
        print(f"caller error: {e}", flush=True)
    offsets, neighbours = graphs.load_reference(run.graph_paths)
    nv = run.graph["nv"]
    pool = [q for q in st.in_window if q in by_qid]
    mismatched = 0
    if pool:
        longest = max(range(len(pool)),
                      key=lambda i: by_qid[pool[i]].iters)
        rng = np.random.default_rng([run.seed % (1 << 63), 6])
        picked = common.sample_indices(
            rng, len(pool), int(run.traffic["check_queries"]),
            always=[longest])
        for i in picked:
            resp = by_qid[pool[i]]
            source = callers.submitted[resp.qid][0]
            want = graphs.cached_array(
                run.graph_paths, f"ref_bfs_{source}",
                lambda: ref.bfs_levels(offsets, neighbours, source))
            bad = 1 if resp.source != source else int(np.count_nonzero(
                ref.hops_to_levels(resp.answer, nv) != want))
            if bad:
                run.failed += 1
            mismatched += bad
        print(f"checked {len(picked)} of {len(pool)} queries retired "
              f"in the window", flush=True)
    else:
        run.failed += 1
    run.check("hops_mismatched", mismatched,
              run.config["guarantees"]["hops_mismatched"])
