"""Runner ``serve_mixed``: three populations of closed-loop callers,
one per query kind, against ONE ``serve.Server``.

What ``serve_closed`` says of a closed loop holds here (retirement is
learnt through ``query_done`` on the serving thread, latency is
submit-to-retire on the benchmark's clock, the window ends at a
boundary); the difference is that each caller is bound to one of the
configuration's ``kinds`` for the whole run and goes round that kind's
own list of sources, so the server always holds work of every kind and
has to share the chip among its runners.

A boundary here is the close of the program's ``serve.boundary`` span,
which every runner leaves at every boundary, whether it retired or
refilled anything or not (``serve_refill`` goes out only where it did,
``segment`` only from the push driver).  The ramp and the window's two
ends are counted in those and in nothing else: 16 callers before the
drain, 16 at each of the first two boundaries (in a ring of three, the
first two kinds' first), the window from the third.  A step goes out
when its boundary has closed, so what it queues for the kind that has
just had its turn waits a round for that kind's next boundary; those
ten latencies are three rounds, like a query that needs three segments.

Besides the answers, the run is held to the scheduling rule itself:
``starved_turns`` is the most turns of OTHER kinds that ran between two
consecutive turns of a kind that had work, read from the program's own
``serve.turn.<family>`` spans (count ``kind``) over the whole window.
Round-robin over three kinds gives 2; a program that serves one kind
until its queue is empty, or that has no such spans, fails the check
(and still ends: the callers stop at the window's end).
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks import graphs
from benchmarks.harness import clock
from benchmarks.readers import program_span
from benchmarks.reference import bfs, ppr, reach
from benchmarks.runners import common
from benchmarks.runners.serve_closed import Callers


class MixedCallers(Callers):
    """The closed loop of ``serve_closed`` with a kind per caller:
    ``sources`` maps each kind to its list, a ``ramp`` step says how
    many callers of each kind start."""

    def __init__(self, run, server, sources, ramp):
        super().__init__(run, server, None, sources, ramp)
        self.kinds = list(sources)
        self.kind_of = {}               # qid -> kind
        self._next = dict.fromkeys(self.kinds, 0)

    def _submit(self, kind):
        order = self.sources[kind]
        source = int(order[self._next[kind] % len(order)])
        self._next[kind] += 1
        t = clock()
        qid = self.server.submit(kind, source=source)
        self.submitted[qid] = (source, t)
        self.kind_of[qid] = kind

    def _ramp_step(self):
        for kind, n in zip(self.kinds, self.ramp.pop(0)):
            for _ in range(n):
                self._submit(kind)

    def _boundary(self):
        """A boundary of some runner has closed: the ramp's next step
        while it lasts (the first went out before the drain, so the
        callers have started by the close of the second boundary),
        then the window opens at the boundary after the last step."""
        if self.t0 is None:
            if self.ramp:
                self._ramp_step()
            else:
                self.t0 = self.run.begin_window()
                self.deadline = self.t0 + self.run.seconds
            return
        now = clock()
        if self.t_end is None and now >= self.deadline:
            self.t_end = now
        self.run.trace_tick()

    def on_event(self, ev):
        # the program swallows what an observer raises: keep it
        try:
            kind = ev.get("kind")
            if kind == "segment":
                # the profiler's label for the host's part of a turn;
                # only the push driver reports a segment here, so a
                # pull boundary's gap goes by the program's own spans
                self._boundary_span(opening=True)
            elif kind == "span" and ev.get("name") == "serve.boundary":
                self._boundary_span(opening=False)
                self._boundary()
            if kind != "query_done":
                return
            qid = ev["qid"]
            if qid not in self.submitted or qid in self.retired:
                return
            self.retired[qid] = clock()
            if self.t_end is None:
                self._submit(self.kind_of[qid])
        except Exception as e:  # noqa: BLE001 - recorded, fails the run
            self.errors.append(repr(e))


def fixed_sources(run, offsets) -> dict:
    """The cell's sources, a block of ``sources_per_kind`` a kind:
    the same for every ``--seed`` (``control_mixed.py`` draws its
    own from the pagerank block)."""
    per_kind = int(run.traffic["sources_per_kind"])
    fixed = common.fixed_vertices(run, offsets, 5,
                                  int(run.traffic["sources"]))
    return {kind: fixed[i * per_kind:(i + 1) * per_kind]
            for i, kind in enumerate(run.config["kinds"])}


def prepare(run):
    from lux_tpu import serve
    from lux_tpu.graph import Graph

    st = types.SimpleNamespace()
    c, t = run.config, run.traffic
    paths = common.cached_graph(run)
    offsets = np.load(paths["ref_offsets"])
    st.kinds = list(c["kinds"])
    st.batch = int(c["batch"])
    per_kind = int(t["sources_per_kind"])
    if per_kind * len(st.kinds) != int(t["sources"]) or \
            int(t["callers_per_kind"]) * len(st.kinds) != int(t["callers"]):
        raise ValueError("the traffic's sources and callers must "
                         "divide evenly over the configuration's kinds")
    warm = common.fixed_vertices(run, offsets, 4,
                                 st.batch * len(st.kinds))
    # the same sources for every seed, each kind's block in an order
    # drawn from the seed
    st.sources = {
        kind: common.seeded_order(run, 5 + i, block)
        for i, (kind, block) in enumerate(
            fixed_sources(run, offsets).items())}
    del offsets
    with run.span("load_layout"):
        g = Graph.from_file(paths["lux"], weighted=None)
    run.graph = {"nv": int(g.nv), "stored_edges": int(g.ne),
                 "generated_edges": int(paths["generated_edges"])}
    seg, tol = c.get("seg_iters"), c.get("tol")
    opts = {} if tol is None else {"tol": float(tol)}
    st.server = serve.Server(
        g, batch=st.batch, num_parts=int(c["num_parts"]),
        seg_iters=serve.DEFAULT_SEG_ITERS if seg is None else int(seg),
        **opts)
    # one warm drain a kind: its engine is built lazily inside it, and
    # its first query_start marks where building ends and compiling
    # begins; then one drain of all three at once, the way the window
    # runs them
    for i, kind in enumerate(st.kinds):
        t_build = clock()
        for s in warm[i * st.batch:(i + 1) * st.batch]:
            st.server.submit(kind, source=int(s))
        n_events = len(run.events)
        st.server.run()
        t_done = clock()
        starts = [e["clock"] for e in run.events[n_events:]
                  if e["kind"] == "query_start"]
        t_first = starts[0] if starts else t_done
        run.spans.append(("engine_build", t_build, t_first))
        run.spans.append(("compile_warm", t_first, t_done))
    t_mixed = clock()
    for i, kind in enumerate(st.kinds):
        st.server.submit(kind, source=int(warm[i * st.batch]))
    st.server.run()
    run.spans.append(("compile_warm", t_mixed, clock()))
    del run.events[:]
    return st


def percentile(sorted_ms, q: float) -> float:
    """Nearest-rank percentile of sorted samples (NaN of none)."""
    if not sorted_ms:
        return float("nan")
    return sorted_ms[min(len(sorted_ms) - 1,
                         int(np.ceil(q * len(sorted_ms))) - 1)]


def turn_records(t0: float, t1: float, ring=None):
    """The program's ``serve.turn.*`` records that began in
    ``[t0, t1)``, in order; None where the program has no span ring,
    [] where it has no such span."""
    ring = program_span.ring() if ring is None else ring
    if ring is None:
        return None
    return sorted((r for r in ring
                   if r["name"].startswith("serve.turn.")
                   and t0 <= r["t0"] < t1), key=lambda r: r["t0"])


def starved_turns(turns, kinds, holding) -> int:
    """The most turns of other kinds between two consecutive turns of
    a kind that had work.  ``turns``: (clock, kind) in order;
    ``holding(kind, clock)``: did a caller of ``kind`` hold an
    unanswered query then."""
    worst = 0
    for kind in kinds:
        waited = 0
        for t, served in turns:
            if served == kind or not holding(kind, t):
                waited = 0
                continue
            waited += 1
            worst = max(worst, waited)
    return worst


def window(run, st):
    from lux_tpu import telemetry

    ramp = [[int(n) for n in step] for step in run.traffic["ramp"]]
    per_kind = int(run.traffic["callers_per_kind"])
    if any(len(step) != len(st.kinds) for step in ramp) or any(
            sum(step[i] for step in ramp) != per_kind
            for i in range(len(st.kinds))):
        raise ValueError("the traffic's ramp must give every kind its "
                         "callers_per_kind")
    callers = MixedCallers(run, st.server, st.sources, ramp)
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
    st.t0, st.t_end = callers.t0, end
    elapsed = end - callers.t0
    st.in_window = [q for q in callers.retired
                    if callers.t0 < callers.retired[q] <= end]
    # a latency sample is every retirement of the window, whenever
    # the query was submitted (as ``serve_closed`` counts them), so
    # the rate and the tail are over the same queries
    ms = {kind: sorted(
        (callers.retired[q] - callers.submitted[q][1]) * 1e3
        for q in st.in_window if callers.kind_of[q] == kind)
        for kind in st.kinds}
    # beside it, per kind: the tail of those submitted inside the
    # window too.  What a ramp step queued waited up to a round for
    # its kind's next boundary, and that wait is in the tail above
    fresh = {kind: sorted(
        (callers.retired[q] - callers.submitted[q][1]) * 1e3
        for q in st.in_window if callers.kind_of[q] == kind
        and callers.submitted[q][1] >= callers.t0)
        for kind in st.kinds}
    lat = sorted(x for v in ms.values() for x in v)
    # the boundary events of the window only, for the readers
    run.events = [e for e in run.events
                  if callers.t0 < e["clock"] <= end]
    if lat:
        run.metrics["serve_qps"] = len(lat) / elapsed
        run.metrics["query_ms.p95"] = percentile(lat, 0.95)
    all_fresh = sorted(x for v in fresh.values() for x in v)
    p95 = percentile(lat, 0.95)
    # latencies are whole rounds, so the percentile sits on a cluster:
    # how many samples hold it there, against how many it takes
    held = sum(1 for x in lat if x >= 0.98 * p95)
    print(f"window: {len(lat)} of {len(callers.submitted)} queries "
          f"retired inside {elapsed:.3f} s; latency samples "
          f"{len(lat)}, median {percentile(lat, 0.5):.1f} ms, p95 "
          f"{p95:.1f} ms ({held} samples within 2% of it or above, "
          f"{len(lat) - int(np.ceil(0.95 * len(lat))) + 1} hold it); "
          f"of the {len(all_fresh)} submitted inside the window too, "
          f"p95 {percentile(all_fresh, 0.95):.1f} ms", flush=True)
    for kind in st.kinds:
        print(f"  {kind:<11s} retired {len(ms[kind]):4d}  median "
              f"{percentile(ms[kind], 0.5):9.1f} ms  p95 "
              f"{percentile(ms[kind], 0.95):9.1f} ms  slowest "
              f"{ms[kind][-1] if ms[kind] else float('nan'):9.1f} ms"
              f"  submitted inside {len(fresh[kind]):4d}  p95 "
              f"{percentile(fresh[kind], 0.95):9.1f} ms",
              flush=True)
    print_turns(st)


def print_turns(st):
    """The window's turns by runner family from the program's spans,
    in every run (the per-layer metrics of a traced run read the same
    records): number, mean, share of the turn seconds; and what a pull
    boundary that worked moved."""
    ring = program_span.ring()
    turns = turn_records(st.t0, st.t_end, ring)
    if not turns:
        print("turns: the program has no serve.turn.* span",
              flush=True)
        return
    total = sum(r["t1"] - r["t0"] for r in turns)
    for family in sorted({r["name"] for r in turns}):
        s = [r["t1"] - r["t0"] for r in turns if r["name"] == family]
        print(f"  {family:<16s} x{len(s):<4d} mean "
              f"{sum(s) / len(s) * 1e3:8.1f} ms  share "
              f"{100 * sum(s) / total:5.1f}% of turn seconds",
              flush=True)
    ids = {r["id"] for r in ring
           if r["name"] == "serve.boundary"
           and r["counts"].get("family") == "pull"
           and r["counts"].get("worked") == 1
           and st.t0 <= r["t0"] < st.t_end}
    moved = [r["counts"].get("bytes", 0) for r in ring
             if r["name"] == "serve.boundary.fetch"
             and r["parent"] in ids]
    if moved:
        print(f"  pull boundaries that worked: {len(moved)}, "
              f"serve.boundary.fetch bytes each "
              f"{sum(moved) / len(moved):.0f}", flush=True)


def check_sssp(run, resp, source, adjacency):
    offsets, neighbours = adjacency
    want = graphs.cached_array(
        run.graph_paths, f"ref_bfs_{source}",
        lambda: bfs.bfs_levels(offsets, neighbours, source))
    return {"hops_mismatched": int(np.count_nonzero(
        bfs.hops_to_levels(resp.answer, run.graph["nv"]) != want))}


def check_components(run, resp, source, adjacency):
    offsets, neighbours = adjacency
    levels = graphs.cached_array(
        run.graph_paths, f"ref_bfs_{source}",
        lambda: bfs.bfs_levels(offsets, neighbours, source))
    want = reach.labels_from_levels(levels, source)
    return {"reach_mismatched": int(np.count_nonzero(
        np.asarray(resp.answer) != want))}


def check_pagerank(run, resp, source, adjacency):
    offsets, neighbours = adjacency
    want = graphs.cached_array(
        run.graph_paths, f"ref_ppr_{source}_{int(resp.iters)}",
        lambda: ppr.personalized_pagerank(offsets, neighbours, source,
                                          int(resp.iters)))
    return ppr.compare_ranks(ppr.to_ranks(resp.answer, offsets), want)


CHECKS = {"sssp": (check_sssp, ("hops_mismatched",)),
          "components": (check_components, ("reach_mismatched",)),
          "pagerank": (check_pagerank, ("ppr_l1_rel_err",
                                        "ppr_max_rel_err"))}


def verify(run, st):
    """Every query answered once; per kind a seeded sample of those
    retired in the window, the one with most iterations of its kind
    among them, against the references; no kind starved."""
    callers = st.callers
    limits = run.config["guarantees"]
    by_qid = {r.qid: r for r in st.responses}
    run.attempted = len(callers.submitted)
    run.failed = sum(1 for q in callers.submitted if q not in by_qid)
    run.failed += len(st.responses) - len(by_qid)   # answered twice
    run.failed += len(callers.errors)
    for e in callers.errors:
        print(f"caller error: {e}", flush=True)
    adjacency = graphs.load_reference(run.graph_paths)
    for i, kind in enumerate(st.kinds):
        check, names = CHECKS[kind]
        gots = []
        pool = [q for q in st.in_window
                if q in by_qid and callers.kind_of[q] == kind]
        if not pool:
            run.failed += 1     # a kind that retired nothing
        else:
            longest = max(range(len(pool)),
                          key=lambda j: by_qid[pool[j]].iters)
            rng = np.random.default_rng([run.seed % (1 << 63), 16 + i])
            picked = common.sample_indices(
                rng, len(pool),
                int(run.traffic["check_queries_per_kind"]),
                always=[longest])
            for j in picked:
                resp = by_qid[pool[j]]
                source = callers.submitted[resp.qid][0]
                if resp.source != source or resp.kind != kind:
                    got = dict.fromkeys(names, float("nan"))
                else:
                    got = check(run, resp, source, adjacency)
                if not all(got[n] <= limits[n] for n in names):
                    run.failed += 1
                gots.append(got)
            print(f"checked {len(picked)} of {len(pool)} {kind} "
                  f"queries retired in the window (most iterations "
                  f"{by_qid[pool[longest]].iters})", flush=True)
        for n in names:     # the largest reading; NaN where none
            vals = [got[n] for got in gots]
            run.check(n, max(vals) if vals and all(
                v == v for v in vals) else float("nan"), limits[n])
    # no kind starved, from the program's own turn spans
    turns = turn_records(st.t0, st.t_end)
    if turns:
        def holding(kind, t):
            return any(callers.kind_of[q] == kind and sub <= t
                       < callers.retired.get(q, float("inf"))
                       for q, (_s, sub) in callers.submitted.items())
        value = starved_turns(
            [(r["t0"], r["counts"].get("kind")) for r in turns],
            st.kinds, holding)
    else:
        # no turn to read: every segment of the window may have been
        # another kind's
        value = max(1 + limits["starved_turns"],
                    sum(1 for e in run.events if e["kind"] == "segment"))
        print("the program has no serve.turn.* span in the window",
              flush=True)
    run.check("starved_turns", value, limits["starved_turns"])
