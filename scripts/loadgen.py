#!/usr/bin/env python
"""Open-loop Poisson load harness for the serving front-end.

Drives a ``lux_tpu.serve.Server`` with OPEN-LOOP arrivals — a seeded
Poisson process submits queries on its own wall-clock schedule,
independent of service progress, which is the only arrival discipline
under which queue wait is an honest signal (closed-loop harnesses
self-throttle and hide saturation; the Ragged-Paged-Attention-style
serving stacks in PAPERS.md are judged on exactly these
latency-vs-offered-rate curves).  Per ramp step:

- a submitter thread draws exponential inter-arrival gaps at the
  step's offered rate (seeded rng: the query set and schedule are
  reproducible) and submits a mixed-kind round-robin of query kinds;
- the main thread runs the serving loop (``Server.serve``:
  continuous-batching refill; the responses a turn retired are handed
  over before the next turn starts, and while nothing is queued or
  resident the loop blocks until the next ``submit``).  The submitter
  gives the stop (``Server.stop``) after its last query, so the loop
  ends when everything submitted is answered: nothing polls;
- the step's latency distribution is read BACK from the server's
  ``metrics_snapshot`` (lux_tpu/metrics.py) — per-kind log-linear
  histograms merged bucket-wise into one distribution — rather than
  recomputed from raw timestamps, so the harness exercises the same
  aggregation path every later SLO consumer will trust;
- offered vs achieved rates are both measured from the load start
  (offered = submitted / time-to-last-enqueue, achieved = served /
  time-to-last-retire), so achieved <= offered holds BY CONSTRUCTION
  — the contradiction scripts/check_bench.py rejects can only come
  from a lying line, never from honest timing.

The report is the latency-vs-offered-rate table plus the measured
SATURATION KNEE: the first ramp step whose achieved rate falls under
``KNEE_FRACTION`` of its offered rate.  ``bench.py -config
serve-slo`` wraps ``run_step`` into calibrated metric lines
(offered/achieved/p50/p99/SLO fields, validated by
scripts/check_bench.py); the on-device run is carried as debt
``serve-slo-on-device`` (PERF.md section 7).

Usage:
    PYTHONPATH=. python scripts/loadgen.py -scale 9 -rates 5,15,40 \
        -queries 24 -slo-ms sssp=250,components=250,pagerank=1000 \
        [-events FILE] [-trace FILE]
"""

from __future__ import annotations

import argparse
import contextvars
import dataclasses
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# a step saturates when it achieves under this fraction of its
# offered rate — the knee of the latency-vs-rate curve
KNEE_FRACTION = 0.9


@dataclasses.dataclass
class StepReport:
    """One ramp step's measured outcome (all rates in queries/s,
    latencies in ms; percentiles come from the merged
    metrics-snapshot histograms, per-kind detail preserved)."""
    step: int
    target_qps: float         # the nominal Poisson rate
    offered_qps: float        # measured: submitted / enqueue window
    achieved_qps: float       # measured: served / retire window
    submitted: int
    served: int
    elapsed_s: float          # load start -> last retirement
    p50_ms: float | None
    p99_ms: float | None
    slo_good_fraction: float | None
    per_kind: dict            # kind -> {count, p50_ms, p99_ms}
    drained: bool
    # serving-fleet fields (round 18, lux_tpu/fleet.py): queries the
    # tier SHED with a typed AdmissionError (admitted + shed
    # partition the submitted set — ``drained`` counts both), and
    # the snapshot's SLO-accounted retirement count (good +
    # violation — computed over ADMITTED queries only; check_bench
    # rejects a line whose accounting covers shed queries)
    shed: int = 0
    slo_accounted: int | None = None
    # the raw Response objects, for oracle verification by chaos
    # acceptance harnesses (not rendered, not serialized)
    responses: list = dataclasses.field(default_factory=list,
                                        repr=False)


def _merged_latency(snapshot) -> tuple:
    """(merged Histogram, {kind: entry}) of the snapshot's
    serve_latency_seconds series (lux_tpu/metrics.py from_snapshot +
    bucket-wise merge — the mergeability the histogram design buys)."""
    from lux_tpu import metrics as metrics_mod

    merged = metrics_mod.Histogram()
    per_kind = {}
    for h in snapshot.get("histograms", []):
        if h.get("name") != "serve_latency_seconds":
            continue
        kind = (h.get("labels") or {}).get("kind", "?")
        per_kind[kind] = h
        merged = merged.merge(metrics_mod.Histogram.from_snapshot(h))
    return merged, per_kind


def _slo_counts(snapshot) -> tuple:
    """(good, violation) totals of the snapshot's SLO counters."""
    good = bad = 0.0
    for c in snapshot.get("counters", []):
        if c.get("name") == "serve_slo_good_total":
            good += c.get("value", 0)
        elif c.get("name") == "serve_slo_violation_total":
            bad += c.get("value", 0)
    return good, bad


def _slo_fraction(snapshot) -> float | None:
    good, bad = _slo_counts(snapshot)
    if good + bad == 0:
        return None
    return good / (good + bad)


def run_step(srv, rate: float, n: int, kinds, rng,
             step: int = 0) -> StepReport:
    """One open-loop step: submit ``n`` mixed-kind queries at Poisson
    rate ``rate`` (qps) while ``srv.serve`` runs on this thread
    (responses arrive as the turn that retired them ends); read the
    step's metrics snapshot back (the published ``metrics_snapshot``
    event — the same aggregate every later SLO consumer reads) and
    measure offered/achieved.  The step swaps in a FRESH metrics
    registry (``Server.set_metrics``) so its percentiles cover
    exactly this step."""
    from lux_tpu import metrics as metrics_mod

    if not rate > 0:
        raise ValueError(f"offered rate must be > 0 qps, got {rate}")
    reg = metrics_mod.Registry()
    srv.set_metrics(reg)
    specs = [(kinds[i % len(kinds)], int(rng.integers(0, srv.g.nv)))
             for i in range(n)]
    gaps = rng.exponential(1.0 / max(rate, 1e-9), size=n)

    enq_last = [0.0]
    shed0 = len(getattr(srv, "shed_records", ()))

    def submit_all():
        from lux_tpu.fleet import AdmissionError
        try:
            for (kind, s), gap in zip(specs, gaps):
                time.sleep(gap)
                try:
                    srv.submit(kind, source=s)
                except AdmissionError:
                    pass    # typed shed: counted via shed_records
                enq_last[0] = time.monotonic()
        finally:
            # the loop ends once everything submitted is answered
            srv.stop()

    # copy_context: the submitter must emit query_enqueue events into
    # the CALLER's telemetry scope (contextvars do not cross threads
    # by themselves)
    ctx = contextvars.copy_context()
    th = threading.Thread(target=lambda: ctx.run(submit_all),
                          daemon=True)
    responses = []
    t_start = time.monotonic()
    t_last = [t_start]

    def deliver(out):
        responses.extend(out)
        t_last[0] = time.monotonic()

    th.start()
    srv.serve(deliver)
    th.join()

    # the emitted event IS the published snapshot (None only without
    # an active event sink — fall back to the registry directly)
    snapshot = srv.emit_metrics_snapshot(step=step, target_qps=rate) \
        or reg.snapshot()

    merged, per_kind_hists = _merged_latency(snapshot)
    p50 = merged.quantile(0.5)
    p99 = merged.quantile(0.99)
    offered = len(specs) / max(enq_last[0] - t_start, 1e-9)
    achieved = len(responses) / max(t_last[0] - t_start, 1e-9)
    shed = len(getattr(srv, "shed_records", ())) - shed0
    good, bad = _slo_counts(snapshot)
    per_kind = {
        k: {"count": h.get("count"),
            "p50_ms": None if h.get("p50") is None
            else h["p50"] * 1e3,
            "p99_ms": None if h.get("p99") is None
            else h["p99"] * 1e3}
        for k, h in sorted(per_kind_hists.items())}
    return StepReport(
        step=step, target_qps=rate, offered_qps=offered,
        achieved_qps=achieved, submitted=len(specs),
        served=len(responses), elapsed_s=t_last[0] - t_start,
        p50_ms=None if p50 is None else p50 * 1e3,
        p99_ms=None if p99 is None else p99 * 1e3,
        slo_good_fraction=_slo_fraction(snapshot),
        per_kind=per_kind,
        drained=len(responses) + shed == len(specs),
        shed=shed,
        slo_accounted=(None if good + bad == 0
                       else int(good + bad)),
        responses=responses)


def warm(srv, kinds) -> int:
    """Build + compile each kind's engine OUTSIDE the measured load
    (one throwaway query per kind, drained before the ramp): the
    first drain otherwise bills remote/XLA compilation to step 0's
    latencies — the serving-tier analogue of the bench drivers'
    excluded warmup run.  Returns the number of warm queries."""
    for k in kinds:
        srv.submit(k, source=0)
    return len(srv.run())


def saturation_knee(reports) -> int | None:
    """Index of the first ramp step whose achieved rate fell under
    KNEE_FRACTION of its offered rate; None = never saturated."""
    for i, r in enumerate(reports):
        if r.achieved_qps < KNEE_FRACTION * r.offered_qps:
            return i
    return None


def render_table(reports, out=sys.stdout) -> None:
    print(f"{'step':>4} {'offered':>9} {'achieved':>9} "
          f"{'p50_ms':>9} {'p99_ms':>9} {'slo_good':>9} "
          f"{'served':>12}", file=out)
    for r in reports:
        frac = "-" if r.slo_good_fraction is None \
            else f"{r.slo_good_fraction:.3f}"
        p50 = "-" if r.p50_ms is None else f"{r.p50_ms:9.1f}"
        p99 = "-" if r.p99_ms is None else f"{r.p99_ms:9.1f}"
        print(f"{r.step:>4} {r.offered_qps:9.2f} "
              f"{r.achieved_qps:9.2f} {p50:>9} {p99:>9} {frac:>9} "
              f"{r.served:>5}/{r.submitted:<6}", file=out)
    knee = saturation_knee(reports)
    if knee is None:
        print("# no saturation knee inside the ramp "
              f"(achieved >= {KNEE_FRACTION:.0%} of offered at every "
              f"step)", file=out)
    else:
        r = reports[knee]
        print(f"# saturation knee at step {knee}: offered "
              f"{r.offered_qps:.2f} qps, achieved "
              f"{r.achieved_qps:.2f} qps", file=out)


def _parse_slo(text: str) -> dict:
    out = {}
    for tok in (text or "").split(","):
        tok = tok.strip()
        if not tok:
            continue
        k, _, v = tok.partition("=")
        out[k.strip()] = float(v)
    return out


def main(argv=None) -> int:
    from lux_tpu import serve, telemetry
    from lux_tpu.convert import rmat_graph

    ap = argparse.ArgumentParser(
        prog="python scripts/loadgen.py",
        description="open-loop Poisson load harness: ramped offered "
                    "rates against a continuous-batching Server; "
                    "reports the latency-vs-offered-rate table and "
                    "the measured saturation knee")
    ap.add_argument("-scale", type=int, default=10)
    ap.add_argument("-ef", type=int, default=8)
    ap.add_argument("-batch", type=int, default=4)
    ap.add_argument("-np", type=int, default=2, dest="num_parts")
    ap.add_argument("-seg-iters", type=int, default=2,
                    dest="seg_iters")
    ap.add_argument("-kinds", default="sssp,components,pagerank")
    ap.add_argument("-rates", default="5,15,40",
                    help="comma list of offered qps, one ramp step "
                         "each")
    ap.add_argument("-queries", type=int, default=24,
                    help="queries per ramp step")
    ap.add_argument("-seed", type=int, default=0)
    ap.add_argument("-slo-ms", dest="slo_ms",
                    default="sssp=250,components=250,pagerank=1000",
                    help="per-kind latency targets, kind=ms comma "
                         "list ('' disables SLO accounting)")
    ap.add_argument("-no-warm", action="store_true", dest="no_warm",
                    help="skip the excluded engine-compile warmup "
                         "(one throwaway query per kind)")
    ap.add_argument("-events", default=None, metavar="FILE",
                    help="append the telemetry trail (query events + "
                         "metrics_snapshot) as JSONL")
    ap.add_argument("-rotate-bytes", type=int, default=None,
                    dest="rotate_bytes",
                    help="EventLog size-rotation threshold for "
                         "-events (long-lived serving processes)")
    ap.add_argument("-trace", default=None, metavar="TRACE_JSON",
                    help="also export the per-query Perfetto trace "
                         "(lux_tpu.tracing.trace_export)")
    args = ap.parse_args(argv)

    kinds = [k.strip() for k in args.kinds.split(",") if k.strip()]
    for k in kinds:
        if k not in serve.KINDS:
            print(f"error: unknown kind {k!r}", file=sys.stderr)
            return 2
    rates = [float(r) for r in args.rates.split(",") if r.strip()]
    if not rates or any(not r > 0 for r in rates):
        print(f"error: -rates must be positive offered qps, got "
              f"{args.rates!r}", file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    g = rmat_graph(scale=args.scale, edge_factor=args.ef,
                   seed=args.seed)
    ev = telemetry.EventLog(args.events,
                            rotate_bytes=args.rotate_bytes) \
        if args.events else telemetry.EventLog()
    reports = []
    with telemetry.use(events=ev):
        ev.emit("run_start", schema=telemetry.SCHEMA, app="loadgen",
                file=f"<rmat{args.scale}>", np=args.num_parts)
        srv = serve.Server(g, batch=args.batch,
                           num_parts=args.num_parts,
                           seg_iters=args.seg_iters,
                           slo_ms=_parse_slo(args.slo_ms))
        t0 = time.perf_counter()
        if not args.no_warm:
            warm(srv, kinds)
        for i, rate in enumerate(rates):
            reports.append(run_step(srv, rate, args.queries, kinds,
                                    rng, step=i))
        ev.emit("run_done",
                seconds=round(time.perf_counter() - t0, 6),
                iters=sum(r.served for r in reports))
    ev.close()
    render_table(reports)
    if args.trace:
        from lux_tpu import tracing
        trace = tracing.trace_export(ev.events, out=args.trace)
        errs = tracing.validate_trace(trace)
        print(f"# trace: {args.trace} "
              f"({'VALID' if not errs else 'INVALID'})")
        for e in errs:
            print(f"ERROR: {e}", file=sys.stderr)
        if errs:
            return 1
    if not all(r.drained for r in reports):
        print("error: a ramp step did not drain", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
