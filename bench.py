"""Benchmark driver: GTEPS per chip on the BASELINE.md configurations.

Methodology matches the reference (BASELINE.md): wall-clock around the
iteration loop only (graph generation/load/init excluded), GTEPS =
ne * iterations / elapsed_seconds / num_chips.  Graphs are R-MAT
(the reference's RMAT family, scaled to fit a single chip's HBM
comfortably at default settings).

Prints ONE JSON line per benched config:
  {"metric": ..., "value": N, "unit": "GTEPS", "vs_baseline": N, ...}
vs_baseline is against the north-star target of 1 GTEPS/chip
(BASELINE.json "north_star").  Preprocessing that affects
comparability (degree relabel, pair-lane threshold, partitions) is
recorded in the line.

Variance discipline: run-to-run spread on identical binaries can
exceed a whole round's optimization gains (0.095-0.127 on the earlier
installation, PERF_NOTES; not re-measured on this one), so every
config runs the TIMED REGION ``-repeats`` times
(default 3; build/compile excluded) and reports the MEDIAN, with the
per-repeat samples recorded in the JSON line.

Telemetry (round 7, lux_tpu/telemetry.py): every config runs inside a
telemetry scope, and each metric line carries a ``telemetry`` field:
``runs`` (per-timed-run seconds + iteration counts, straight from the
``timed_run`` events — the per-sample decomposition that makes
run-to-run variance auditable) and ``counters`` (the device-side
per-iteration
counter digest when ``-iter-stats`` is on; null otherwise — counters
run a separate compiled variant of the loop, so they are opt-in for
the headline numbers).  ``-events FILE`` additionally appends the raw
event JSONL (rendered by scripts/events_summary.py);
scripts/check_bench.py validates the telemetry field against samples
and attempts.

Guarded execution (round 9, lux_tpu/health.py): ``-health`` runs
every config's timed loops under the device-side watchdog (NaN/Inf,
divergence/oscillation, frontier stalls — a separate compiled loop
variant, like the counter variants) and records the digest in each
line's ``telemetry.health`` (null when off); a tripped watchdog
fails the config with a _FAILED line.  scripts/check_bench.py
type-checks the digest.

Static audit (round 10, lux_tpu/audit.py): ``-audit`` (default
"warn") traces every config's compiled program variants at build time
and records the digest in each metric line's ``audit`` field — a
metric produced by a build that violates the framework's structural
invariants (two gathers in a dense iteration, a baked-in constant
past the const-bytes ceiling, a broken owner collective schedule...)
is rejected
by scripts/check_bench.py, and ``-audit error`` refuses to run it at
all.

Resilience (round 6, lux_tpu/resilience.py): each config runs under
the supervisor — transient failures (worker death, connection
drops) retry with backoff up to ``-retries`` times, deterministic
ones (OOM, an oversized program) fail the config immediately; and
samples more than
``-outlier``x off their batch median (BENCH_r05's pagerank-mp
collapse: [0.1116, 0.0107, 0.1118]) are DISCARDED and re-run once
rather than silently medianed.  Every metric line records the audit
trail: "attempts" (total timed runs incl. outlier reruns),
"discarded" (the thrown-away samples), and "run_attempts" when the
whole config was retried.  scripts/check_bench.py validates the
schema.

Observatory (round 12, lux_tpu/observe.py): the session-calibration
probe runs once up front and every metric line carries its
``calibration`` digest (measured probe ns/elem vs the canonical
PERF_NOTES figures, platform, ndev, grade) — scripts/check_bench.py
REJECTS lines from "degraded" or "uncalibrated" sessions, so a
session far off the canon is detected and labeled instead of entering
the trajectory.  Every run also appends its lines to the persistent perf
ledger (``-ledger``, default PERFLEDGER.jsonl) and writes the
machine-readable BENCH_rNN.json artifact itself (``-json-out``,
default auto-numbered — the empty bench trajectory was a
hand-assembly gap, not a measurement gap).

Configs (-config runs one):
  pagerank        PageRank, pull model, fixed iterations   (BASELINE #1/#4)
  pagerank-mp     PageRank, np=4 multi-part OWNER exchange + pair
                  composition — the mesh-relevant path, regression-
                  guarded in the round artifact
  cc              Connected Components, push, to convergence (BASELINE #2)
  sssp            SSSP/BFS hops, push, to convergence        (BASELINE #3)
  sssp-delta      weighted SSSP, delta-stepping frontier     (BASELINE #3)
  colfilter       SGD matrix factorization, weighted pull    (BASELINE #5)

By DEFAULT every config runs (one JSON line each, pagerank LAST so a
line-parsing driver still records the headline metric as its tail
line).

``main()`` measures on the chip or not at all: it refuses a non-TPU
platform before touching the ledger or minting an artifact (tests call
``run_config`` directly and are unaffected), and it exits non-zero if
the calibration probe or ANY config failed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from statistics import median

# The same preprocessing is applied at EVERY partition count so
# single-chip and multi-chip GTEPS stay apples-to-apples (round-1
# advice): degree relabel concentrates hubs into shared 128-vertex
# tiles, pair-lane delivery then serves dense tile pairs without the
# per-edge gather (ops/pairs.py, PERF_NOTES.md).
PAIR_THRESHOLD = 16   # default; override with -pair

# (scale, edge_factor) per config.  colfilter approximates the
# BASELINE NetFlix shape (497K vertices, ~400 ratings/vertex — dense):
# rmat16 x ef128 keeps the run short while staying density-faithful;
# the sparse rmat18 x ef16 shape it replaced is preserved in
# PERF_NOTES round-over-round tables.
DEFAULT_SHAPE = {"pagerank": (21, 16), "cc": (20, 16),
                 "sssp": (21, 16), "sssp-delta": (21, 16),
                 "colfilter": (16, 128), "pagerank-mp": (23, 16),
                 "sssp-mp": (23, 16),
                 # query-batched engines (ROADMAP item 2): k-source
                 # SSSP + personalized PageRank; `-config batch-sweep`
                 # expands over -batch (default B in {1, 8, 64}) and
                 # each line records batch + query_gteps = B x the
                 # machine rate — one gather serving B queries, so
                 # per-query delivered cost is 1/query_gteps ns/edge
                 "ksssp-batch": (20, 16), "ppr-batch": (20, 16),
                 # paged-vs-flat gather A/B (round 15,
                 # ops/pagegather.py): `-config gather-ab` runs
                 # pagerank BOTH ways on one degree-sorted graph and
                 # records the plan's measured unique-page ratio /
                 # row fill on both lines (scripts/check_bench.py
                 # validates the fields)
                 "gather-ab": (21, 16),
                 # MXU-vs-VPU reduce A/B (round 23, ops/tiled.py):
                 # `-config mxu-ab` runs the B=8 personalized-
                 # pagerank program (wide payload — the regime where
                 # the one-hot contraction amortizes, scalemodel.
                 # mxu_break_even_wide) BOTH ways on one degree-
                 # sorted community graph; each line carries the
                 # resolved mode + the modeled per-row reduce rates
                 # for both paths (scripts/check_bench.py validates
                 # mode-vs-name and the mxu/vpu pairing).  Community
                 # + degree sort keeps chunk rows dense (fill >= 23)
                 # so the per-row toll, not sparse-tail padding, is
                 # what the pair isolates.
                 "mxu-ab": (16, 64),
                 # serving-tier SLO lines (round 17, lux_tpu/serve.py
                 # + scripts/loadgen.py): `-config serve-slo` expands
                 # over -rates into one open-loop load step per
                 # offered rate; each line carries offered/achieved
                 # qps, snapshot p50/p99 and the SLO good fraction
                 # (scripts/check_bench.py rejects the contradictions:
                 # p99 < p50, achieved > offered, fraction outside
                 # [0, 1]).  The on-device run is carried as debt
                 # serve-slo-on-device (PERF.md section 7).
                 "serve-slo": (12, 8),
                 # serving-tier chaos lines (round 18,
                 # lux_tpu/fleet.py): `-config serve-chaos` runs the
                 # serve-slo open-loop load against a FleetServer of
                 # -serve-replicas replicas with a ReplicaKillPlan
                 # armed post-warm; each line extends the serve-slo
                 # record with replicas/failovers/shed/shed_fraction
                 # plus (round 24, self-healing) respawns/
                 # quarantines/mttr_s/journal_replayed — the fleet
                 # runs with a durable admission journal and the
                 # resurrection supervisor armed (scripts/
                 # check_bench.py rejects the contradictions:
                 # shed_fraction outside [0,1], failovers or
                 # respawns with replicas=1, SLO accounting over
                 # shed queries, mttr without a fired kill,
                 # journal_replayed > submitted).  The real-TPU
                 # drill is debt serve-chaos-on-device.
                 "serve-chaos": (12, 8),
                 # live-graph serving lines (round 20,
                 # lux_tpu/livegraph.py): `-config serve-live` runs
                 # mixed-kind traffic against a MUTATING graph —
                 # WAL-free LiveGraph ingest between drains, per-
                 # column epoch pinning, the epoch-keyed answer
                 # cache, and at least one natural threshold-
                 # triggered compaction — and verifies EVERY answer
                 # against its NumPy oracle at the query's admission
                 # epoch before the line may print.  The line carries
                 # mutations/mutation_rate/epochs_advanced/
                 # compactions/cache_hit_fraction/peak_occupancy
                 # (scripts/check_bench.py rejects the
                 # contradictions: epochs advanced with zero
                 # mutations, hit fraction outside [0, 1], a
                 # compaction count with delta occupancy never past
                 # threshold).  The on-device run is carried as debt
                 # live-mutation-on-device (PERF.md section 7).
                 "serve-live": (12, 8)}

# the batch-sweep expansion (one metric line per B per app)
BATCH_SWEEP_DEFAULT = "1,8,64"


def build_graph(scale, ef, verbose, weighted=False):
    import numpy as np

    from lux_tpu.convert import rmat_graph

    t0 = time.perf_counter()
    g = rmat_graph(scale=scale, edge_factor=ef, seed=0)
    if weighted:
        rng = np.random.default_rng(1)
        g.weights = rng.integers(1, 6, size=g.ne).astype(np.int32)
    if verbose:
        print(f"# graph built: nv={g.nv} ne={g.ne} "
              f"({time.perf_counter() - t0:.1f}s)", file=sys.stderr)
    return g


def _print_coverage(args, eng):
    if args.verbose and eng.pairs is not None:
        cov = eng.pairs.stats["coverage"]
        print(f"# pair-lane coverage {cov * 100:.1f}%", file=sys.stderr)


def _comm_build(eng, extra):
    """Round 19 (lux_tpu/comms.py): the per-collective byte ledger of
    the engine's step program — traced, oracle- and audit-cross-
    checked — lands in the metric line's ``comm`` field
    (comm_bytes_per_edge + the modeled comm_frac at this placement).
    A failing ledger records errors instead of a digest;
    scripts/check_bench.py rejects such lines, so a published number
    can never ride an un-accountable byte bill."""
    from lux_tpu import comms, observe

    try:
        led = comms.ledger_for(eng)
        model = observe._engine_model(eng, 1.0)
        compute_ns = sum(v for v in model.values() if v)
        extra["comm"] = comms.bench_digest(led, compute_ns=compute_ns)
    except Exception as e:  # noqa: BLE001 — a broken ledger must not
        # kill the run; the line records the failure and check_bench
        # rejects it from the trajectory
        extra["comm"] = {"errors": 1,
                         "error": f"{type(e).__name__}: {e}"[:200]}
        print(f"# comm ledger failed: {type(e).__name__}: {e}",
              file=sys.stderr)


def _mem_build(eng, extra, consumers=None, trail=None):
    """Round 22 (lux_tpu/memwatch.py): the runtime memory drift
    verdict of the engine's build — measured (or memory_analysis-
    modeled) peak vs the unified byte ledger — lands in the metric
    line's ``mem`` field.  A drifting or failing verdict records
    errors instead of a clean digest; scripts/check_bench.py rejects
    such lines, so a published number can never ride a build whose
    byte accounting has rotted."""
    from lux_tpu import memwatch

    try:
        extra["mem"] = memwatch.bench_digest(eng, trail=trail,
                                             consumers=consumers)
        if extra["mem"].get("errors"):
            print(f"# mem drift: {extra['mem']}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — a broken ledger must not
        # kill the run; the line records the failure and check_bench
        # rejects it from the trajectory
        extra["mem"] = {"errors": 1,
                        "error": f"{type(e).__name__}: {e}"[:200]}
        print(f"# mem ledger failed: {type(e).__name__}: {e}",
              file=sys.stderr)


def _audit_build(eng, args, extra):
    """Static program audit of the freshly built engine
    (lux_tpu/audit.py, round 10): traces every compiled loop variant
    — nothing executes, so the cost is size-independent — and records
    the digest in the metric line's ``audit`` field.
    scripts/check_bench.py REJECTS metric lines whose digest carries
    errors, so a benchmark number can never be published off a build
    that violates the framework's structural invariants; ``-audit
    error`` additionally fails the config at build time (typed
    AuditError, classified fatal).  Round 19: the comm byte ledger
    (``_comm_build``) rides the same hook — every engine metric line
    carries its ``comm`` digest regardless of the -audit mode.
    Round 22: the memory drift verdict (``_mem_build``) rides the
    same hook — every engine metric line carries its ``mem``
    digest."""
    _comm_build(eng, extra)
    _mem_build(eng, extra)
    if args.audit == "off":
        return
    from lux_tpu import audit

    findings = audit.audit_engine(eng, mode=None)
    d = audit.digest(findings, mode=args.audit)
    extra["audit"] = d
    if d["errors"] and args.audit == "error":
        audit.raise_findings(findings, where=type(eng).__name__)
    # findings print UNCONDITIONALLY: under the default 'warn' a
    # violating build would otherwise burn the whole benchmark run
    # silently and only be rejected by check_bench afterwards
    for f in findings:
        print(f"# audit: {f}", file=sys.stderr)


def bench_fused(eng, ne, ni, verbose, repeats):
    """GTEPS samples over ``repeats`` timed fused runs (ONE warmup/
    compile up front inside timed_fused_run; each repeat re-times only
    the fused loop).  Returns (samples, rerun) where ``rerun()`` times
    one more run (jit cache is warm) — the outlier discard-and-rerun
    rule's second chance."""
    import numpy as np

    from lux_tpu.timing import timed_fused_run

    t0 = time.perf_counter()
    state, elapsed = timed_fused_run(eng, ni, repeats=repeats)
    if verbose:
        times = " ".join(f"{e:.2f}s" for e in elapsed)
        print(f"# {repeats} timed runs ({time.perf_counter() - t0:.1f}s"
              f" total): {times}", file=sys.stderr)
    # the benched result must be sane, or the GTEPS line is meaningless
    assert np.isfinite(eng.unpad(state)).all(), "non-finite bench result"

    def rerun():
        _state, [e] = timed_fused_run(eng, ni, repeats=1)
        return ne * ni / e

    return [ne * ni / e for e in elapsed], rerun


def bench_converge(eng, ne, verbose, repeats):
    """GTEPS samples over ``repeats`` timed whole-run converges;
    returns (samples, rerun) like bench_fused."""
    from lux_tpu.timing import timed_converge

    labels, iters, elapsed = timed_converge(eng, repeats=repeats)
    if verbose:
        times = " ".join(f"{e:.2f}s" for e in elapsed)
        print(f"# converged in {iters} iterations; {repeats} timed "
              f"runs: {times}", file=sys.stderr)

    def rerun():
        _l, it, [e] = timed_converge(eng, repeats=1)
        return ne * it / e

    return [ne * iters / e for e in elapsed], rerun


def _rate_token(rate: float) -> str:
    return f"{rate:g}".replace(".", "p").replace("-", "m")


def run_serve_load(config, args, *, chaos: bool):
    """Shared body of the serve-slo and serve-chaos configs: one
    open-loop Poisson load step (scripts/loadgen.py) at the offered
    rate named by "<config>@RATE" against a mixed-kind
    continuous-batching server with per-kind latency SLOs.  The
    line's value/samples are the MEASURED achieved qps; offered/
    achieved, snapshot p50/p99, SLO targets and good fraction ride
    the line for scripts/check_bench.py's contradiction rejects
    (p99 < p50, achieved > offered, fraction outside [0, 1]).

    ``chaos`` (round 18, lux_tpu/fleet.py) swaps the single Server
    for a FleetServer of ``-serve-replicas`` replicas with a
    faults.ReplicaKillPlan armed AFTER the engine-compile warmup
    (the last replica dies at its ``-kill-boundary``-th loaded
    boundary), extends the line with replicas/failovers/shed/
    shed_fraction/slo_accounted, and FAILS unless the kill actually
    fired and at least one query failed over — a chaos line measured
    without chaos is a lie."""
    import itertools
    import os

    import numpy as np

    sdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "scripts")
    if sdir not in sys.path:
        sys.path.insert(0, sdir)
    import loadgen

    from lux_tpu import serve, telemetry

    family = "serve-chaos" if chaos else "serve-slo"
    _, _, rstr = config.partition("@")
    rate = float(rstr) if rstr else (60.0 if chaos else 20.0)
    if not rate > 0:
        # the bare-config expansion validates -rates; the @-form must
        # reject too, or a zero rate hangs the submitter forever
        raise ValueError(f"{family} offered rate must be > 0 qps, "
                         f"got {rate}")
    scale = args.scale or DEFAULT_SHAPE[family][0]
    ef = args.ef or DEFAULT_SHAPE[family][1]
    kinds = [k.strip() for k in args.serve_kinds.split(",")
             if k.strip()]
    slo = loadgen._parse_slo(args.slo_ms)
    g = build_graph(scale, ef, args.verbose)
    extra = {"np": args.np, "scale": scale, "ef": ef,
             "serve_batch": args.serve_batch, "kinds": kinds,
             "queries": args.serve_queries, "unit": "qps"}
    if chaos:
        from lux_tpu import faults, fleet, resilience
        if args.serve_replicas < 2:
            raise ValueError(
                "serve-chaos needs -serve-replicas >= 2: there is "
                "no surviving replica to fail over to with one")
        import tempfile
        # round 24: the chaos line exercises the SELF-HEALING tier —
        # admissions journaled durably (the line reports how many a
        # recovery would replay: 0 on a drained run) and the killed
        # replica resurrected under backoff with canary-gated
        # routing re-entry (respawns/quarantines/mttr_s ride the
        # line; check_bench rejects the contradictions)
        jpath = os.path.join(tempfile.mkdtemp(prefix="lux_chaos_j_"),
                             "admissions.journal")
        srv = fleet.FleetServer(
            g, replicas=args.serve_replicas, batch=args.serve_batch,
            num_parts=args.np, seg_iters=2, slo_ms=slo,
            health=args.health,
            retry=resilience.RetryPolicy(retries=3, backoff_s=0.01,
                                         max_backoff_s=0.1,
                                         jitter_seed=0),
            journal_path=jpath, heal=True,
            respawn_retry=resilience.RetryPolicy(
                retries=3, backoff_s=0.01, max_backoff_s=0.1,
                jitter_seed=1))
        runner_of = srv._replicas[0].runner
        extra["replicas"] = args.serve_replicas
    else:
        srv = serve.Server(g, batch=args.serve_batch,
                           num_parts=args.np, seg_iters=2,
                           slo_ms=slo, health=args.health)
        runner_of = srv._runner
    if args.audit != "off":
        from lux_tpu import audit
        findings = []
        for k in kinds:
            findings += audit.audit_engine(runner_of(k).eng,
                                           mode=None)
        d = audit.digest(findings, mode=args.audit)
        extra["audit"] = d
        if d["errors"] and args.audit == "error":
            audit.raise_findings(findings, where=family)
        for f in findings:
            print(f"# audit: {f}", file=sys.stderr)
    # compile outside the load — the fleet warms EVERY (replica,
    # kind) engine (routing-spread warm would leave cold runners
    # whose first measured query pays XLA compilation)
    if chaos:
        srv.warm(kinds)
        # arm the kill AFTER warm so its boundary counter sees only
        # loaded traffic — and on the replica routing WILL pick
        # (fleet.routing_target): routing is a positive-feedback
        # loop (drain -> fresh beat -> picked again), so a plan
        # armed on any fixed index is a coin flip on beat timing
        # inside warm, and the losing side is a chaos line that
        # silently measured a fault-free run (the round-22 fix;
        # the regression test pins it)
        victim = srv.routing_target(kinds[0])
        srv.set_fault(faults.ReplicaKillPlan(
            {victim: args.kill_boundary}))
    else:
        loadgen.warm(srv, kinds)
    rng = np.random.default_rng(7)   # fixed seed: one query schedule
    steps = itertools.count()

    def one_step():
        step = next(steps)
        rep = loadgen.run_step(srv, rate, args.serve_queries, kinds,
                               rng, step=step)
        telemetry.current().emit("timed_run", repeat=step,
                                 iters=rep.served,
                                 seconds=round(rep.elapsed_s, 6))
        if not rep.drained:
            raise RuntimeError(
                f"{family} load step {step} did not drain "
                f"({rep.served}+{rep.shed}/{rep.submitted})")
        if rep.slo_good_fraction is None or rep.p50_ms is None:
            raise RuntimeError(
                f"{family} load step {step} produced no SLO "
                f"accounting (slo_ms={slo!r})")
        return rep

    rep = one_step()
    # round 22: the serving line's mem digest — one drained engine's
    # drift verdict widened by the dynamic consumer terms (cache is
    # absent on these configs; the digest still prices the engine)
    from lux_tpu import memwatch
    _mem_build(runner_of(kinds[0]).eng, extra,
               consumers=memwatch.consumer_terms(
                   cache=getattr(srv, "cache", None),
                   live=getattr(srv, "live", None)))
    if chaos and (not srv.fault.fired or srv.failovers < 1):
        raise RuntimeError(
            "serve-chaos kill plan never fired (or nothing failed "
            "over) — the chaos line would be measuring a fault-free "
            "run")
    if chaos and srv.respawns + srv.quarantines < 1:
        # heal-armed run() does not return until every lost replica
        # resurrected or quarantined, so a fired kill with neither
        # means the healing tier silently did not engage
        raise RuntimeError(
            "serve-chaos kill fired but the healing supervisor "
            "neither respawned nor quarantined the replica")
    if args.verbose:
        loadgen.render_table([rep], out=sys.stderr)
    extra.update(offered_qps=round(rep.offered_qps, 4),
                 achieved_qps=round(rep.achieved_qps, 4),
                 p50_ms=round(rep.p50_ms, 4),
                 p99_ms=round(rep.p99_ms, 4),
                 slo_target_ms=slo,
                 slo_good_fraction=round(rep.slo_good_fraction, 4),
                 served=rep.served, submitted=rep.submitted)
    if chaos:
        extra.update(failovers=int(srv.failovers),
                     shed=int(rep.shed),
                     shed_fraction=round(rep.shed
                                         / max(1, rep.submitted), 4),
                     slo_accounted=rep.slo_accounted,
                     # round-24 healing gauges: resurrections that
                     # re-entered routing (canary-gated), typed
                     # quarantines, repair time (first loss -> pool
                     # whole; None when the pool never re-completed),
                     # and how many admitted-unretired queries a
                     # crash recovery would re-dispatch NOW (a
                     # drained run retired everything: 0)
                     respawns=int(srv.respawns),
                     quarantines=int(srv.quarantines),
                     mttr_s=(None if srv.mttr_s is None
                             else round(srv.mttr_s, 4)),
                     journal_replayed=int(srv.journal_replayed))
    prefix = "serve_chaos" if chaos else "serve_slo"
    name = f"{prefix}_q{_rate_token(rate)}_rmat{scale}"
    return (name, [rep.achieved_qps], extra,
            lambda: one_step().achieved_qps)


def run_serve_live(config, args):
    """The live-graph serving line (rounds 20-22,
    lux_tpu/livegraph.py): mixed-kind traffic over a MUTATING
    WEIGHTED graph exercising the FULL mutation algebra — appends,
    deletions + the honest re-seed, and (round 22) per-phase
    REWEIGHTS, the algebra leg an unweighted headline structurally
    reported as reweights=0.  Each phase appends first (one
    published epoch), then drains two query waves — the second wave
    repeats the first's hot sources at the SAME epoch, so the
    epoch-keyed answer cache measurably hits.  Two of the phases
    DELETE a previously-appended edge and run the honest
    anti-monotone re-seed (a converged pre-deletion state repaired
    to the published epoch on a standalone engine over
    ``graph_at(target)``, exactly equal to the full recompute —
    integer-valued f32 weights keep the comparison exact);
    compaction is decided by the round-21
    CompactionScheduler (anti-monotone pressure / occupancy / drag
    economics) instead of the bare occupancy heuristic, with
    Server.refresh_live generation adoption between drains.  EVERY
    answer is verified against its NumPy oracle at the query's
    admission epoch before the line may print — a wrong answer is a
    crash, never a published number.  check_bench rejects the line's
    contradictions, round-21 algebra fields included (see
    DEFAULT_SHAPE comment)."""
    import os
    import time as _time

    import numpy as np

    from lux_tpu import livegraph, serve, telemetry
    from lux_tpu.apps import sssp as _sssp

    sdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "scripts")
    if sdir not in sys.path:
        sys.path.insert(0, sdir)
    import loadgen

    scale = args.scale or DEFAULT_SHAPE["serve-live"][0]
    ef = args.ef or DEFAULT_SHAPE["serve-live"][1]
    kinds = [k.strip() for k in args.serve_kinds.split(",")
             if k.strip()]
    slo = loadgen._parse_slo(args.slo_ms)
    # round 22: the headline line is WEIGHTED — integer-valued f32
    # weights (1..5) keep every device f32 distance exact, so the
    # weighted oracle checks and the honest re-seed stay exact
    # comparisons, and the line's reweight counter measures the one
    # algebra leg (round 21) the unweighted line structurally
    # couldn't (reweights=0 forever)
    g = build_graph(scale, ef, args.verbose, weighted=True)
    # f32 as said: integer weights would give int32 distances
    # (apps/sssp.py), and the appended / reweighted edges are f32
    g.weights = g.weights.astype(np.float32)
    capacity = args.delta_capacity

    def build_tier():
        """ONE construction for sample 0 and every rerun — the two
        must measure the identical workload (live graph shape, cache
        policy, scheduler cadence), so there is exactly one place
        to tune it."""
        from lux_tpu import memwatch
        lv = livegraph.LiveGraph(g, capacity=capacity,
                                 compact_threshold=0.75)
        sv = serve.Server(g, batch=args.serve_batch,
                          num_parts=args.np, seg_iters=2, slo_ms=slo,
                          health=args.health, weighted=True,
                          live=lv, cache=True)
        # round 22: the runtime occupancy trail rides the drain —
        # boundary-only samples (measured free, PERF_NOTES round 22)
        # over the unified server ledger, so the events trail carries
        # the mem_sample/mem_watermark series events_summary renders
        sv.mem = memwatch.MemoryTrail(
            bytes_fn=lambda: memwatch.MemoryLedger
            .for_server(sv).total_bytes, emit_every=4)
        sc = livegraph.CompactionScheduler(lv, burn=sv.slo_burn)
        return lv, sv, sc

    live, srv, sched = build_tier()
    extra = {"np": args.np, "scale": scale, "ef": ef,
             "serve_batch": args.serve_batch, "kinds": kinds,
             "unit": "qps", "delta_capacity": capacity,
             "compact_threshold": live.compact_threshold}
    if args.audit != "off":
        from lux_tpu import audit
        findings = []
        for k in kinds:
            eng = srv._runner(k).eng
            if k in ("sssp", "components"):
                # the live delta-relax step rides the same audited
                # gather budget as the dense iterations
                live.register_audit(eng)
            findings += audit.audit_engine(eng, mode=None)
        d = audit.digest(findings, mode=args.audit)
        extra["audit"] = d
        if d["errors"] and args.audit == "error":
            audit.raise_findings(findings, where="serve-live")
        for f in findings:
            print(f"# audit: {f}", file=sys.stderr)
    loadgen.warm(srv, kinds)
    nv = g.nv
    phases = 6
    per = max(len(kinds), args.serve_queries // (2 * phases))
    # mutation volume sized to cross the compact threshold mid-run:
    # phases-1 batches of ceil(threshold*cap/(phases-2)) edges pass
    # 0.75*cap at phase ~ phases-2, leaving >= 1 natural compaction
    per_mut = int(np.ceil(live.compact_threshold * capacity
                          / max(1, phases - 2)))

    delete_phases = (2, 4)

    def reseed_honest(lv, target):
        """The HONEST anti-monotone re-seed: converge over the
        pre-deletion snapshot, repair that state to ``target`` on a
        standalone engine built over ``graph_at(target)`` (the
        revalidate contract), and refuse the line unless the result
        is exactly the full recompute — the weighted line's
        integer-valued f32 weights make every finite distance exact,
        so this stays an equality check, not a tolerance."""
        import jax

        pre = lv.graph_at(target - 1)
        eng0 = _sssp.build_engine(pre, 0, num_parts=args.np,
                                  weighted=True)
        lab, act = eng0.init_state()
        lab, act, _ = eng0.converge(lab, act)
        host = eng0.sg.from_padded(np.asarray(jax.device_get(lab)))
        g_t = lv.graph_at(target)
        eng1 = _sssp.build_engine(g_t, 0, num_parts=args.np,
                                  weighted=True)
        lab1, act1 = eng1.place(
            eng1.sg.to_padded(host),
            eng1.sg.to_padded(np.zeros(nv, bool)))
        lab1, act1, _ = lv.revalidate(eng1, lab1, act1)
        got = eng1.sg.from_padded(
            np.asarray(jax.device_get(lab1)))
        ref = _sssp.reference_sssp(g_t, 0, weighted=True)
        fin_g, fin_r = np.isfinite(got), np.isfinite(ref)
        if not (np.array_equal(fin_g, fin_r)
                and np.array_equal(
                    got[fin_g].astype(np.float64),
                    ref[fin_r].astype(np.float64))):
            raise RuntimeError(
                "serve-live: the anti-monotone re-seed differs from "
                "the full recompute at its target epoch — a wrong "
                "repair must never print a line")

    def load_phase(lv, sv, sc, rng, phase, tracked):
        """One phase: append (tracking an edge for later deletion),
        on the deletion phases delete a tracked edge + run the
        honest re-seed, on the others REWEIGHT the newest tracked
        edge (the round-21 algebra leg an unweighted line cannot
        carry), then two query waves — the repeat wave is the
        cache-hit traffic.  The scheduler alone decides folds at the
        phase boundary.  Returns (responses, submitted)."""
        s_new = rng.integers(nv, size=per_mut)
        d_new = rng.integers(nv, size=per_mut)
        w_new = rng.integers(1, 6, size=per_mut).astype(np.float32)
        sv.mutate(s_new, d_new, w_new)
        tracked.append((int(s_new[0]), int(d_new[0])))
        if phase in delete_phases and len(tracked) > 1:
            es, ed = tracked.pop(0)
            sv.mutate([es], [ed], op="delete")
            reseed_honest(lv, lv.epoch)
        elif phase and tracked:
            rs, rd = tracked[-1]
            sv.mutate([rs], [rd],
                      weights=[float(rng.integers(1, 6))],
                      op="reweight")
        hot = {k: int(rng.integers(nv)) for k in kinds}
        n = 0
        out = []
        for wave in range(2):
            for i in range(per):
                kind = kinds[i % len(kinds)]
                s = hot[kind] if i < len(kinds) \
                    else int(rng.integers(nv))
                sv.submit(kind, source=s)
                n += 1
            out += sv.run()
        sc.maybe_compact(server=sv)
        return out, n

    def one_step(lv, sv, sc):
        rng = np.random.default_rng(7)
        t0 = _time.monotonic()
        responses, submitted = [], 0
        tracked = []
        for phase in range(phases):
            out, n = load_phase(lv, sv, sc, rng, phase, tracked)
            responses += out
            submitted += n
        elapsed = _time.monotonic() - t0
        bad = livegraph.check_live_answers(lv, responses,
                                           weighted=True)
        if bad:
            raise RuntimeError(
                f"serve-live: {bad} answer(s) differ from the NumPy "
                f"oracle at their admission epochs — a wrong-answer "
                f"line must never print")
        telemetry.current().emit("timed_run", repeat=0,
                                 iters=len(responses),
                                 seconds=round(elapsed, 6))
        return len(responses) / elapsed, elapsed, submitted

    def fresh_run():
        """A rerun must measure the SAME workload as the sample it
        replaces — mutation stream, deletions + re-seeds, scheduler
        folds, cold answer cache — so it rebuilds the tier
        (build_tier, the one shared construction) and replays the
        identical seeded traffic.  The jit cache is warm (same
        shapes), so no compile cost recurs; replaying more queries
        over the now-static mutated graph instead would skip the
        very mutation/compaction path this line claims to time."""
        lv, sv, sc = build_tier()
        loadgen.warm(sv, kinds)
        return one_step(lv, sv, sc)[0]

    qps, elapsed, submitted = one_step(live, srv, sched)
    hit_frac = srv.cache.hit_fraction() or 0.0
    # round 22: the live line's mem digest prices the full unified
    # ledger — engine terms + the REAL post-run consumer bytes
    # (answer cache, delta blocks, WAL, multiset, staging)
    from lux_tpu import memwatch
    _mem_build(srv._runner(kinds[0]).eng, extra,
               consumers=memwatch.consumer_terms(cache=srv.cache,
                                                 live=live))
    if live.compactions < 1:
        raise RuntimeError(
            "serve-live: no compaction fired — the line would not "
            "measure the generation-swap path it claims to")
    if live.deletions < 1 or live.reseeds < 1:
        raise RuntimeError(
            "serve-live: the deletion/re-seed phases did not run — "
            "the line would not measure the mutation algebra it "
            "claims to")
    if live.reweights < 1:
        raise RuntimeError(
            "serve-live: no reweight ran — the weighted line would "
            "not measure the algebra leg it exists to carry")
    extra.update(
        weighted=True,
        submitted=submitted,
        served=submitted,
        mutations=int(live.mutations),
        mutation_rate_per_s=round(live.mutations / elapsed, 4),
        epochs_advanced=int(live.epoch),
        compactions=int(live.compactions),
        deletions=int(live.deletions),
        reweights=int(live.reweights),
        reseeds=int(live.reseeds),
        scheduler_compactions=int(sched.scheduler_compactions),
        cache_hit_fraction=round(hit_frac, 4),
        peak_occupancy=round(live.peak_count / capacity, 4))
    name = f"serve_live_rmat{scale}"
    return (name, [qps], extra, fresh_run)


def run_config(config, args):
    """Returns (name, gteps samples list, extra json fields,
    rerun() -> one more gteps sample)."""
    pair_t = args.pair if args.pair > 0 else None
    import numpy as np

    from lux_tpu.graph import pair_relabel

    if config.startswith("serve-slo"):
        return run_serve_load(config, args, chaos=False)

    if config.startswith("serve-chaos"):
        return run_serve_load(config, args, chaos=True)

    if config.startswith("serve-live"):
        return run_serve_live(config, args)

    if config.startswith("gather-ab"):
        # paged-vs-flat A/B: "gather-ab@paged[:reorder]" names one
        # side + preprocessing each; all sides run the SAME base
        # graph, so the pairs are directly comparable.  The reorder
        # token (round 16, lux_tpu/reorder.py) swaps the degree sort
        # for the page-aware pass and records it in the line's
        # ``reorder`` field (scripts/check_bench.py validates
        # mode-vs-name AND fill-not-decreased vs the paired none
        # line).
        from lux_tpu.apps import pagerank
        from lux_tpu.graph import ShardedGraph, degree_relabel
        from lux_tpu.ops.pagegather import plan_paged_stats

        _, _, spec = config.partition("@")
        mode, _, reorder = (spec or "paged").partition(":")
        reorder = reorder or "none"
        scale = args.scale or DEFAULT_SHAPE["gather-ab"][0]
        ef = args.ef or DEFAULT_SHAPE["gather-ab"][1]
        shape = getattr(args, "shape", "rmat")
        if shape == "community":
            from lux_tpu.convert import community_graph
            t0 = time.perf_counter()
            g = community_graph(scale=scale, edge_factor=ef)
            if args.verbose:
                print(f"# community graph built: nv={g.nv} ne={g.ne}"
                      f" ({time.perf_counter() - t0:.1f}s)",
                      file=sys.stderr)
        else:
            g = build_graph(scale, ef, args.verbose)
        if reorder == "none":
            # degree sort concentrates hubs into shared pages — the
            # round-15 baseline preprocessing, kept for the paired
            # none lines so reorder gains are measured against it
            g2, _perm = degree_relabel(g)
        else:
            from lux_tpu.reorder import page_reorder
            g2, _perm, rep = page_reorder(g, method=reorder,
                                          num_parts=args.np,
                                          verbose=args.verbose)
            if args.verbose:
                print(f"# reorder {reorder}: padded_fill "
                      f"{rep['baseline_fill']} -> "
                      f"{rep['chosen_fill']}", file=sys.stderr)
        sg = ShardedGraph.build(g2, args.np, vpad_align=128)
        eng = pagerank.build_engine(g2, num_parts=args.np, sg=sg,
                                    gather=mode, health=args.health)
        # the recorded page stats come from the SAME counting pass
        # for every side (dense paged shape) — the exact objective
        # the reorder pass maximizes — so paired lines compare one
        # quantity regardless of delivery mode or the engine's
        # resolved exchange (a pagemajor plan's virtual fill or an
        # owner-shaped fill would break check_bench's
        # fill-not-decreased pairing rule on a correct run)
        stats = plan_paged_stats(sg)
        extra = {"np": args.np, "scale": scale, "ef": ef,
                 "relabel": True, "pair_threshold": None,
                 "gather": mode, "exchange": eng.exchange,
                 "reorder": reorder, "shape": shape,
                 "page_ratio": round(float(stats["page_ratio"]), 4),
                 # the PADDED fill — live lanes per padded row, the
                 # exact input gather="auto" and the phase model
                 # consume (class-pad rows pay full machinery)
                 "page_fill": round(float(stats["padded_fill"]), 2)}
        _audit_build(eng, args, extra)
        samples, rerun = bench_fused(eng, g.ne, args.ni, args.verbose,
                                     args.repeats)
        extra["ne"] = int(g.ne)
        tag = "comm" if shape == "community" else "rmat"
        rtok = "" if reorder == "none" else f"{reorder}_"
        return (f"pagerank_{mode}_{rtok}{tag}{scale}",
                [s / 1e9 for s in samples], extra,
                lambda: rerun() / 1e9)

    if config.startswith("mxu-ab"):
        # MXU-vs-VPU reduce A/B (round 23, ops/tiled.py):
        # "mxu-ab@mxu" / "mxu-ab@vpu" name one reduce path each; both
        # sides run the SAME degree-sorted community graph and the
        # SAME B=8-column personalized-pagerank program (the wide
        # payload is where the one-hot contraction amortizes its
        # ~160 ns materialization toll — scalemodel.
        # mxu_break_even_wide), so the pair isolates the chunk-row
        # reduce and nothing else.  Every line records the engine's
        # RESOLVED mode plus the scalemodel per-row rates for BOTH
        # paths (the modeled step-change); scripts/check_bench.py
        # validates mode-vs-name and rejects an mxu line whose
        # paired vpu baseline is missing from the artifact.  The
        # real-TPU run is debt mxu-core-ab (PERF.md section 7).
        from lux_tpu import scalemodel
        from lux_tpu.apps import pagerank
        from lux_tpu.convert import community_graph
        from lux_tpu.graph import ShardedGraph, degree_relabel
        from lux_tpu.ops.pagegather import plan_paged_stats

        _, _, mode = config.partition("@")
        mode = mode or "mxu"
        if mode not in ("mxu", "vpu"):
            raise ValueError(f"mxu-ab side must be mxu|vpu, "
                             f"got {mode!r}")
        scale = args.scale or DEFAULT_SHAPE["mxu-ab"][0]
        ef = args.ef or DEFAULT_SHAPE["mxu-ab"][1]
        t0 = time.perf_counter()
        g = community_graph(scale=scale, edge_factor=ef)
        if args.verbose:
            print(f"# community graph built: nv={g.nv} ne={g.ne}"
                  f" ({time.perf_counter() - t0:.1f}s)",
                  file=sys.stderr)
        g2, _perm = degree_relabel(g)
        sg = ShardedGraph.build(g2, args.np, vpad_align=128)
        # fixed-seed sources: every side (and every round) serves the
        # same query set; B=8 matches the flagship auto-engagement
        # audit config (ppr_np2_batched)
        B = 8
        rng = np.random.default_rng(23)
        sources = sorted(int(x) for x in
                         rng.choice(g2.nv, size=B, replace=False))
        eng = pagerank.build_engine(g2, num_parts=args.np, sg=sg,
                                    sources=sources,
                                    use_mxu=(mode == "mxu"),
                                    health=args.health)
        stats = plan_paged_stats(sg)
        kind = getattr(eng.program, "reduce", "sum")
        extra = {"np": args.np, "scale": scale, "ef": ef,
                 "relabel": True, "pair_threshold": None,
                 "batch": B, "shape": "community",
                 "mxu": mode, "use_mxu": bool(eng.use_mxu),
                 "exchange": eng.exchange, "reduce_kind": kind,
                 # the modeled per-chunk-row rates for BOTH paths —
                 # identical on the paired lines by construction, so
                 # the pair's measured ratio is read against ONE
                 # prediction (scalemodel round 23)
                 "mxu_row_ns": round(scalemodel.mxu_reduce_row_ns(
                     wide=B, kind=kind), 2),
                 "vpu_row_ns": round(scalemodel.vpu_reduce_row_ns(
                     wide=B), 2),
                 "page_fill": round(float(stats["padded_fill"]), 2)}
        _audit_build(eng, args, extra)
        samples, rerun = bench_fused(eng, g.ne, args.ni, args.verbose,
                                     args.repeats)
        extra["ne"] = int(g.ne)
        return (f"ppr_{mode}_comm{scale}",
                [s / 1e9 for s in samples], extra,
                lambda: rerun() / 1e9)

    if config.startswith(("ksssp-batch", "ppr-batch")):
        # query-batched configs (ROADMAP item 2): "<base>@B" names
        # one sweep point — handled BEFORE the generic shape lookup
        # (DEFAULT_SHAPE is keyed by the base name, not "@B").
        # Sources are a fixed-seed draw so every sweep point (and
        # every round) serves the same query set; pair delivery is
        # scalar-state and stays off.
        base, _, bstr = config.partition("@")
        B = int(bstr) if bstr else 8
        scale = args.scale or DEFAULT_SHAPE[base][0]
        ef = args.ef or DEFAULT_SHAPE[base][1]
        extra = {"np": args.np, "scale": scale, "ef": ef}
        g = build_graph(scale, ef, args.verbose)
        rng = np.random.default_rng(7)
        sources = sorted(int(x) for x in
                         rng.choice(g.nv, size=B, replace=False))
        if base == "ksssp-batch":
            from lux_tpu.apps import sssp
            eng = sssp.build_engine(g, sources=sources,
                                    num_parts=args.np,
                                    health=args.health)
            extra.update(batch=B, relabel=False, pair_threshold=None,
                         exchange=eng.exchange)
            _audit_build(eng, args, extra)
            samples, rerun = bench_converge(eng, g.ne, args.verbose,
                                            args.repeats)
            name = f"ksssp_b{B}_rmat{scale}"
        else:
            from lux_tpu.apps import pagerank
            eng = pagerank.build_engine(g, num_parts=args.np,
                                        sources=sources,
                                        health=args.health)
            extra.update(batch=B, relabel=False, pair_threshold=None,
                         exchange=eng.exchange)
            _audit_build(eng, args, extra)
            samples, rerun = bench_fused(eng, g.ne, args.ni,
                                         args.verbose, args.repeats)
            name = f"ppr_b{B}_rmat{scale}"
        extra["ne"] = int(g.ne)
        return (name, [s / 1e9 for s in samples], extra,
                lambda: rerun() / 1e9)

    scale = args.scale or DEFAULT_SHAPE[config][0]
    ef = args.ef or DEFAULT_SHAPE[config][1]
    extra = {"np": args.np, "scale": scale, "ef": ef}

    if config in ("pagerank", "pagerank-mp"):
        from lux_tpu.apps import pagerank
        # pagerank-mp: the multi-part OWNER-exchange path (+ pair
        # composition) — the mesh-relevant configuration, regression-
        # guarded in the round artifact (round-3 VERDICT weak #2).
        # The scale-23 table (34 MB) sits under the auto threshold, so
        # the exchange is pinned explicitly.
        mp = config == "pagerank-mp"
        np_parts = max(args.np, 4) if mp else args.np
        g = build_graph(scale, ef, args.verbose)
        g2, _perm, starts = pair_relabel(g, np_parts,
                                         pair_threshold=pair_t or 16)
        eng = pagerank.build_engine(g2, num_parts=np_parts,
                                    pair_threshold=pair_t,
                                    pair_min_fill=args.min_fill,
                                    starts=starts,
                                    exchange="owner" if mp else "auto",
                                    health=args.health)
        extra.update(relabel=True, pair_threshold=pair_t, np=np_parts,
                     exchange=eng.exchange, min_fill=args.min_fill)
        _audit_build(eng, args, extra)
        _print_coverage(args, eng)
        samples, rerun = bench_fused(eng, g.ne, args.ni, args.verbose,
                                     args.repeats)
        name = f"pagerank{'_mp' if mp else ''}_rmat{scale}"
    elif config == "colfilter":
        from lux_tpu.apps import colfilter
        g = build_graph(scale, ef, args.verbose, weighted=True)
        if pair_t is not None:
            g2, _perm, starts = pair_relabel(g, args.np,
                                             pair_threshold=pair_t)
            eng = colfilter.build_engine(g2, num_parts=args.np,
                                         pair_threshold=pair_t,
                                         pair_min_fill=args.min_fill_dot,
                                         starts=starts,
                                         health=args.health)
            extra.update(relabel=True, pair_threshold=pair_t,
                         min_fill=args.min_fill_dot)
        else:
            eng = colfilter.build_engine(g, num_parts=args.np,
                                         health=args.health)
            extra.update(relabel=False, pair_threshold=None)
        _audit_build(eng, args, extra)
        _print_coverage(args, eng)
        samples, rerun = bench_fused(eng, g.ne, args.ni, args.verbose,
                                     args.repeats)
        name = f"colfilter_rmat{scale}"
    else:
        from lux_tpu.apps import components, sssp
        weighted = config == "sssp-delta"
        g = build_graph(scale, ef, args.verbose, weighted=weighted)
        if config == "cc":
            # CC semantics need an undirected graph; symmetrize and
            # count the doubled edge set in GTEPS (it is what runs)
            from lux_tpu.graph import Graph
            s, d = components.symmetrize(*g.edge_arrays())
            g = Graph.from_edges(s, d, g.nv)
            if args.verbose:
                print(f"# symmetrized: ne={g.ne}", file=sys.stderr)
            g2, _perm, starts = pair_relabel(g, args.np, pair_threshold=pair_t or 16)
            eng = components.build_engine(g2, num_parts=args.np,
                                          pair_threshold=pair_t,
                                          pair_min_fill=args.min_fill,
                                          starts=starts,
                                          health=args.health)
            extra.update(relabel=True, pair_threshold=pair_t,
                         min_fill=args.min_fill)
        else:
            # sssp-mp: the PUSH engine's mesh-relevant path — np=4
            # owner-side dense iterations + sparse queues, regression-
            # guarded like pagerank-mp (round-4 VERDICT #7).  The
            # scale-23 int32 label table (34 MB) sits under the auto
            # threshold, so the exchange is pinned explicitly.
            mp = config == "sssp-mp"
            np_parts = max(args.np, 4) if mp else args.np
            g2, perm, starts = pair_relabel(g, np_parts,
                                            pair_threshold=pair_t or 16)
            rank = np.empty(g.nv, np.int64)
            rank[perm] = np.arange(g.nv)
            eng = sssp.build_engine(
                g2, start_vertex=int(rank[0]), num_parts=np_parts,
                weighted=weighted,
                delta="auto" if config == "sssp-delta" else None,
                pair_threshold=pair_t, pair_min_fill=args.min_fill,
                starts=starts,
                exchange="owner" if mp else "auto",
                health=args.health)
            extra.update(relabel=True, pair_threshold=pair_t,
                         min_fill=args.min_fill, np=np_parts,
                         exchange=eng.exchange,
                         delta="auto" if weighted else None)
        _audit_build(eng, args, extra)
        _print_coverage(args, eng)
        samples, rerun = bench_converge(eng, g.ne, args.verbose,
                                        args.repeats)
        name = f"{config.replace('-', '_')}_rmat{scale}"
    # ne as it RAN (post-symmetrize for cc): lets check_bench re-derive
    # each sample from the telemetry runs' (iters, seconds)
    extra["ne"] = int(g.ne)
    return (name, [s / 1e9 for s in samples], extra,
            lambda: rerun() / 1e9)


def emit(name, samples, extra, attempts=None, discarded=(),
         telemetry=None, calibration=None):
    """One JSON metric line.  attempts = total timed runs (originals
    + outlier reruns); discarded = samples thrown out by the >3x rule
    — recorded, never silently medianed; telemetry = per-run seconds
    + counter digest; calibration = the session-calibration
    fingerprint digest (lux_tpu/observe.py — labels the line with
    this process's measured probe rate so an off-canon session
    is detected, not medianed).  scripts/check_bench.py validates
    all of it.  Returns the line dict (artifact/ledger writers)."""
    gteps = median(samples)
    # serve-slo lines are qps, not GTEPS — the unit names the metric
    # suffix so the two families can never be conflated by name
    unit = extra.get("unit", "GTEPS")
    per_query = {}
    if "batch" in extra:
        # the machine rate serves every query of the batch at once:
        # query_gteps = B x value is the delivered query-edge
        # throughput, and 1/query_gteps the per-query ns/edge cost
        # (the ~9/B amortization, PERF_NOTES "query batching");
        # scripts/check_bench.py cross-checks it against batch*value
        qg = round(gteps * extra["batch"], 4)
        # derive the ns cost from the ROUNDED rate so the published
        # pair is self-consistent to the digits it carries
        per_query = {"query_gteps": qg,
                     "per_query_edge_ns": (round(1.0 / qg, 4)
                                           if qg > 0 else None)}
    result = {
        "metric": f"{name}_{unit.lower()}_per_chip",
        "value": round(gteps, 4),
        "unit": unit,
        "vs_baseline": round(gteps / 1.0, 4),
        **per_query,
        "samples": [round(s, 4) for s in samples],
        "attempts": len(samples) if attempts is None else attempts,
        "discarded": [round(d, 4) for d in discarded],
        **({"telemetry": telemetry} if telemetry is not None else {}),
        "calibration": calibration,
        **extra,
    }
    print(json.dumps(result), flush=True)
    return result


def next_artifact_path(directory=".") -> str:
    """BENCH_rNN.json with NN = one past the highest existing round —
    the bench trajectory was EMPTY because artifact assembly was a
    manual step; now the driver metric file writes itself."""
    import os
    import re

    best = 0
    for name in os.listdir(directory or "."):
        m = re.match(r"^BENCH_r(\d+)\.json$", name)
        if m:
            best = max(best, int(m.group(1)))
    return os.path.join(directory or ".", f"BENCH_r{best + 1:02d}.json")


def write_artifact(path, lines, calibration, rc, argv):
    """The machine-readable bench artifact (schema shared with
    scripts/check_bench.py's driver-artifact reader: metric lines
    live in 'tail', one JSON object per line)."""
    doc = {
        "round": None,
        "cmd": "python bench.py " + " ".join(argv),
        "rc": rc,
        "calibration": calibration,
        "tail": "\n".join(json.dumps(ln) for ln in lines),
    }
    import re
    m = re.search(r"BENCH_r(\d+)\.json$", path)
    if m:
        doc["round"] = int(m.group(1))
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {path} ({len(lines)} metric line(s))",
          file=sys.stderr)


def config_telemetry(events, start_idx, iter_stats):
    """The metric line's ``telemetry`` field for one config: the
    ``timed_run`` events emitted since ``start_idx`` (one per timed
    repeat, outlier reruns included), the counter digest, and — with
    -health — the watchdog digest from the run's ``health`` event
    (null when the watchdog was off; a TRIPPED watchdog raises and
    the config emits a _FAILED line instead, so a digest here always
    reports a clean bill: tripped=false plus what was checked).
    Round 11 adds ``topology``: null normally, a {shrinks, ndev_final}
    digest when the run's events record a mid-run mesh shrink —
    scripts/check_bench.py REJECTS such lines (a degraded-mesh GTEPS
    must never be compared against full-mesh lines silently).
    scripts/check_bench.py type-checks all four."""
    runs = [{"repeat": ev["repeat"], "iters": ev["iters"],
             "seconds": ev["seconds"]}
            for ev in events.events[start_idx:]
            if ev["kind"] == "timed_run"]
    health = None
    for ev in events.events[start_idx:]:
        if ev["kind"] == "health":
            health = {k: v for k, v in ev.items()
                      if k not in ("t", "tm", "pid", "session",
                                   "kind", "where")}
    shrinks = [ev for ev in events.events[start_idx:]
               if ev["kind"] == "mesh_shrink"]
    topology = None
    if shrinks:
        last = shrinks[-1]
        topology = {"shrinks": len(shrinks),
                    "ndev_final": last.get("to_ndev",
                                           last.get("to_nproc"))}
    # round 13 (lux_tpu/tracing.py era): the per-part imbalance digest
    # — {kind, index (max/mean per-part work), parts (per-part
    # totals)} — null when -iter-stats was off or the engine predates
    # per-part counters.  check_bench cross-validates the index
    # against the parts and the parts sum against the scalar counters.
    return {"runs": runs,
            "counters": (iter_stats.summary()
                         if iter_stats is not None else None),
            "imbalance": (iter_stats.imbalance_digest()
                          if iter_stats is not None else None),
            "health": health,
            "topology": topology}


def main() -> int:
    from lux_tpu import runtime
    runtime.use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("-config", default=None,
                    choices=list(DEFAULT_SHAPE) + ["batch-sweep"],
                    help="run ONE config (default: all five, "
                         "pagerank last); 'batch-sweep' expands "
                         "ksssp-batch + ppr-batch over -batch "
                         "(one metric line per B)")
    ap.add_argument("-batch", default=BATCH_SWEEP_DEFAULT,
                    help="comma list of query-batch widths B for the "
                         "ksssp-batch/ppr-batch/batch-sweep configs "
                         f"(default {BATCH_SWEEP_DEFAULT!r})")
    ap.add_argument("-all", action="store_true",
                    help="run every config (pagerank last; the "
                         "default when -config is not given)")
    ap.add_argument("-rates", default="15,45",
                    help="comma list of offered qps for the "
                         "serve-slo config (one open-loop load step "
                         "and one metric line per rate)")
    ap.add_argument("-serve-queries", type=int, default=36,
                    dest="serve_queries",
                    help="queries per serve-slo load step")
    ap.add_argument("-serve-batch", type=int, default=4,
                    dest="serve_batch",
                    help="serving engine column count B for "
                         "serve-slo")
    ap.add_argument("-serve-kinds",
                    default="sssp,components,pagerank",
                    dest="serve_kinds",
                    help="mixed query kinds for the serve-slo load")
    ap.add_argument("-serve-replicas", type=int, default=2,
                    dest="serve_replicas",
                    help="replica count for the serve-chaos config "
                         "(lux_tpu/fleet.py; needs >= 2 — one dies)")
    ap.add_argument("-kill-boundary", type=int, default=1,
                    dest="kill_boundary",
                    help="segment boundary (post-warm) of the last "
                         "replica at which the serve-chaos kill plan "
                         "fires")
    ap.add_argument("-slo-ms", dest="slo_ms",
                    default="sssp=250,components=250,pagerank=1000",
                    help="per-kind latency SLO targets for "
                         "serve-slo, kind=ms comma list")
    ap.add_argument("-delta-capacity", type=int, default=64,
                    dest="delta_capacity",
                    help="live-graph delta block capacity for the "
                         "serve-live config (lux_tpu/livegraph.py; "
                         "sized so the mutation stream crosses the "
                         "compact threshold mid-run)")
    ap.add_argument("-reorder", default="none",
                    choices=["none", "native", "hillclimb"],
                    help="page-aware vertex reorder for the "
                         "gather-ab config (lux_tpu/reorder.py): "
                         "'native' = the clustering BFS pass "
                         "(native/reorder.cc), 'hillclimb' = "
                         "candidates + dominant-tile refinement "
                         "scored against the plan's measured "
                         "page_fill.  Non-none expands gather-ab to "
                         "FOUR lines (reordered pair + its paired "
                         "none baseline) so scripts/check_bench.py "
                         "can enforce fill-must-not-decrease")
    ap.add_argument("-shape", default="rmat",
                    choices=["rmat", "community"],
                    help="gather-ab graph family: 'rmat' (the bench "
                         "default — honest negative: little page "
                         "locality to harvest) or 'community' (the "
                         "scrambled planted-partition synthetic, "
                         "convert.community_edges — the locality-"
                         "rich case the reorder pass recovers)")
    ap.add_argument("-scale", type=int, default=0,
                    help="RMAT scale (nv = 2**scale; 0 = per-config "
                         "default)")
    ap.add_argument("-ef", type=int, default=0,
                    help="edges per vertex (0 = per-config default)")
    ap.add_argument("-ni", type=int, default=20,
                    help="iterations (fixed-iteration configs)")
    ap.add_argument("-np", type=int, default=1, help="partitions")
    ap.add_argument("-pair", type=int, default=PAIR_THRESHOLD,
                    help="pair-lane threshold (0 disables)")
    ap.add_argument("-min-fill", type=int, default=-1,
                    dest="min_fill", metavar="F",
                    help="pair rows under F live lanes ride the "
                         "residual instead (ops/pairs.py min_fill; "
                         "measured +33%% on the headline — the "
                         "RMAT21 sweep put the optimum at 24, "
                         "PERF_NOTES round 5; 0 disables; default -1 "
                         "= per-config: 24 for scalar programs, the "
                         "K-AWARE break-even for colfilter's SDDMM "
                         "rows, scalemodel.break_even_fill)")
    ap.add_argument("-repeats", type=int, default=3,
                    help="timed repeats per config; the JSON line "
                         "reports the median (run-to-run variance can "
                         "exceed round-over-round gains, PERF_NOTES)")
    ap.add_argument("-retries", type=int, default=2,
                    help="per-config retries for RETRYABLE failures "
                         "(transient worker/connection death, "
                         "classified by lux_tpu.resilience); "
                         "deterministic failures (OOM, an oversized "
                         "program) never retry")
    ap.add_argument("-backoff", type=float, default=5.0,
                    help="initial retry backoff seconds (doubles per "
                         "retry)")
    ap.add_argument("-outlier", type=float, default=3.0,
                    help="discard-and-rerun factor: samples more than "
                         "F x off the batch median are discarded, "
                         "re-run once, and recorded in 'discarded' "
                         "(VERDICT r5 #7; 0 disables)")
    ap.add_argument("-events", default=None, metavar="FILE",
                    help="append the run's structured telemetry "
                         "events as JSONL to FILE "
                         "(scripts/events_summary.py renders it); "
                         "the per-config 'telemetry' JSON field is "
                         "recorded regardless")
    ap.add_argument("-iter-stats", action="store_true",
                    dest="iter_stats",
                    help="record device-side per-iteration counters "
                         "and put their digest in each line's "
                         "telemetry.counters — runs the engines' "
                         "counter-recording loop variant, so keep it "
                         "OFF for headline numbers (overhead was "
                         "within noise on the earlier installation, "
                         "PERF_NOTES round 7)")
    ap.add_argument("-health", action="store_true",
                    help="run every config under the device-side "
                         "health watchdog (lux_tpu/health.py) and "
                         "record its digest in telemetry.health — a "
                         "separate compiled loop variant (within "
                         "noise of watchdog-off on the earlier "
                         "installation, PERF_NOTES round 9), so keep "
                         "it OFF for headline numbers")
    ap.add_argument("-audit", default="warn",
                    choices=["off", "warn", "error"],
                    help="static program audit of every config's "
                         "engine build (lux_tpu/audit.py; tracing "
                         "only, no extra compiles).  The digest "
                         "lands in each metric line's 'audit' field "
                         "and scripts/check_bench.py REJECTS lines "
                         "from an audit-failing build; 'error' "
                         "additionally fails the config at build "
                         "time, 'off' omits the field")
    ap.add_argument("-json-out", default="auto", dest="json_out",
                    metavar="auto|off|FILE",
                    help="write the machine-readable BENCH artifact "
                         "('auto' = next BENCH_rNN.json in the cwd — "
                         "the hand-assembly gap that left the bench "
                         "trajectory empty; 'off' disables)")
    ap.add_argument("-ledger", default="PERFLEDGER.jsonl",
                    metavar="FILE",
                    help="append every metric line to the persistent "
                         "perf ledger (lux_tpu/observe.py; 'off' "
                         "disables)")
    ap.add_argument("-flight", default=None, metavar="FILE",
                    help="install the crash flight recorder "
                         "(lux_tpu/tracing.py): the resilience "
                         "supervisor dumps the recent-event ring + "
                         "last health word to FILE on fatal/topology "
                         "failures, so a config that dies mid-run "
                         "stays diagnosable")
    ap.add_argument("-verbose", action="store_true")
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a measurement path that finds no chip fails; it does not
        # fall back to the CPU (nor append CPU rows to the ledger)
        print(f"error: bench.py measures on a TPU; this process sees "
              f"platform={dev.platform!r} ({dev.device_kind}).  Tests "
              f"drive bench.run_config directly.", file=sys.stderr)
        return 2
    if args.flight:
        from lux_tpu import tracing
        tracing.install_flight_recorder(args.flight)
    if args.repeats < 1:
        ap.error("-repeats must be >= 1")
    if args.min_fill < -1:
        ap.error("-min-fill must be >= -1 "
                 "(-1 = per-config default, 0 = off)")
    if args.min_fill == -1:      # per-config defaults
        args.min_fill = 24              # scalar rows, round-5 optimum
        args.min_fill_dot = "auto"      # K-aware SDDMM break-even
    elif args.min_fill == 0:
        args.min_fill = args.min_fill_dot = None
    else:
        args.min_fill_dot = args.min_fill

    from lux_tpu import observe, resilience, telemetry

    configs = ([args.config] if args.config and not args.all
               else ["cc", "sssp", "sssp-delta", "colfilter",
                     "sssp-mp", "pagerank-mp", "pagerank"])
    try:
        batch_widths = [int(b) for b in
                        str(args.batch).split(",") if b.strip()]
    except ValueError:
        ap.error(f"-batch must be a comma list of ints, got "
                 f"{args.batch!r}")
    if any(b < 1 for b in batch_widths) or not batch_widths:
        ap.error("-batch widths must be >= 1")
    # expand the batch configs into one sweep point per width
    expanded = []
    for c in configs:
        if c == "batch-sweep":
            expanded += [f"ksssp-batch@{b}" for b in batch_widths]
            expanded += [f"ppr-batch@{b}" for b in batch_widths]
        elif c in ("ksssp-batch", "ppr-batch"):
            expanded += [f"{c}@{b}" for b in batch_widths]
        elif c in ("serve-slo", "serve-chaos"):
            try:
                rates = [float(r) for r in args.rates.split(",")
                         if r.strip()]
            except ValueError:
                ap.error(f"-rates must be a comma list of numbers, "
                         f"got {args.rates!r}")
            if not rates or any(r <= 0 for r in rates):
                ap.error("-rates must be positive offered qps")
            expanded += [f"{c}@{r:g}" for r in rates]
        elif c == "mxu-ab":
            # mxu first (the headline of the A/B); the vpu side is
            # its paired baseline — check_bench rejects an mxu line
            # that arrives without the pair in the same artifact
            expanded += ["mxu-ab@mxu", "mxu-ab@vpu"]
        elif c == "gather-ab":
            # one line per side, paged first (the headline of the
            # A/B); both carry the plan's page stats.  A reorder run
            # ALSO emits the none-reorder pair, so every reordered
            # line has its paired baseline in the same artifact
            # (check_bench enforces fill-must-not-decrease on pairs)
            expanded += ["gather-ab@paged", "gather-ab@flat"]
            if args.reorder != "none":
                expanded += [f"gather-ab@paged:{args.reorder}",
                             f"gather-ab@flat:{args.reorder}"]
        else:
            expanded.append(c)
    configs = expanded
    failures = 0
    # one event log for the whole bench run (in-memory always — the
    # timed_run events are the per-config telemetry field; -events
    # additionally streams them to disk as JSONL)
    events = telemetry.EventLog(args.events)
    # session calibration FIRST (lux_tpu/observe.py): the fixed-cost
    # reference probe stamps every metric line with this process's
    # measured primitive rate vs the canonical figures, so an
    # off-canon session is labeled at the source.  The probe is also
    # the run's first Pallas compile (the lane-shuffle kernel): a
    # probe crash is the bench's crash, not a calibration=null line.
    with telemetry.use(events=events):
        fingerprint = observe.calibrate()
    cal_digest = fingerprint.digest()
    if fingerprint.grade == "degraded":
        print(f"# WARNING: session graded 'degraded' — gather probe "
              f"{fingerprint.deviation:.2f}x the canonical figure "
              f"(>3x off in either direction; the canon predates "
              f"this installation); lines are labeled and "
              f"check_bench will reject them from the trajectory",
              file=sys.stderr)
    ledger = (None if args.ledger == "off"
              else observe.PerfLedger(args.ledger))
    metric_lines = []
    for config in configs:
        report = resilience.RunReport()
        policy = resilience.RetryPolicy(retries=max(0, args.retries),
                                        backoff_s=args.backoff)
        st = telemetry.IterStats() if args.iter_stats else None
        events.emit("config_start", config=config,
                    schema=telemetry.SCHEMA)
        idx0 = len(events.events)
        with telemetry.use(events=events, iter_stats=st):
            try:
                # supervised: a transient worker crash retries the
                # whole config (fresh graph+engine — exactly what a
                # dead worker needs) with backoff; fatal classes
                # surface immediately
                (name, samples, extra, rerun), report = \
                    resilience.supervise(
                        lambda k: run_config(config, args), policy,
                        report)
                try:
                    samples, discarded, attempts = \
                        resilience.screen_outliers(
                            samples, rerun, factor=args.outlier)
                except Exception as e:  # noqa: BLE001 — rerun crashed
                    # a crash during an outlier RERUN must not void
                    # the already-measured batch: screen without the
                    # rerun (the discard still drops the collapse)
                    # and record what happened
                    samples, discarded, attempts = \
                        resilience.screen_outliers(
                            samples, None, factor=args.outlier)
                    extra = dict(
                        extra,
                        rerun_error=f"{type(e).__name__}: {e}"[:200],
                        rerun_error_class=resilience.classify(e))
            except Exception as e:  # noqa: BLE001 — one config's crash
                # must not take down the remaining configs or the
                # tail-line headline metric the driver records; it
                # still fails the run (rc below)
                failures += 1
                failed = {"metric": f"{config}_FAILED",
                          "error": f"{type(e).__name__}: {e}"[:300],
                          "attempts": report.attempts,
                          "failure_class": resilience.classify(e)}
                print(json.dumps(failed), flush=True)
                metric_lines.append(failed)
                continue
        if report.attempts > 1:
            extra = dict(extra, run_attempts=report.attempts)
        line = emit(name, samples, extra, attempts=attempts,
                    discarded=discarded,
                    telemetry=config_telemetry(events, idx0, st),
                    calibration=cal_digest)
        metric_lines.append(line)
        if ledger is not None:
            try:
                ledger.append("bench", line, fingerprint)
            except OSError as e:
                print(f"# perf-ledger append failed: {e}",
                      file=sys.stderr)
    events.close()
    rc = 1 if failures else 0
    if args.json_out != "off" and metric_lines:
        grade = cal_digest.get("grade")
        if args.json_out == "auto" and grade != "canonical":
            # the BENCH_rNN series IS the trajectory: an auto-minted
            # artifact from an off-canon session would enter it (and
            # trip the repo artifact audit).  The ledger keeps the
            # labeled lines; an explicit -json-out FILE still writes
            # anywhere.
            print(f"# artifact suppressed (session grade="
                  f"{grade}); lines are in the ledger only — pass "
                  f"-json-out FILE to force a file", file=sys.stderr)
        else:
            path = (next_artifact_path() if args.json_out == "auto"
                    else args.json_out)
            try:
                write_artifact(path, metric_lines, cal_digest, rc,
                               sys.argv[1:])
            except OSError as e:
                print(f"# artifact write failed: {e}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
