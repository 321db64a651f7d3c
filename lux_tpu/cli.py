"""Command-line apps with the reference's flag surface.

The reference ships one binary per app (``./pagerank -ll:gpu 4 -file
g.lux -ni 10``, reference README.md:40-52, pagerank.cc:121-148,
sssp.cc:148-180).  Here: ``python -m lux_tpu.cli <app> -file ... ``.

Flags (reference names kept):
  -file PATH    .lux graph file (required)
  -ni N         iterations (pagerank/colfilter; default 10)
  -start V      source vertex (sssp; default 0)
  -check        run the correctness audit after the run
  -verbose      per-iteration progress + phase timing
  -np N         number of partitions (the reference's -ll:gpu x nodes;
                default: the -mesh size, i.e. one partition per device)
  -mesh N       shard over an N-device mesh (default: 1 device)
  -weighted     treat the graph/run as weighted (colfilter implies it)
  -weight-type T  sssp -weighted: the file's weights are int32
                (default) or float32; a .lux does not say which.
                int32 weights give exact int32 distances, float32
                weights float32 ones (apps/sssp.py)
  -retries N    supervised run: classify + retry transient failures,
                auto-resuming from the last segment checkpoint
  -seg-budget S duration-budgeted segments (each XLA execution < S s;
                lux_tpu/segmented.py)
  -resume CKPT  checkpoint path to save to / resume from
                (all three: lux_tpu/resilience.py)
  -elastic      degraded-mesh recovery (round 11): a topology fault
                (device loss, coordination-service heartbeat loss)
                rebuilds the mesh over the surviving devices and
                resumes from the segment checkpoint instead of dying
                (supervised path + -mesh > 1 only)
  -events FILE  append structured JSONL telemetry events (header with
                graph shape + HBM estimate, per-run/segment timings,
                retries, checkpoints; lux_tpu/telemetry.py)
  -iter-stats   device-side per-iteration counters accumulated INSIDE
                the fused loop (push: frontier/edges, pull: residual/
                changed), replayed after the run — works on the fused
                AND the supervised/segmented paths
  -health       device-side health watchdog (lux_tpu/health.py):
                NaN/Inf, divergence/oscillation, frontier stalls trip
                a typed HealthError with the check/part/iteration
  -validate     structural .lux validation at load (lux_tpu/format.
                validate_graph; offline: scripts/fsck_lux.py)
  -audit MODE   static program audit at engine build (lux_tpu/audit.
                py): warn prints findings, error refuses a violating
                build with a typed AuditError (exit 2).  Repo-wide
                form: python -m lux_tpu.audit
  -calibrate    session-calibration probe before the run (lux_tpu/
                observe.py): prints/emits the fingerprint (measured
                probe ns/elem vs canonical, platform, ndev, grade) —
                an off-canon session is labeled up front.

Timing methodology matches the reference: wall clock around the
iteration loop only, printed as ``ELAPSED TIME = ... s`` plus GTEPS
(reference pagerank.cc:108-118; BASELINE.md).
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import time

import numpy as np


from lux_tpu.timing import timed_converge, timed_fused_run


def _min_fill_arg(v: str):
    """-min-fill value: an int, or 'auto' for the K-aware modeled
    break-even (ops/pairs.resolve_min_fill)."""
    if v == "auto":
        return "auto"
    return int(v)


def _common(ap: argparse.ArgumentParser):
    ap.add_argument("-file", required=True, help=".lux graph file")
    ap.add_argument("-np", type=int, default=0,
                    help="partitions (0 = the mesh size)")
    ap.add_argument("-mesh", type=int, default=1,
                    help="devices in the parts mesh")
    ap.add_argument("-check", action="store_true")
    ap.add_argument("-verbose", action="store_true")
    ap.add_argument("-validate", action="store_true",
                    help="validate the .lux file's structural "
                         "invariants at load (monotone row_ptrs, "
                         "col_idx in range, section sizes, degree "
                         "consistency — lux_tpu/format.validate_graph"
                         "); a malformed file exits with a typed "
                         "error instead of running to a wrong answer "
                         "(offline form: scripts/fsck_lux.py)")
    ap.add_argument("-health", action="store_true",
                    help="run under the device-side health watchdog "
                         "(lux_tpu/health.py): NaN/Inf state, "
                         "divergent/oscillating residuals and "
                         "frontier stalls accumulate an O(1) health "
                         "word inside the fused loop, checked at "
                         "run/segment boundaries; a trip raises a "
                         "typed HealthError naming the check, part "
                         "and iteration.  Compiles a separate loop "
                         "variant; the default programs are untouched")
    ap.add_argument("-audit", default=None, choices=["warn", "error"],
                    help="statically audit every compiled program "
                         "variant at engine build (lux_tpu/audit.py: "
                         "gather budget, baked-constant ceiling, "
                         "dtype discipline, collective schedule, "
                         "identity inits, no in-loop callbacks — "
                         "traced jaxprs only, nothing executes).  "
                         "'warn' prints AuditWarnings; 'error' "
                         "refuses to run a violating build (exit 2, "
                         "typed AuditError)")
    ap.add_argument("-profile", default=None, metavar="DIR",
                    help="capture an XLA profiler trace of the timed "
                         "run into DIR (view in TensorBoard/Perfetto)")
    ap.add_argument("-pair", type=int, default=None, metavar="T",
                    help="enable pair-lane delivery with threshold T "
                         "(degree-relabels the graph internally; "
                         "per-vertex results are mapped back to input "
                         "ids where printed; colfilter's edge-wise "
                         "RMSE/check need no mapping)")
    ap.add_argument("-exchange", default="auto",
                    choices=["auto", "gather", "owner"],
                    help="state exchange for pagerank/sssp/cc: "
                         "'gather' (all-gather + per-edge gather from "
                         "the full table), 'owner' (per-source-part "
                         "gathers from own shards + reduce_scatter; "
                         "2x+ once state outgrows ~64 MB — "
                         "PERF_NOTES.md), or 'auto' (owner above a "
                         "96 MB state table; the default).  "
                         "colfilter's dot path has its own dst-free "
                         "machinery and ignores this")
    ap.add_argument("-gather", default="flat",
                    choices=["flat", "paged", "pagemajor", "auto"],
                    help="state-table delivery for dense iterations: "
                         "'paged' replaces the ~9 ns/edge per-edge "
                         "gather with the page-binned row fetch + "
                         "Pallas lane shuffle (ops/pagegather.py); "
                         "'pagemajor' binds delivery rows to source "
                         "pages first (full 128-lane rows) and "
                         "routes completed rows to their destination "
                         "tiles second (owner engines: an all_to_all "
                         "routing hop); 'auto' arbitrates flat vs "
                         "paged vs page-major by the scalemodel "
                         "break-even on the plan's measured "
                         "unique-page ratio / fills (best after a "
                         "page-aware reorder, lux_tpu/reorder.py).  "
                         "Mutually exclusive with -pair (both are "
                         "row-granular delivery layouts)")
    ap.add_argument("-mxu", default="auto",
                    choices=["auto", "mxu", "vpu"],
                    help="per-chunk reduce formulation (ops/tiled."
                         "chunk_partials): 'mxu' forces the one-hot "
                         "contraction core (round 23 — sum as one "
                         "int8 matmul, min/max as the bit-serial "
                         "tournament, the segmented combine as "
                         "blocked scan-as-matmul), 'vpu' forces the "
                         "fused masked broadcast-reduce, 'auto' "
                         "(default) engages the MXU when the "
                         "program's K x B payload width amortizes "
                         "the one-hot toll (scalemodel."
                         "mxu_break_even_wide: wide >= 2 for sum — "
                         "batched/K-dim programs — never for "
                         "min/max)")
    ap.add_argument("-min-fill", type=_min_fill_arg, default=None,
                    dest="min_fill", metavar="F",
                    help="with -pair: drop pair rows that would "
                         "deliver < F live lanes (their edges ride "
                         "the residual path); break-even ~15 at the "
                         "measured 150 ns/row vs ~10 ns/edge rates "
                         "(PERF_NOTES round 5).  'auto' picks the "
                         "K-AWARE modeled break-even (~16 scalar, "
                         "~22 for colfilter's K=20 SDDMM rows — "
                         "scalemodel.break_even_fill)")
    ap.add_argument("-sparse", type=int, default=1, metavar="0|1",
                    help="sssp/cc: keep the src-sorted sparse-frontier "
                         "view (1, default).  0 halves edge memory at "
                         "big scale; every iteration runs dense "
                         "(memory_report(push_sparse=...) prices it)")
    ap.add_argument("-retries", type=int, default=0, metavar="N",
                    help="supervise the run (lux_tpu.resilience): "
                         "classify failures, retry transient ones up "
                         "to N times with exponential backoff, and "
                         "auto-resume from the last segment "
                         "checkpoint instead of restarting")
    ap.add_argument("-seg-budget", type=float, default=0.0,
                    dest="seg_budget", metavar="S",
                    help="run in duration-budgeted segments: size "
                         "each XLA execution to stay under S seconds "
                         "(lux_tpu/segmented.py DurationBudget); "
                         "implies the supervised path")
    ap.add_argument("-elastic", action="store_true",
                    help="with the supervised path (-retries/"
                         "-seg-budget/-resume) and -mesh > 1: survive "
                         "device loss.  A TOPOLOGY-classified failure "
                         "(device unavailable, coordination-service "
                         "heartbeat loss) rebuilds the mesh over the "
                         "surviving devices — the largest count "
                         "dividing -np — re-places the checkpointed "
                         "state, and resumes degraded instead of "
                         "dying (lux_tpu/resilience.py round 11)")
    ap.add_argument("-resume", default=None, metavar="CKPT",
                    help="checkpoint file: save after every segment "
                         "and resume from it if it exists; implies "
                         "the supervised path (without -resume, "
                         "-retries/-seg-budget checkpoint to a "
                         "temporary file for in-run crash recovery "
                         "only).  Supervised timing includes segment "
                         "checkpoint saves")
    ap.add_argument("-events", default=None, metavar="FILE",
                    help="append structured telemetry events to FILE "
                         "as JSONL (one object per line; schema in "
                         "lux_tpu/telemetry.py, rendered by "
                         "scripts/events_summary.py): graph header "
                         "with the HBM estimate, timed-run/segment "
                         "seconds, classified retries, checkpoint "
                         "saves/resumes")
    ap.add_argument("-iter-stats", action="store_true",
                    dest="iter_stats",
                    help="record device-side per-iteration counters "
                         "inside the fused loop (push: frontier size "
                         "+ edges relaxed; pull: residual + changed "
                         "vertices) and replay them after the run — "
                         "unlike the old stepwise -verbose this "
                         "neither changes the timed path's shape nor "
                         "adds host syncs, and it composes with "
                         "-retries/-seg-budget segment runs")
    ap.add_argument("-flight", default=None, metavar="FILE",
                    help="install the crash flight recorder "
                         "(lux_tpu/tracing.py): a bounded ring of "
                         "recent telemetry events plus the last "
                         "health word and placement metadata, dumped "
                         "atomically to FILE by the resilience "
                         "supervisor on fatal failures and topology "
                         "faults — a run that dies mid-flight "
                         "stays diagnosable after the fact (render: "
                         "scripts/events_summary.py -flight FILE)")
    ap.add_argument("-sources", default=None, metavar="A,B,C",
                    help="comma list of query sources: runs the "
                         "QUERY-BATCHED engine (ROADMAP item 2) — "
                         "k-source SSSP / seeded components / "
                         "personalized (one-hot reset) pagerank — "
                         "with one state column per query, ONE "
                         "gather serving all of them.  Composes "
                         "with -retries/-seg-budget/-iter-stats/"
                         "-health; -pair and sssp -delta are "
                         "single-query machinery and must be off")
    ap.add_argument("-batch", type=int, default=0, metavar="B",
                    help="without -sources: build a B-query batch "
                         "from evenly spaced source vertices; with "
                         "-sources: must match the list length "
                         "(sanity check).  The serving front-end is "
                         "python -m lux_tpu.serve")
    ap.add_argument("-calibrate", action="store_true",
                    help="run the session-calibration probe "
                         "(lux_tpu/observe.py) before the run and "
                         "print/emit the fingerprint — labels this "
                         "process's measured primitive rate vs the "
                         "canonical PERF_NOTES figures, so an "
                         "off-canon session is detected before "
                         "any number is read")


def _load(args, weighted: bool):
    from lux_tpu.format import GraphFormatError
    from lux_tpu.graph import Graph

    import os
    if not os.path.exists(args.file):
        print(f"error: graph file not found: {args.file}", file=sys.stderr)
        raise SystemExit(2)
    t0 = time.perf_counter()
    try:
        g = Graph.from_file(args.file, weighted=weighted or None,
                            weight_dtype=np.dtype(
                                getattr(args, "weight_type", "int32")),
                            validate=getattr(args, "validate", False))
    except GraphFormatError as e:
        # a malformed graph is a typed, named refusal — never a run
        # that silently computes wrong answers through clamping gathers
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(2)
    if args.verbose:
        print(f"loaded nv={g.nv} ne={g.ne} weighted={g.weights is not None}"
              f" ({time.perf_counter() - t0:.2f}s)")
    return g


def _mesh_and_parts(args):
    from lux_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(args.mesh) if args.mesh > 1 else None
    num_parts = args.np or (args.mesh if args.mesh > 1 else 1)
    if mesh is not None and num_parts % args.mesh:
        rounded = args.mesh * ((num_parts + args.mesh - 1) // args.mesh)
        print(f"note: -np {num_parts} rounded up to {rounded} "
              f"(must divide the {args.mesh}-device mesh)")
        num_parts = rounded
    return mesh, num_parts


def _batched_sources(args, nv: int):
    """None, or the resolved query-source list from -sources/-batch
    (ROADMAP item 2 batched engines).  -batch without -sources draws
    B evenly spaced vertices — deterministic, so batched CLI runs
    are reproducible."""
    srcs = getattr(args, "sources", None)
    B = int(getattr(args, "batch", 0) or 0)
    if srcs is None and not B:
        return None
    if getattr(args, "pair", None) is not None:
        print("error: -pair is single-query machinery (pair delivery "
              "reads scalar state); drop it for -sources/-batch runs",
              file=sys.stderr)
        raise SystemExit(2)
    if srcs is not None:
        try:
            out = [int(s) for s in srcs.split(",") if s.strip()]
        except ValueError:
            print(f"error: -sources must be a comma list of vertex "
                  f"ids, got {srcs!r}", file=sys.stderr)
            raise SystemExit(2)
        if not out:
            print("error: -sources named no vertices", file=sys.stderr)
            raise SystemExit(2)
        if B and B != len(out):
            print(f"error: -batch {B} != len(-sources) = {len(out)}",
                  file=sys.stderr)
            raise SystemExit(2)
    else:
        out = [int(x) for x in
               np.linspace(0, nv - 1, B).round().astype(np.int64)]
    for s in out:
        if not 0 <= s < nv:
            print(f"error: source vertex {s} out of range [0, {nv})",
                  file=sys.stderr)
            raise SystemExit(2)
    return out


def _print_batch(sources, ne, iters, elapsed):
    """The batched runs' per-query delivered-rate line (the metric
    bench.py's batch-sweep records as query_gteps)."""
    B = len(sources)
    if iters > 0 and elapsed > 0:
        qg = ne * iters * B / elapsed / 1e9
        print(f"BATCH = {B} queries; QUERY-GTEPS = {qg:.4f} "
              f"({1.0 / qg:.1f} ns/edge/query delivered)")
    else:
        print(f"BATCH = {B} queries")


def _maybe_calibrate(args):
    """-calibrate: run (or reuse) the session probe and print the
    fingerprint header; inside a telemetry scope the ``calibration``
    event lands in the log too (observe.calibrate emits it)."""
    if not getattr(args, "calibrate", False):
        return
    from lux_tpu import observe
    fp = observe.calibrate()
    print(f"# calibration: session {fp.session} {fp.platform}/"
          f"{fp.backend} ndev={fp.ndev} grade={fp.grade} — gather "
          f"{fp.probe['gather_small_ns']:.2f} ns/elem "
          f"({fp.deviation:.2f}x canonical)")
    if fp.grade == "degraded":
        # >3x off the canon in EITHER direction; the canon predates
        # this installation, so faster is not a fault
        how = "slower" if fp.deviation > 1.0 else "FASTER"
        print(f"# NOTE: session graded 'degraded' — the gather probe "
              f"is {how} than the canonical figure by more than "
              f"{observe.DEVIATION_BOUND:g}x; numbers from this "
              f"process are labeled, the canon wants re-measuring")


@contextlib.contextmanager
def _telemetry(args, app):
    """Scope the run's telemetry sinks (lux_tpu/telemetry.py) from
    -events / -iter-stats.  Without either flag this is the null
    handle and every emit stays a no-op; engines keep building their
    counter-free programs."""
    from lux_tpu import telemetry

    if getattr(args, "flight", None):
        from lux_tpu import tracing
        tracing.install_flight_recorder(args.flight)
    if not (args.events or args.iter_stats):
        _maybe_calibrate(args)
        yield telemetry.current()
        return
    ev = telemetry.EventLog(args.events) if args.events else None
    st = telemetry.IterStats() if args.iter_stats else None
    try:
        with telemetry.use(events=ev, iter_stats=st) as tel:
            tel.emit("run_start", schema=telemetry.SCHEMA, app=app,
                     file=args.file, mesh=args.mesh,
                     np=args.np or None)
            _maybe_calibrate(args)
            yield tel
    finally:
        if ev is not None:
            ev.close()


def _finish_run(tel, elapsed, iters):
    """Close out one timed run: emit the ``run_done`` event
    (scripts/events_summary.py checks segment seconds against it) and
    replay the device-side per-iteration counters when -iter-stats
    recorded them — the exact series the old stepwise -verbose path
    printed, now read from the fused run's buffers."""
    tel.emit("run_done", seconds=round(elapsed, 6), iters=iters)
    st = tel.iter_stats
    if st is None or st.kind is None:
        return
    print("# iter-stats (device-side counters, fused run):")
    for line in st.replay_lines():
        print(line)
    # per-part imbalance attribution (round 13): the measured skew
    # signal the locality-aware partitioner will optimize
    for line in st.parts_lines():
        print(f"# {line}")
    # the digest's "kind" (push|pull) would shadow the event kind
    tel.emit("iter_stats", **{("engine" if k == "kind" else k): v
                              for k, v in st.summary().items()})


def _mxu_arg(args):
    """-mxu auto|mxu|vpu -> the engines' use_mxu value."""
    m = getattr(args, "mxu", "auto")
    return {"auto": "auto", "mxu": True, "vpu": False}[m]


def _warn_exchange_ignored(args):
    """colfilter's dot path has its own dst-free delivery; -exchange
    does not apply there."""
    if args.exchange not in ("gather", "auto"):
        print(f"note: -exchange {args.exchange} does not apply to "
              f"colfilter's dot path; ignored")


def _supervisor_opts(args, app):
    """None, or (checkpoint path, supervised-run kwargs) when any of
    -retries / -seg-budget / -resume asks for the resilience
    supervisor (lux_tpu/resilience.py)."""
    if not (args.retries > 0 or args.seg_budget > 0 or args.resume):
        if getattr(args, "elastic", False):
            # never drop a recovery flag silently: without the
            # supervised path there is no checkpoint to re-place from
            print("note: -elastic implies the supervised path; add "
                  "-retries/-seg-budget/-resume or it cannot recover "
                  "anything; ignored")
        return None
    import os
    import tempfile

    from lux_tpu import resilience

    if getattr(args, "profile", None):
        print("note: -profile is ignored on the supervised path "
              "(segments are separate XLA executions)")
    if getattr(args, "verbose", False):
        print("note: -verbose is ignored on the supervised path; "
              "-iter-stats records per-iteration counters across "
              "segments instead")
    # pid-qualified: concurrent runs must not clobber (or worse,
    # cross-resume) each other's in-run recovery checkpoints
    path = args.resume or os.path.join(
        tempfile.gettempdir(),
        f"lux_{app}_supervised.{os.getpid()}.ckpt.npz")
    kw = dict(policy=resilience.RetryPolicy(retries=max(0, args.retries)),
              seg_budget=args.seg_budget or None,
              resume=args.resume is not None)
    return path, kw


def _run_supervised(eng, sup, args, ni=None, make_engine=None):
    """One supervised execution (pull fixed-``ni``, or push converge
    when ni is None), printing the supervisor report and reclaiming
    the implicit (non -resume) recovery checkpoint on BOTH success
    and failure — its pid-qualified name means nothing else ever
    would.  Returns (result, total_iters, elapsed, billed, mark):
    ``billed`` excludes iterations a previous invocation's -resume
    checkpoint already did (in-run retries bill in full — redone
    segments and backoff are this run's cost, resilience.RunReport
    .initial_resume).

    make_engine(mesh) — the app's engine factory — plus -elastic arms
    degraded-mesh recovery: a topology fault rebuilds over the
    survivors and resumes instead of dying."""
    import os

    from lux_tpu import resilience

    path, kw = sup
    if getattr(args, "elastic", False):
        if make_engine is not None and args.mesh > 1:
            kw = dict(kw, elastic=make_engine)
            if kw["policy"].retries < 1:
                # the topology handler only runs with retry budget
                # left (supervise: k < retries) — armed-but-inert
                # must not be silent
                print("note: -elastic needs -retries >= 1 to re-place "
                      "after a topology fault; a fault will be fatal")
        else:
            print("note: -elastic needs -mesh > 1 (a single device "
                  "has no topology to shrink); ignored")
    t0 = time.perf_counter()
    try:
        if ni is not None:
            result, report = resilience.supervised_run(eng, ni, path,
                                                       **kw)
            total = ni
        else:
            label, _active, total, report = \
                resilience.supervised_converge(eng, path, **kw)
            result = eng.unpad(label)
        elapsed = time.perf_counter() - t0
    finally:
        if not args.resume:
            from lux_tpu import checkpoint
            checkpoint.remove(path)     # both generations
    print(f"# supervisor: attempts={report.attempts} "
          f"segments={report.segments} "
          f"resumed_from={report.resumed_from}")
    if report.topology:
        hops = " -> ".join(
            [str(report.topology[0]['from_ndev'])]
            + [str(t['to_ndev']) for t in report.topology])
        print(f"# supervisor: DEGRADED — mesh shrank {hops} devices "
              f"(lost {[t['lost_devices'] for t in report.topology]}); "
              f"results are exact, timings are not comparable to "
              f"full-mesh runs")
    billed = total - (report.initial_resume or 0)
    return (result, total, elapsed, billed,
            " (supervised; incl. checkpoint saves)")


def _relabel_for_pairs(args, g, num_parts):
    """-pair T: relabel so pair-lane delivery finds dense tile pairs
    (degree sort + tile round-robin over parts).  Returns (graph to
    run on, perm|None, starts|None) with perm[new]=old."""
    if getattr(args, "pair", None) is None:
        return g, None, None
    from lux_tpu.graph import pair_relabel
    g2, perm, starts = pair_relabel(g, num_parts,
                                    pair_threshold=args.pair)
    if args.verbose:
        print(f"pair-lane: degree relabel + threshold {args.pair}")
    return g2, perm, starts


def _build_sg(args, g, num_parts, starts=None):
    """Build the padded layout once; print the memory advisor (the
    analogue of the reference's startup requirement estimate,
    reference pagerank.cc:60-85) under -verbose."""
    from lux_tpu.graph import ShardedGraph

    # -gather paged|auto: the paged plan needs 128-aligned vertex
    # padding, like pair delivery (ops/pagegather.py)
    paged = getattr(args, "gather", "flat") != "flat"
    sg = ShardedGraph.build(g, num_parts, starts=starts,
                            pair_threshold=getattr(args, "pair", None),
                            vpad_align=128 if paged else 8)
    from lux_tpu import telemetry
    telemetry.current().emit("header", schema=telemetry.SCHEMA,
                             **sg.telemetry_header())
    if args.verbose:
        rep = sg.memory_report()
        print(f"memory: {rep['total_bytes'] / 1e6:.1f} MB total over "
              f"{num_parts} part(s) "
              f"({rep['edge_bytes_per_part'] / 1e6:.1f} MB edges + "
              f"{rep['vertex_bytes_per_part'] / 1e6:.1f} MB vertices "
              f"per part)")
    return sg


def _timed_build(make_eng, mesh, t_start):
    """Build the engine and say which formulations the build RESOLVED
    to and where it runs — 'auto' reduce picks the Pallas kernel only
    on a TPU backend (engine/delivery.resolve_reduce_method), so this line
    is what tells a chip run from one that quietly took the XLA path.
    On a mesh, also what each device actually HOLDS: a mesh that
    replicated instead of sharding would still compute right answers.
    Returns (engine, (load+layout seconds since ``t_start``, engine
    build seconds)) — the pair _print_setup reports."""
    import jax

    t0 = time.perf_counter()
    eng = make_eng(mesh)
    setup = (t0 - t_start, time.perf_counter() - t0)
    dev = jax.devices()[0]
    print(f"engine: reduce={eng.reduce_method} "
          f"exchange={eng.exchange} gather={eng.gather} "
          f"mxu={eng.use_mxu} parts={eng.sg.num_parts} "
          f"devices={eng.ndev} platform={dev.platform} "
          f"({dev.device_kind})")
    if eng.mesh is not None:
        def held(tree):
            """(total bytes, bytes resident per mesh device)."""
            leaves = jax.tree.leaves(tree)
            per = dict.fromkeys((d.id for d in eng.mesh.devices.flat), 0)
            for x in leaves:
                for sh in x.addressable_shards:
                    per[sh.device.id] += sh.data.nbytes
            return sum(x.nbytes for x in leaves), list(per.values())

        g_total, g_per = held(eng.arrays)
        s_total, s_per = held(eng.init_state())
        print(f"placement: graph {g_total} bytes, per device {g_per}; "
              f"state {s_total} bytes, per device {s_per}")
    return eng, setup


def _print_setup(setup, t_call, elapsed):
    """One line of set-up cost beside the reference-style ELAPSED
    TIME: host graph load + layout build, engine build (``setup``,
    from _timed_build), and compile + warm-up — the wall of the
    timing.timed_* call begun at ``t_call`` minus its timed run (the
    helpers warm the SAME program once before timing it)."""
    layout_s, build_s = setup
    warm_s = time.perf_counter() - t_call - elapsed
    print(f"SETUP TIME = load+layout {layout_s:.2f} s, engine build "
          f"{build_s:.2f} s, compile+warm {warm_s:.2f} s")


def cmd_pagerank(argv):
    ap = argparse.ArgumentParser(prog="lux_tpu pagerank")
    _common(ap)
    ap.add_argument("-ni", type=int, default=10)
    ap.add_argument("-tol", type=float, default=None,
                    help="run to convergence (max-abs change of the "
                         "degree-scaled rank state <= tol) instead of "
                         "a fixed -ni count")
    ap.add_argument("-max-iters", type=int, default=10000,
                    dest="max_iters",
                    help="iteration cap for -tol runs (default 10000)")
    args = ap.parse_args(argv)

    from lux_tpu.apps import pagerank

    with _telemetry(args, "pagerank") as tel:
        t_start = time.perf_counter()
        g = _load(args, weighted=False)
        mesh, num_parts = _mesh_and_parts(args)
        sources = _batched_sources(args, g.nv)
        g_run, perm, starts = _relabel_for_pairs(args, g, num_parts)
        sg = _build_sg(args, g_run, num_parts, starts)
        def make_eng(m):
            # the -elastic factory: same graph/config, new mesh —
            # engines compile per-mesh automatically (arrays are jit
            # arguments), and the rebuilt engine re-audits under the
            # same -audit mode at the new device count.  -sources
            # builds the personalized (one-hot reset) batched engine
            # (ROADMAP item 2).
            return pagerank.build_engine(g_run, num_parts, m, sg=sg,
                                         pair_threshold=args.pair,
                                         pair_min_fill=args.min_fill,
                                         exchange=args.exchange,
                                         gather=args.gather,
                                         use_mxu=_mxu_arg(args),
                                         health=args.health,
                                         sources=sources,
                                         audit=args.audit)

        eng, setup = _timed_build(make_eng, mesh, t_start)
        if args.tol is not None:
            if args.retries > 0 or args.seg_budget > 0 or args.resume:
                print("note: -tol runs one monolithic convergence "
                      "program; -retries/-seg-budget/-resume apply to "
                      "fixed -ni runs only and are ignored here")
            from lux_tpu.timing import timed_run_until
            t_call = time.perf_counter()
            state, iters, res, elapsed = timed_run_until(
                eng, args.tol, args.max_iters, trace_dir=args.profile)
            _print_setup(setup, t_call, elapsed)
            print(f"ELAPSED TIME = {elapsed:.7f} s ({iters} iterations, "
                  f"residual {res:.3e})")
            print(f"GTEPS = {g.ne * iters / elapsed / 1e9:.4f}")
            if sources is not None:
                _print_batch(sources, g.ne, iters, elapsed)
            _finish_run(tel, elapsed, iters)
        else:
            sup = _supervisor_opts(args, "pagerank")
            if sup is not None:
                state, total, elapsed, ni, mark = _run_supervised(
                    eng, sup, args, ni=args.ni, make_engine=make_eng)
            else:
                t_call = time.perf_counter()
                state, [elapsed] = timed_fused_run(
                    eng, args.ni, trace_dir=args.profile)
                _print_setup(setup, t_call, elapsed)
                total = ni = args.ni
                mark = ""
            print(f"ELAPSED TIME = {elapsed:.7f} s")
            if ni > 0:
                print(f"GTEPS = {g.ne * ni / elapsed / 1e9:.4f}{mark}")
            else:
                print("GTEPS = n/a (run already complete in checkpoint)")
            if sources is not None:
                _print_batch(sources, g.ne, ni, elapsed)
            _finish_run(tel, elapsed, total)

        if sources is not None and args.check:
            # per-column device_check rides the batch-sweep debt
            print("note: -check does not support batched runs yet; "
                  "skipped (oracle proofs: tests/test_batched.py)")
            return 0
        if args.check:
            # On-device sharded audit over the resident edge arrays
            # (the reference's per-part GPU check tasks,
            # sssp_gpu.cu:800-843); runs at any scale, no host
            # edge-list rebuild.  NOTE: audits the FULL sg built
            # above, not eng.sg (pair-lane engines keep only the
            # residual edges there).  The residual is
            # permutation-invariant, so no -pair un-relabel is needed.
            from lux_tpu.device_check import check_pagerank_device
            res = check_pagerank_device(sg, state, tol=1e-3,
                                        mesh=eng.mesh)
            print(res)
            return 0 if res.ok else 1
    return 0


def _push_app(argv, prog_name):
    ap = argparse.ArgumentParser(prog=f"lux_tpu {prog_name}")
    _common(ap)
    ap.add_argument("-start", type=int, default=0)
    ap.add_argument("-weighted", action="store_true")
    if prog_name == "sssp":
        ap.add_argument("-weight-type", dest="weight_type",
                        choices=("int32", "float32"), default="int32",
                        help="what the file's 4-byte weights are (a "
                             ".lux does not say): int32 (default) or "
                             "float32, e.g. Graph500 kernel 3's "
                             "uniform [0, 1) weights")
        ap.add_argument("-delta", default=None,
                        help="delta-stepping bucket width (a number or "
                             "'auto'; default: off)")
    args = ap.parse_args(argv)

    from lux_tpu.apps import components, sssp

    weighted = prog_name == "sssp" and args.weighted
    with _telemetry(args, prog_name) as tel:
        t_start = time.perf_counter()
        g = _load(args, weighted=weighted)
        mesh, num_parts = _mesh_and_parts(args)
        sources = _batched_sources(args, g.nv)
        g_run, perm, starts = _relabel_for_pairs(args, g, num_parts)
        sg = _build_sg(args, g_run, num_parts, starts)
        start = args.start if prog_name == "sssp" else None
        if perm is not None and start is not None:
            rank = np.empty(g.nv, np.int64)
            rank[perm] = np.arange(g.nv)
            start = int(rank[start])
        if prog_name == "sssp":
            delta = args.delta
            if delta is not None and delta != "auto":
                delta = float(delta)
            if sources is not None and delta is not None:
                print("error: -delta is single-query machinery; drop "
                      "it for -sources/-batch runs", file=sys.stderr)
                return 2

            def make_eng(m):
                return sssp.build_engine(
                    g_run, start_vertex=start, num_parts=num_parts,
                    mesh=m, weighted=weighted, delta=delta, sg=sg,
                    pair_threshold=args.pair,
                    pair_min_fill=args.min_fill,
                    exchange=args.exchange,
                    gather=args.gather,
                    enable_sparse=bool(args.sparse),
                    use_mxu=_mxu_arg(args),
                    sources=sources,
                    health=args.health, audit=args.audit)
        else:
            def make_eng(m):
                return components.build_engine(
                    g_run, num_parts=num_parts, mesh=m, sg=sg,
                    pair_threshold=args.pair,
                    pair_min_fill=args.min_fill,
                    exchange=args.exchange,
                    gather=args.gather,
                    enable_sparse=bool(args.sparse),
                    use_mxu=_mxu_arg(args),
                    sources=sources,
                    health=args.health, audit=args.audit)
        eng, setup = _timed_build(make_eng, mesh, t_start)
        sup = _supervisor_opts(args, prog_name)
        if sup is not None:
            labels, iters, elapsed, it_exec, mark = _run_supervised(
                eng, sup, args, make_engine=make_eng)
        else:
            t_call = time.perf_counter()
            labels, iters, [elapsed] = timed_converge(
                eng, verbose=args.verbose, trace_dir=args.profile)
            _print_setup(setup, t_call, elapsed)
            it_exec, mark = iters, ""
        print(f"ELAPSED TIME = {elapsed:.7f} s ({iters} iterations)")
        if it_exec > 0:
            print(f"GTEPS = {g.ne * it_exec / elapsed / 1e9:.4f}{mark}")
        else:
            print("GTEPS = n/a (run already complete in checkpoint)")
        if weighted:
            # int32 distances of integer weights: a sum past the type
            # is refused by name, never handed out
            sssp.ensure_in_range(np.asarray(labels))
        if sources is not None:
            _print_batch(sources, g.ne, it_exec, elapsed)
        _finish_run(tel, elapsed, iters)

        if sources is not None and args.check:
            # per-column device_check needs the batched fixed-point
            # audits; the oracle proofs live in tests/test_batched.py
            print("note: -check does not support batched runs yet; "
                  "skipped")
            return 0
        if args.check:
            # On-device per-part audits (reference sssp_gpu.cu:800-843,
            # components_gpu.cu:788); labels are in g_run order, which
            # is exactly sg's order — the fixed-point properties are
            # permutation-invariant, so no -pair un-relabel is needed.
            from lux_tpu import device_check
            if prog_name == "sssp":
                res = device_check.check_sssp_device(
                    sg, labels, weighted=weighted, mesh=eng.mesh)
            else:
                res = device_check.check_components_device(
                    sg, labels, mesh=eng.mesh)
            print(res)
            return 0 if res.ok else 1
    return 0


def cmd_sssp(argv):
    return _push_app(argv, "sssp")


def cmd_components(argv):
    return _push_app(argv, "components")


def cmd_colfilter(argv):
    ap = argparse.ArgumentParser(prog="lux_tpu colfilter")
    _common(ap)
    ap.add_argument("-ni", type=int, default=10)
    args = ap.parse_args(argv)

    from lux_tpu.apps import colfilter

    _warn_exchange_ignored(args)
    if getattr(args, "sources", None) or getattr(args, "batch", 0):
        print("note: colfilter trains one shared factorization; "
              "-sources/-batch apply to sssp/components/pagerank "
              "(per-user top-N serving is future work); ignored")
    with _telemetry(args, "colfilter") as tel:
        t_start = time.perf_counter()
        g = _load(args, weighted=True)
        mesh, num_parts = _mesh_and_parts(args)
        g_run, _perm, starts = _relabel_for_pairs(args, g, num_parts)
        sg = _build_sg(args, g_run, num_parts, starts)
        def make_eng(m):
            return colfilter.build_engine(g_run, num_parts, m, sg=sg,
                                          pair_threshold=args.pair,
                                          pair_min_fill=args.min_fill,
                                          gather=args.gather,
                                          use_mxu=_mxu_arg(args),
                                          health=args.health,
                                          audit=args.audit)

        eng, setup = _timed_build(make_eng, mesh, t_start)
        sup = _supervisor_opts(args, "colfilter")
        if sup is not None:
            state, total, elapsed, ni, mark = _run_supervised(
                eng, sup, args, ni=args.ni, make_engine=make_eng)
        else:
            t_call = time.perf_counter()
            state, [elapsed] = timed_fused_run(eng, args.ni,
                                               trace_dir=args.profile)
            _print_setup(setup, t_call, elapsed)
            total = ni = args.ni
            mark = ""
        print(f"ELAPSED TIME = {elapsed:.7f} s")
        if ni > 0:
            print(f"GTEPS = {g.ne * ni / elapsed / 1e9:.4f}{mark}")
        else:
            print("GTEPS = n/a (run already complete in checkpoint)")
        _finish_run(tel, elapsed, total)
        out = eng.unpad(state)
        # out is in the run graph's (possibly relabeled) vertex order;
        # rmse is computed over edges, so the relabeled graph is the
        # matching — and equivalent — choice
        print(f"RMSE = {colfilter.rmse(g_run, out):.6f}")
        if args.check:
            from lux_tpu.device_check import check_colfilter_device
            res = check_colfilter_device(sg, out, mesh=eng.mesh)
            print(res)
            return 0 if res.ok else 1
    return 0


def cmd_convert(argv):
    ap = argparse.ArgumentParser(prog="lux_tpu convert")
    ap.add_argument("-input", required=True, help="text edge list")
    ap.add_argument("-output", required=True, help=".lux output")
    ap.add_argument("-nv", type=int, required=True)
    ap.add_argument("-weighted", action="store_true")
    args = ap.parse_args(argv)

    from lux_tpu.convert import convert_edge_list
    convert_edge_list(args.input, args.output, args.nv,
                      weighted=args.weighted)
    return 0


_APPS = {
    "pagerank": cmd_pagerank,
    "sssp": cmd_sssp,
    "components": cmd_components,
    "colfilter": cmd_colfilter,
    "convert": cmd_convert,
}


def main(argv=None) -> int:
    from lux_tpu import runtime
    runtime.use_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m lux_tpu.cli "
              f"{{{','.join(_APPS)}}} [flags]\n"
              "run 'python -m lux_tpu.cli <app> -h' for app flags")
        return 0 if argv else 2
    app = argv[0]
    if app not in _APPS:
        print(f"unknown app {app!r}; choose from {list(_APPS)}",
              file=sys.stderr)
        return 2
    try:
        return _APPS[app](argv[1:])
    except Exception as e:
        from lux_tpu.apps.sssp import DistanceRangeError, WeightRangeError
        from lux_tpu.audit import AuditError
        if isinstance(e, (AuditError, WeightRangeError,
                          DistanceRangeError)):
            # -audit error: a violating build is a typed, named
            # refusal (like GraphFormatError), never a run whose
            # numbers silently embed the violation; so are integer
            # weights or distances past what int32 distances hold
            print(f"error: {e}", file=sys.stderr)
            return 2
        raise


if __name__ == "__main__":
    sys.exit(main())
