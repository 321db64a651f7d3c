#!/usr/bin/env python
"""Render a telemetry event-log JSONL into the reference-style table.

The reference's -verbose run prints a per-iteration
loadTime/compTime/updateTime breakdown and little else (reference
sssp_gpu.cu:513-518, pagerank.cc:108-118).  ``-events FILE`` runs
(lux_tpu/cli.py, bench.py) leave a structured JSONL instead
(lux_tpu/telemetry.py); this script renders one back into that
human shape — and audits it while doing so:

- unparseable lines or events without a ``kind`` FAIL the render, as
  do timed events (timed_run/segment/run_done) missing their
  ``seconds``
- per run: segment seconds must not sum PAST the ``run_done``
  elapsed (20% + 50 ms slack) — overshoot means segments overlap or
  double-count, i.e. the fenced slice timings are lying.  Summing
  UNDER the elapsed is expected: the elapsed legitimately includes
  checkpoint saves and host driver time between slices.
- round 9: ``health_trip`` events (the device-side watchdog,
  lux_tpu/health.py) must carry flags/iteration/part/engine — an
  undiagnosable trip fails the audit; ``health`` digests and
  ``checkpoint_fallback`` generation-fallback events are rendered.
- round 11 (elastic recovery, lux_tpu/resilience.py): a
  ``topology_fault`` without its error FAILS, as does a
  ``mesh_shrink`` that does not record a shrinking from/to device
  (or heartbeat-protocol process) count, and a ``replace`` without
  its from/to mesh — a degraded continuation must be fully diagnosed
  in its event trail.  ``budget_reset`` and ``straggler`` render.
- round 12 (observatory, lux_tpu/observe.py): every event now carries
  a monotonic ``tm`` plus ``pid``/``session`` fields, so
  multi-process logs (heartbeat drills append several processes into
  ONE file) merge unambiguously: events are grouped per
  (session, pid) stream before run-splitting, each stream renders
  under its own header, and a stream whose ``tm`` goes BACKWARDS
  fails the audit (two processes' events conflated under one pid
  means the merge key is lying).  ``calibration`` fingerprints and
  ``drift``/``phase_cost`` attribution events render.

- round 17 (serving observability, lux_tpu/metrics.py + serve.py):
  ``metrics_snapshot`` events render the per-kind latency table
  (count / p50 / p99 from the snapshot's log-linear histograms),
  queue depths and the SLO burn record — and are CROSS-AUDITED
  against the raw ``query_done`` stream: a snapshot whose
  ``serve_latency_seconds`` histogram claims MORE retired queries of
  a kind than ``query_done`` events exist in the run FAILS (the
  established contradiction-check pattern), as does a histogram
  whose ``count`` disagrees with the sum of its own bucket cells or
  whose p99 lies under its p50.  ``log_rotate`` markers render, and
  every FILE argument is expanded to its rotated ``.2/.1/live``
  generation set (telemetry.EventLog(rotate_bytes=...)) and
  consumed, oldest first, as ONE stream.

- round 13 (tracing & imbalance attribution, lux_tpu/tracing.py):
  ``iter_stats`` digests carrying per-part counters render a
  per-part table with the imbalance index, and the AUDIT checks that
  the per-part totals SUM to the scalar counter (bitwise — the
  engines reduce the same device-side values part-first) and that
  the index equals max/mean of its own parts; ``heartbeat`` boundary
  syncs and ``flight_dump`` records render; ``-flight FILE`` renders
  a crash-flight-recorder FLIGHT.json postmortem instead of an event
  log.

- round 19 (communication observatory, lux_tpu/comms.py):
  ``comm_ledger`` events render the per-collective byte table
  (prim / launches / payload / wire bytes, branch-tagged for the
  sparse-dense alternatives) and are AUDITED against the
  collective-schedule eqn set they carry: a breakdown whose per-prim
  eqn counts disagree with ``audit_eqns`` FAILS — the ledger and the
  auditor walk the same program registry, so a mismatch means the
  trail lies about the program.  ``link_calibration`` events (the
  measured ICI/DCN bytes/s probes, observe.calibrate_links) render
  with their fed-scalemodel flag.

- round 20 (live graphs, lux_tpu/livegraph.py): the mutation /
  epoch / compaction / cache trail renders (mutation batches, epoch
  advances, peak delta occupancy, compaction fold counts, WAL
  truncate/replay records, epoch-keyed cache hits) and is AUDITED
  for the snapshot-isolation contract: a ``query_done`` whose
  ``answer_epoch`` differs from its admission ``epoch`` is a
  TORN-EPOCH answer and FAILS (as does an epoch-carrying answer
  with no answer_epoch at all); a ``compact_done`` whose generation
  has no preceding ``compact_start`` breaks the WAL compaction
  bracket and FAILS; a ``wal_replay`` that recovers a LOWER epoch
  than the trail already published is a replay-after-crash epoch
  regression (acknowledged mutations vanished) and FAILS — checked
  both in-stream (render_run's ordered walk) and CROSS-process
  (audit_wal_replays pairs wal-carrying publishes with replays on
  the log path across (session, pid) streams, wall-clock ordered:
  the crashing publisher and the recovering process are never the
  same pid).

- round 24 (self-healing fleet, lux_tpu/fleet.py + journal.py): the
  respawn / quarantine / canary trail renders, as do the admission-
  journal truncate/replay records, and the ORDERED audits hold: a
  ``replica_respawn`` without a preceding ``replica_lost`` of that
  name FAILS (a resurrection of a replica that never died), as does
  one without a PASSING ``canary`` since the loss (a replica whose
  oracle probe failed — or never ran — re-entered routing), a
  malformed ``canary``/``replica_quarantine`` record, and a
  recovered re-dispatch (``query_enqueue`` with ``recovered``) with
  no preceding ``journal_replay`` naming the journal it came from.

- PR 24 (one span primitive, lux_tpu/telemetry.py ``span``/``mark``):
  ``span`` events render as host seconds and summed counts (bytes,
  retired, pair_edges, ...) by span name; one whose
  ``t1`` precedes its ``t0`` (or without a name) FAILS.

Usage:
    python scripts/events_summary.py FILE [FILE...]
    python scripts/events_summary.py -flight FLIGHT.json

Exit status: 0 clean, 1 any error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

KNOWN = {"run_start", "config_start", "header", "timed_run",
         "segment", "run_done", "iter_stats", "phases",
         "checkpoint_save", "checkpoint_resume", "checkpoint_fallback",
         "retry", "failure", "budget_lock", "budget_halve",
         "budget_reset", "outlier_discard", "outlier_rerun", "health",
         "health_trip", "topology_fault", "mesh_shrink", "replace",
         "straggler", "calibration", "phase_cost", "drift",
         "debt_collected", "heartbeat", "flight_dump",
         "query_enqueue", "query_start", "query_done", "serve_refill",
         "metrics_snapshot", "log_rotate",
         "replica_up", "replica_lost", "failover", "query_shed",
         "brownout", "comm_ledger", "link_calibration",
         "mutation", "epoch_advance", "compact_start", "compact_done",
         "wal_truncate", "wal_replay", "reseed", "compact_scheduled",
         "mem_sample", "mem_watermark", "mem_pressure",
         "replica_respawn", "replica_quarantine", "canary",
         "journal_truncate", "journal_replay", "span"}

# round 19 (communication observatory, lux_tpu/comms.py): the
# collective primitives a comm_ledger breakdown may name — matching
# comms.COLLECTIVE_PRIMS with psum_scatter normalized away
COMM_PRIMS = {"ppermute", "all_to_all", "reduce_scatter",
              "all_gather", "psum", "pmin", "pmax"}

# a query_shed without these cannot be diagnosed — the serving
# fleet's typed-rejection contract (lux_tpu/fleet.py)
QUERY_SHED_REQUIRED = ("qid", "query_kind", "reason")

# round 21 (mutation algebra, lux_tpu/livegraph.py
# CompactionScheduler): a scheduler compaction must carry the
# economics that justified it, or the decision cannot be audited
COMPACT_SCHEDULED_REQUIRED = ("occupancy", "threshold", "delta_count",
                              "drag_ns", "drag_source", "reason")

# round 22 (memory observatory, lux_tpu/memwatch.py): a mem_pressure
# without these cannot justify the forecast it claims — the
# burn-rate/time-to-full decision contract
MEM_PRESSURE_REQUIRED = ("reason", "live_bytes", "budget_bytes",
                         "burn")

# a failover without these cannot name the transition it claims
FAILOVER_REQUIRED = ("qid", "from_replica", "to_replica")

# a query_done without these cannot account for the query's cost —
# the serving front-end's per-query latency contract (lux_tpu/serve.py)
QUERY_DONE_REQUIRED = ("qid", "query_kind", "iters", "segments",
                       "latency_s")

# a health_trip without these fields cannot be diagnosed — the whole
# point of the watchdog is a NAMED check at a NAMED iteration
HEALTH_TRIP_REQUIRED = ("flags", "iteration", "part", "engine")


def _shrink_pair(ev):
    """(from, to) of a mesh_shrink/replace event — device counts for
    the in-process elastic path, process counts for the heartbeat
    shrink protocol.  None when neither pair is present/numeric."""
    for a, b in (("from_ndev", "to_ndev"), ("from_nproc", "to_nproc")):
        f, t = ev.get(a), ev.get(b)
        if (isinstance(f, int) and not isinstance(f, bool)
                and isinstance(t, int) and not isinstance(t, bool)):
            return f, t
    return None


def rotated_set(path: str) -> list[str]:
    """[path.N, ..., path.1, path] — the oldest-first generation set
    a size-rotated EventLog leaves behind (mirrors
    lux_tpu.telemetry.rotated_paths; re-implemented so this script
    stays stdlib-only)."""
    n = 1
    while os.path.exists(f"{path}.{n}"):
        n += 1
    return [f"{path}.{g}" for g in range(n - 1, 0, -1)] + [path]


def load_events(path: str):
    """Parse one JSONL file.  Returns (events, errors)."""
    events, errs = [], []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError as e:
                errs.append(f"line {i}: unparseable JSON ({e})")
                continue
            if not isinstance(ev, dict) or "kind" not in ev:
                errs.append(f"line {i}: event without a 'kind'")
                continue
            events.append(ev)
    if not events and not errs:
        errs.append("no events found")
    return events, errs


def split_streams(events):
    """Partition a flat (possibly multi-process) event list into
    per-process streams keyed by (session, pid) — the round-12 merge
    key that makes several processes appending into ONE file
    unambiguous.  Events predating the fields (or hand-written logs)
    share the legacy ``None`` stream.  Returns ([(key, events)],
    errors) in first-appearance order; a stream whose monotonic
    ``tm`` DECREASES is an error — one (session, pid) key can only
    belong to one process, whose monotonic clock never goes back."""
    streams, order, errs = {}, [], []
    for ev in events:
        key = None
        if "session" in ev or "pid" in ev:
            key = (ev.get("session"), ev.get("pid"))
        if key not in streams:
            streams[key] = []
            order.append(key)
        streams[key].append(ev)
    for key in order:
        last = None
        for ev in streams[key]:
            tm = ev.get("tm")
            if not isinstance(tm, (int, float)) \
                    or isinstance(tm, bool):
                continue
            if last is not None and tm < last:
                errs.append(
                    f"stream {key}: monotonic tm went backwards "
                    f"({last} -> {tm}) — two processes' events "
                    f"conflated under one (session, pid) key")
            last = tm
    return [(k, streams[k]) for k in order], errs


def split_runs(events):
    """Group one stream into runs at run_start/config_start
    boundaries (one CLI invocation / bench config each); a log
    without boundary events is one anonymous run."""
    runs, cur = [], []
    for ev in events:
        if ev["kind"] in ("run_start", "config_start") and cur:
            runs.append(cur)
            cur = []
        cur.append(ev)
    if cur:
        runs.append(cur)
    return runs


def _fmt_s(x: float) -> str:
    return f"{x:9.3f} s"


def _is_int(x) -> bool:
    return isinstance(x, int) and not isinstance(x, bool)


def _is_num(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and x == x and abs(x) != float("inf")


def render_parts_table(title, st, out) -> list[str]:
    """Round-13 per-part attribution table of one ``iter_stats``
    digest — and its audit: the per-part totals must SUM to the
    scalar counter bitwise (the engines reduce the very same
    device-side values part-first; a mismatch means the imbalance
    signal is lying about the series it claims to decompose)."""
    errs = []
    metric = "edges" if "parts_edges" in st else \
        "changed" if "parts_changed" in st else None
    if metric is None:
        return errs
    parts = st.get(f"parts_{metric}")
    if (not isinstance(parts, list) or not parts
            or not all(_is_int(p) and p >= 0 for p in parts)):
        errs.append(f"{title}: parts_{metric} must be a non-empty "
                    f"list of ints >= 0, got {parts!r}"[:200])
        return errs
    scalar = st.get(f"{metric}_sum")
    # congruence mod 2^32, not plain equality: each scalar series
    # entry is a device-side uint32 (sum of its per-part row, which
    # wraps past 2^32 edges/iteration) while the host part totals
    # sum exactly — Σ(wrapped) ≡ Σ(exact) (mod 2^32) always holds
    if _is_int(scalar) and (sum(parts) - scalar) % (1 << 32):
        errs.append(
            f"{title}: per-part {metric} sum {sum(parts)} != scalar "
            f"{metric}_sum {scalar} (mod 2^32) — the imbalance "
            f"table contradicts the counters it decomposes")
    imb = st.get("imbalance")
    if imb is not None and (not isinstance(imb, (int, float))
                            or isinstance(imb, bool)):
        errs.append(f"{title}: non-numeric imbalance {imb!r}")
        imb = None
    tot = sum(parts) or 1
    print(f"  per-part {metric} (P={len(parts)}, imbalance "
          f"{imb if imb is not None else 'n/a'} max/mean):", file=out)
    for p, v in enumerate(parts):
        print(f"    part {p}: {v:>12d} ({v / tot * 100:5.1f}%)",
              file=out)
    if imb is not None:
        mean = sum(parts) / len(parts)
        want = max(parts) / mean if mean else None
        if want is not None and abs(imb - want) > 1e-3 * max(1, want):
            errs.append(
                f"{title}: imbalance {imb} contradicts its own "
                f"per-part totals (max/mean = {want:.4f})")
    return errs


def render_metrics_snapshot(title, snap, qdone_by_kind, out,
                            render: bool = True,
                            truncated: bool = False) -> list[str]:
    """Round-17 serving snapshot (lux_tpu/metrics.py): render the
    per-kind latency table, queue depths and SLO burn — and audit it
    against the raw query_done stream: a snapshot claiming MORE
    retired queries of a kind than query_done events exist is lying
    about the stream it aggregates (the contradiction-check
    pattern), as is a histogram whose count disagrees with its own
    bucket cells or whose p99 undercuts its p50.  ``truncated``
    disarms the overcount check ONLY: when rotation dropped
    generations (more rotations than kept generations), the raw
    stream is known-incomplete and a cumulative registry count
    legitimately exceeds the surviving query_done events."""
    errs = []
    step = f" (step {snap['step']})" if "step" in snap else ""
    hists = snap.get("histograms")
    gauges = snap.get("gauges") or []
    counters = snap.get("counters") or []
    if not isinstance(hists, list):
        return [f"{title}: metrics_snapshot without a histograms "
                f"list: {snap!r}"[:200]]
    lat = [h for h in hists
           if h.get("name") == "serve_latency_seconds"]
    if lat and render:
        print(f"  metrics snapshot{step} — per-kind latency:",
              file=out)
    for h in lat:
        kind = (h.get("labels") or {}).get("kind", "?")
        count, buckets = h.get("count"), h.get("buckets")
        if not _is_int(count) or count < 0:
            errs.append(f"{title}: snapshot latency histogram "
                        f"[{kind}] non-int count {count!r}")
            continue
        if isinstance(buckets, dict):
            cells = sum(int(v) for v in buckets.values())
            if cells != count:
                errs.append(
                    f"{title}: snapshot latency histogram [{kind}] "
                    f"count {count} != sum of its bucket cells "
                    f"{cells} — the histogram contradicts itself")
        seen = qdone_by_kind.get(kind, 0)
        if count > seen and not truncated:
            errs.append(
                f"{title}: metrics snapshot claims {count} retired "
                f"{kind!r} queries but only {seen} query_done "
                f"event(s) exist — the snapshot contradicts the raw "
                f"per-query stream")
        p50, p99 = h.get("p50"), h.get("p99")
        if _is_num(p50) and _is_num(p99) and p99 < p50:
            errs.append(f"{title}: snapshot latency histogram "
                        f"[{kind}] p99 {p99} < p50 {p50}")
        if render:
            p50s = "-" if not _is_num(p50) else f"{p50 * 1e3:8.1f}ms"
            p99s = "-" if not _is_num(p99) else f"{p99 * 1e3:8.1f}ms"
            print(f"    {kind:12s} count {count:>5d}  "
                  f"p50 {p50s:>10s}  p99 {p99s:>10s}", file=out)
    def _gval(g, what):
        """Numeric gauge/counter value or an audit error (a
        malformed trail must FAIL the render, never crash it)."""
        v = g.get("value")
        if _is_num(v):
            return v
        errs.append(f"{title}: snapshot {what} "
                    f"[{(g.get('labels') or {}).get('kind', '?')}] "
                    f"non-numeric value {v!r}")
        return None

    depths = [g for g in gauges
              if g.get("name") == "serve_queue_depth"]
    dvals = [(g, _gval(g, "queue-depth gauge")) for g in depths]
    if depths and render:
        cells = "  ".join(
            f"{(g.get('labels') or {}).get('kind', '?')}="
            f"{'?' if v is None else f'{v:g}'}" for g, v in dvals)
        print(f"    queue depth: {cells}", file=out)
    burn = [g for g in gauges
            if g.get("name") == "serve_slo_burn_rate"]
    slo_counts = {}
    for c in counters:
        if c.get("name") in ("serve_slo_good_total",
                             "serve_slo_violation_total"):
            kind = (c.get("labels") or {}).get("kind", "?")
            key = "good" if c["name"].endswith("good_total") \
                else "bad"
            slo_counts.setdefault(kind, {})[key] = c.get("value")
    bvals = [(g, _gval(g, "burn-rate gauge")) for g in burn]
    if (burn or slo_counts) and render:
        def num(v):
            return f"{v:g}" if _is_num(v) else "?"

        cells = []
        for g, v in bvals:
            kind = (g.get("labels") or {}).get("kind", "?")
            gb = slo_counts.get(kind, {})
            cells.append(f"{kind}: burn {num(v)} "
                         f"(good {num(gb.get('good', 0))} / viol "
                         f"{num(gb.get('bad', 0))})")
        print(f"    SLO burn: {'; '.join(cells)}", file=out)
    return errs


def render_comm_ledger(title, cl, out) -> list[str]:
    """Round-19 comm-ledger event (lux_tpu/comms.py via
    observe.decompose / python -m lux_tpu.comms -events): render the
    per-collective table and AUDIT it — the breakdown's per-prim eqn
    counts must match the ``audit_eqns`` set the collective-schedule
    auditor sees on the same program (the two subsystems walk one
    registry, so a published mismatch means the trail is lying about
    the program), shipped bytes must be non-negative ints, and prims
    must be known collectives."""
    errs = []
    where = f"{title}/{cl.get('app', cl.get('config', '?'))}"
    pcs = cl.get("per_collective")
    audit_eqns = cl.get("audit_eqns")
    if not isinstance(pcs, list) or not isinstance(audit_eqns, dict):
        return [f"{where}: comm_ledger without its per_collective "
                f"list + audit_eqns dict: {cl!r}"[:200]]
    seen: dict = {}
    for g in pcs:
        if not isinstance(g, dict):
            errs.append(f"{where}: malformed comm_ledger group "
                        f"{g!r}"[:160])
            continue
        prim = g.get("prim")
        if prim not in COMM_PRIMS:
            errs.append(f"{where}: comm_ledger names unknown "
                        f"collective {prim!r}")
            continue
        ec = g.get("eqns")
        sb = g.get("shipped_bytes")
        if not _is_int(ec) or ec < 1:
            errs.append(f"{where}: comm_ledger [{prim}] eqns={ec!r} "
                        f"must be an int >= 1")
            continue
        if not _is_int(sb) or sb < 0:
            errs.append(f"{where}: comm_ledger [{prim}] "
                        f"shipped_bytes={sb!r} must be an int >= 0")
        seen[prim] = seen.get(prim, 0) + ec
    want = {k: v for k, v in audit_eqns.items() if _is_int(v) and v}
    if seen != want:
        errs.append(
            f"{where}: comm_ledger breakdown counts {seen} contradict "
            f"the audit collective-schedule eqn set {want} — ledger "
            f"and auditor walk ONE registry, so the published trail "
            f"is lying about the program")
    bpi = cl.get("bytes_per_iter")
    print(f"  comm ledger [{cl.get('app', cl.get('config', '?'))}]: "
          f"{bpi} B/iter over {cl.get('messages')} collective(s) "
          f"[{cl.get('tier')}] verdict={cl.get('verdict', '-')}",
          file=out)
    for g in pcs:
        if isinstance(g, dict) and g.get("prim") in COMM_PRIMS:
            br = f" ({g['branch']})" if g.get("branch") else ""
            print(f"    {g['prim']:14s}{br} x{g.get('count')}  "
                  f"payload {g.get('payload_bytes')} B  wire "
                  f"{g.get('shipped_bytes')} B", file=out)
    return errs


def render_run(run, out=sys.stdout) -> list[str]:
    """Print one run's table; returns audit errors."""
    errs = []
    by = {}
    for ev in run:
        by.setdefault(ev["kind"], []).append(ev)

    def seconds_of(kind):
        """[seconds] of every ``kind`` event; missing/non-numeric
        seconds become audit errors instead of a crash."""
        vals = []
        for ev in by.get(kind, []):
            s = ev.get("seconds")
            if isinstance(s, (int, float)) and not isinstance(s, bool):
                vals.append(s)
            else:
                errs.append(f"{kind} event without numeric "
                            f"'seconds': {ev!r}"[:160])
        return vals

    head = (by.get("run_start") or by.get("config_start") or [{}])[0]
    title = head.get("app") or head.get("config") or "run"
    print(f"== {title} ==", file=out)
    for h in by.get("header", []):
        mem = h.get("memory", {})
        per_part = mem.get("edge_bytes_per_part", 0) \
            + mem.get("vertex_bytes_per_part", 0)
        print(f"  graph: nv={h.get('nv')} ne={h.get('ne')} "
              f"parts={h.get('num_parts')} "
              f"(~{per_part / 1e6:.1f} MB/part HBM, "
              f"{mem.get('total_bytes', 0) / 1e6:.1f} MB total)",
              file=out)

    # the reference's per-iteration loadTime/compTime/updateTime
    # table, from the CLI's -phases instrumented iterations
    META = ("frontier", "bucket", "advances")   # counters, not times
    for ph in by.get("phases", []):
        print("  per-iteration phases (reference loadTime/compTime/"
              "updateTime analogue):", file=out)
        for i, t in enumerate(ph.get("report", [])):
            cells = "  ".join(
                (f"{k}={v:g}" if k in META
                 else f"{k}={v * 1e3:8.2f}ms") for k, v in t.items()
                if isinstance(v, (int, float)))
            print(f"    iter {i}: {cells}", file=out)

    for st in by.get("iter_stats", []):
        eng = st.get("engine")
        # a zero-iteration digest carries only kind/iters/truncated
        if eng == "push" and "frontier_max" in st:
            print(f"  counters (push): {st.get('iters')} iters, "
                  f"frontier max {st.get('frontier_max')} "
                  f"sum {st.get('frontier_sum')}, "
                  f"edges relaxed {st.get('edges_sum')}", file=out)
        elif eng == "pull" and "residual_first" in st:
            print(f"  counters (pull): {st.get('iters')} iters, "
                  f"residual {st['residual_first']:.3e} -> "
                  f"{st['residual_last']:.3e}, "
                  f"changed_last {st.get('changed_last')}", file=out)
        else:
            print(f"  counters ({eng}): {st.get('iters')} iters",
                  file=out)
        if st.get("truncated"):
            print("    WARNING: counter buffers truncated", file=out)
        errs += render_parts_table(title, st, out)

    timed = by.get("timed_run", [])
    if timed:
        secs = seconds_of("timed_run")
        print(f"  timed runs: {len(timed)}  "
              f"[{' '.join(f'{s:.3f}s' for s in secs)}]", file=out)

    segs = by.get("segment", [])
    seg_s = sum(seconds_of("segment"))
    if segs:
        print(f"  segments: {len(segs)}  compTime {_fmt_s(seg_s)}",
              file=out)
    saves = by.get("checkpoint_save", [])
    if saves:
        print(f"  checkpoint saves: {len(saves)}  updateTime "
              f"{_fmt_s(sum(s.get('seconds', 0) for s in saves))}",
              file=out)
    for r in by.get("checkpoint_resume", []):
        print(f"  resumed from iter {r.get('iter')} "
              f"({r.get('path')})", file=out)
    for r in by.get("checkpoint_fallback", []):
        print(f"  CHECKPOINT FALLBACK: {r.get('path')} corrupt -> "
              f"{r.get('fallback')} ({r.get('error')})", file=out)
    for h in by.get("health", []):
        flags = h.get("flags")
        if (not isinstance(flags, list)
                or not all(isinstance(f, str) for f in flags)
                or not isinstance(h.get("tripped"), bool)):
            errs.append(f"{title}: malformed health event (flags "
                        f"must be a list of names, tripped a bool): "
                        f"{h!r}"[:200])
            continue
        print(f"  watchdog ({h.get('engine')}): "
              f"{'TRIPPED ' + '+'.join(flags) if h['tripped'] else 'clean'}"
              f" over {h.get('iters')} iters", file=out)
    for h in by.get("health_trip", []):
        missing = [k for k in HEALTH_TRIP_REQUIRED if k not in h]
        if missing:
            errs.append(f"{title}: health_trip event missing "
                        f"{missing} — an undiagnosable trip: {h!r}"[:200])
            continue
        print(f"  WATCHDOG TRIPPED ({h['engine']}): "
              f"{'+'.join(h['flags'])} at iteration {h['iteration']}"
              f", part {h['part']} ({h.get('where', '?')})", file=out)
    for tf in by.get("topology_fault", []):
        if not tf.get("error"):
            errs.append(f"{title}: topology_fault event without an "
                        f"'error': {tf!r}"[:200])
            continue
        print(f"  TOPOLOGY FAULT: {tf['error']} (attempt "
              f"{tf.get('attempt')}, "
              f"{'re-placed' if tf.get('handled') else 'UNHANDLED'})",
              file=out)
    for ms in by.get("mesh_shrink", []):
        pair = _shrink_pair(ms)
        if pair is None or pair[1] >= pair[0]:
            errs.append(f"{title}: mesh_shrink event must record a "
                        f"SHRINKING from/to device (or process) "
                        f"count: {ms!r}"[:200])
            continue
        unit = "process" if "from_nproc" in ms else "device"
        # in-process shrinks name the LOST devices; the heartbeat
        # protocol names the SURVIVORS — never conflate the two
        who = (f"lost {ms['lost']}" if "lost" in ms
               else f"survivors {ms.get('survivors')}")
        print(f"  MESH SHRINK: {pair[0]} -> {pair[1]} {unit}s "
              f"({who}, parts {ms.get('parts', '?')})", file=out)
    for rp in by.get("replace", []):
        pair = _shrink_pair(rp)
        if pair is None:
            errs.append(f"{title}: replace event without numeric "
                        f"from_ndev/to_ndev: {rp!r}"[:200])
            continue
        print(f"  re-placement: checkpoint from a {pair[0]}-device "
              f"mesh resumed on {pair[1]} (iter {rp.get('iter')}, "
              f"{rp.get('path')})", file=out)
    for br in by.get("budget_reset", []):
        print(f"  budget rate reset ({br.get('reason') or '?'}; "
              f"was locked at {br.get('locked')})", file=out)
    for sgl in by.get("straggler", []):
        print(f"  straggler: peer(s) {sgl.get('peers')} "
              f"{sgl.get('behind_s')}s behind at boundary "
              f"{sgl.get('boundary')}", file=out)
    hbs = by.get("heartbeat", [])
    if hbs:
        last = max((h.get("boundary", 0) for h in hbs), default=0)
        print(f"  heartbeats: {len(hbs)} boundary sync(s), last "
              f"boundary {last}", file=out)
    for fd in by.get("flight_dump", []):
        print(f"  FLIGHT RECORDER: {fd.get('events')} event(s) "
              f"dumped to {fd.get('path')} "
              f"[{fd.get('classification')}] {fd.get('reason')}",
              file=out)
    for r in by.get("retry", []):
        print(f"  retry: attempt {r.get('attempt')} "
              f"{r.get('error')} [{r.get('classification')}] "
              f"backoff {r.get('backoff_s')}s", file=out)
    for r in by.get("failure", []):
        print(f"  FAILURE: {r.get('error')} "
              f"[{r.get('classification')}]", file=out)
    for d in by.get("outlier_discard", []):
        print(f"  outlier discarded: {d.get('sample')} "
              f"(median {d.get('median')})", file=out)
    for c in by.get("calibration", []):
        probe = c.get("probe") or {}
        print(f"  calibration: session {c.get('session')} "
              f"{c.get('platform')}/{c.get('backend')} "
              f"ndev={c.get('ndev')} grade={c.get('grade')} "
              f"(gather {probe.get('gather_small_ns')} ns/elem, "
              f"deviation {c.get('deviation')}x)", file=out)
    pc = by.get("phase_cost", [])
    if pc:
        apps = sorted({p.get("app") for p in pc})
        print(f"  phase attribution: {len(pc)} phase(s) over "
              f"{', '.join(str(a) for a in apps)}", file=out)
    for d in by.get("drift", []):
        print(f"  DRIFT ({d.get('app')}/{d.get('phase')}): "
              f"{d.get('verdict')} — measured {d.get('measured_s')}s "
              f"vs model {d.get('predicted_s')}s "
              f"({d.get('ratio')}x)", file=out)
    for d in by.get("debt_collected", []):
        print(f"  carried debt collected: {d.get('debt')}", file=out)
    for lc in by.get("link_calibration", []):
        print(f"  link calibration [{lc.get('tier')}]: "
              f"{lc.get('bytes_per_s')} B/s ({lc.get('prim')}, "
              f"payload {lc.get('payload_bytes')} B, ndev "
              f"{lc.get('ndev')}"
              f"{', fed scalemodel' if lc.get('fed_scalemodel') else ''})",
              file=out)
    for cl in by.get("comm_ledger", []):
        errs += render_comm_ledger(title, cl, out)

    # serving front-end (round 14, lux_tpu/serve.py): per-query
    # latency accounting.  AUDIT: every query_done carries its
    # qid/kind/iters/segments/latency, latencies are finite and >=
    # the query's wait (enqueue -> column), and every retired qid was
    # enqueued — a served answer with no matching request means the
    # per-query trail is lying.
    qdone = by.get("query_done", [])
    if qdone:
        enq = {e.get("qid") for e in by.get("query_enqueue", [])}
        lats = []
        for q in qdone:
            missing = [k for k in QUERY_DONE_REQUIRED if k not in q]
            if missing:
                errs.append(f"{title}: query_done missing {missing}: "
                            f"{q!r}"[:200])
                continue
            lat, wait = q["latency_s"], q.get("wait_s", 0)
            if not _is_num(lat) or lat < 0:
                errs.append(f"{title}: query_done qid={q['qid']} "
                            f"non-finite latency {lat!r}")
                continue
            if _is_num(wait) and lat + 1e-9 < wait:
                errs.append(f"{title}: query_done qid={q['qid']} "
                            f"latency {lat} < wait {wait} — the "
                            f"per-query clock is inconsistent")
            # no `if enq` guard: a trail with ZERO enqueue events is
            # the maximally-broken case and must fail loudest
            if q["qid"] not in enq:
                errs.append(f"{title}: query_done qid={q['qid']} was "
                            f"never enqueued")
            lats.append(lat)
        if lats:
            lats.sort()
            kinds = {}
            for q in qdone:
                k = q.get("query_kind", "?")
                kinds[k] = kinds.get(k, 0) + 1
            mix = ", ".join(f"{k} x{n}" for k, n in sorted(kinds.items()))
            print(f"  queries served: {len(qdone)} ({mix})  latency "
                  f"p50 {_fmt_s(lats[len(lats) // 2])} max "
                  f"{_fmt_s(lats[-1])}", file=out)
        refills = by.get("serve_refill", [])
        live = sum(1 for r in refills
                   if r.get("retired", 0) and r.get("filled", 0))
        if refills:
            print(f"  continuous batching: {len(refills)} refill "
                  f"boundary(ies), {live} retire+refill", file=out)

    # round 18 (serving fleet, lux_tpu/fleet.py): the resilience
    # trail — replica membership, failovers, sheds, brownout — and
    # its exactly-once / typed-rejection audits:
    # - a qid that retires TWICE violates exactly-once retirement
    # - a query_done for a SHED qid means a rejected query ran anyway
    # - a replica_lost with in-flight queries but no failover (or
    #   shed) accounting for them is an UNDIAGNOSED loss
    done_count = {}
    for q in by.get("query_done", []):
        if "qid" in q:
            done_count[q["qid"]] = done_count.get(q["qid"], 0) + 1
    # round 24: a journal re-dispatch (query_enqueue recovered=true)
    # legitimately RE-ANSWERS a query whose pre-crash answer was
    # computed but never acknowledged — the crash interposed between
    # the runner's retire and the fleet's delivery, so the client
    # saw it at most once.  ONE extra query_done per recovered qid
    # is that at-least-once-compute seam; a third is still a dup.
    recovered_qids = {e.get("qid")
                      for e in by.get("query_enqueue", [])
                      if e.get("recovered")}
    for qid, n in sorted(done_count.items()):
        if n > 1 and not (qid in recovered_qids and n == 2):
            errs.append(f"{title}: qid={qid} retired {n} times — "
                        f"exactly-once retirement violated")
    sheds = []          # WELL-FORMED sheds only: a malformed record
    shed_qids = set()   # must not vouch for anything below
    for s in by.get("query_shed", []):
        missing = [k for k in QUERY_SHED_REQUIRED if k not in s]
        if missing:
            errs.append(f"{title}: query_shed missing {missing} — "
                        f"an unaccountable rejection: {s!r}"[:200])
            continue
        sheds.append(s)
        shed_qids.add(s["qid"])
    for qid in sorted(shed_qids & set(done_count)):
        errs.append(f"{title}: query_done for qid={qid} which was "
                    f"SHED — a rejected query must never retire")
    fos = []
    for f in by.get("failover", []):
        missing = [k for k in FAILOVER_REQUIRED if k not in f]
        if missing:
            errs.append(f"{title}: failover missing {missing} — an "
                        f"unaccountable transition: {f!r}"[:200])
            continue
        fos.append(f)
    ups = by.get("replica_up", [])
    losts = by.get("replica_lost", [])
    for rl in losts:
        if not rl.get("replica") or not rl.get("error"):
            errs.append(f"{title}: replica_lost without its "
                        f"replica/error: {rl!r}"[:200])
            continue
        inflight = rl.get("inflight")
        if _is_int(inflight) and inflight > 0:
            # only failovers FROM this replica, or sheds with the
            # failover-path reasons (no_capacity / retries), diagnose
            # a loss — an unrelated admission-time shed (brownout,
            # quota, queue_full, deadline) must not vouch for
            # vanished in-flight queries
            accounted = any(f.get("from_replica") == rl["replica"]
                            for f in fos) \
                or any(s.get("reason") in ("no_capacity", "retries")
                       for s in sheds)
            if not accounted:
                errs.append(
                    f"{title}: replica_lost {rl['replica']!r} with "
                    f"{inflight} in-flight query(ies) but no "
                    f"failover or shed accounts for them — an "
                    f"undiagnosed loss")
    if ups or losts:
        lost_names = sorted(str(rl.get("replica")) for rl in losts)
        print(f"  replicas: {len(ups)} up, {len(losts)} lost"
              + (f" ({', '.join(lost_names)})" if lost_names else ""),
              file=out)
    if fos:
        qids = sorted({f.get("qid") for f in fos})
        print(f"  failovers: {len(fos)} re-dispatch(es) over "
              f"{len(qids)} qid(s)", file=out)
    if sheds:
        reasons = {}
        for s in sheds:
            r = s.get("reason", "?")
            reasons[r] = reasons.get(r, 0) + 1
        mix = ", ".join(f"{r} x{n}"
                        for r, n in sorted(reasons.items()))
        print(f"  shed: {len(sheds)} query(ies) ({mix})", file=out)
    for b in by.get("brownout", []):
        print(f"  BROWNOUT level={b.get('level')} capacity "
              f"{b.get('capacity_frac')} min_priority="
              f"{b.get('min_priority')}", file=out)

    # round 20 (live graphs, lux_tpu/livegraph.py): the mutation /
    # epoch / compaction / cache trail and its audits:
    # - TORN-EPOCH: a query_done carrying an admission ``epoch`` must
    #   carry ``answer_epoch`` EQUAL to it — the answer was computed
    #   at a different epoch than the query pinned at admission,
    #   which is a torn read published as an answer (serve.py stamps
    #   answer_epoch from the serving MECHANISM: the column's delta
    #   mask / the engine's base generation — never from the request)
    # - a compact_done whose generation has no preceding
    #   compact_start breaks the WAL compaction bracket
    # - a wal_replay that comes up at a LOWER epoch than the trail
    #   already published is a replay-after-crash epoch REGRESSION:
    #   acknowledged mutations vanished
    # round 21 (mutation algebra): two more ordered audits —
    # - a ``reseed`` is the anti-monotone revalidation of a deletion
    #   or weight update; one appearing BEFORE any delete/reweight
    #   mutation publish on its log (or a wal_replay, which can
    #   restore pending anti ops from a crashed publisher) re-seeded
    #   state that had nothing to re-seed — the trail is incoherent
    # - a ``compact_scheduled`` missing its economics fields
    #   (COMPACT_SCHEDULED_REQUIRED) is a fold that cannot justify
    #   itself — the scheduler's decision contract
    muts = by.get("mutation", [])
    for q in qdone:
        if "epoch" not in q:
            continue
        if "answer_epoch" not in q:
            errs.append(f"{title}: query_done qid={q.get('qid')} "
                        f"carries admission epoch {q['epoch']} but "
                        f"no answer_epoch — the live-serving answer "
                        f"cannot prove it was computed at its "
                        f"admission epoch")
        elif q["answer_epoch"] != q["epoch"]:
            errs.append(f"{title}: TORN-EPOCH answer qid="
                        f"{q.get('qid')}: admitted at epoch "
                        f"{q['epoch']} but answered at epoch "
                        f"{q['answer_epoch']} — snapshot isolation "
                        f"violated")
    # order-sensitive audits walk the raw run, not the by-kind map
    pending_gens, compacts_done = set(), 0
    # per-WAL-path epoch high-water marks (same pairing rule as the
    # cross-process audit_wal_replays): a replay of log B must never
    # be judged against epochs published to log A in the same run —
    # two LiveGraphs beside each other is a clean trail, not a
    # regression.  No-WAL publishes key on None and no replay can
    # ever pair with them (a replay always carries its path).
    max_epoch_seen: dict = {}
    # wal keys that have seen a delete/reweight publish (or a
    # wal_replay, which can restore a crashed publisher's pending
    # anti ops) — the only trails a reseed may follow
    anti_published: set = set()
    # round 24 (self-healing fleet, lux_tpu/fleet.py + journal.py):
    # ordered respawn-trail state — a resurrection must FOLLOW a
    # loss of that name AND a passing canary (routing a replica
    # whose canary failed — or that never ran one — is serving wrong
    # or unproven answers), and a recovered re-dispatch
    # (query_enqueue recovered=true) must follow its journal_replay
    heal_lost: set = set()
    canary_passed: set = set()
    saw_journal_replay = False
    # round 22 (memory observatory, lux_tpu/memwatch.py): replica
    # keys (None = unlabelled trail) that have published at least one
    # occupancy sample.  A mem_pressure — or a query_shed with the
    # typed ``memory`` reason — with NO preceding mem_sample /
    # mem_watermark anywhere in the run is a forecast with no
    # evidence: the decision claims a burn rate no sample fed
    mem_sampled: set = set()
    mem_peak, mem_pressures = 0, 0

    def _saw_epoch(path, e):
        max_epoch_seen[path] = max(max_epoch_seen.get(path, 0), e)

    for ev in run:
        k = ev["kind"]
        if k == "mutation":
            e = ev.get("epoch")
            if _is_int(e):
                _saw_epoch(ev.get("wal"), e)
            # ``op`` is round 21; its absence means an append-only
            # round-20 publisher — never an anti op
            if ev.get("op") in ("delete", "reweight"):
                anti_published.add(ev.get("wal"))
        elif k == "reseed":
            if ev.get("wal") not in anti_published:
                errs.append(f"{title}: reseed at epoch "
                            f"{ev.get('epoch')} without any preceding "
                            f"delete/reweight publish (or wal_replay) "
                            f"on its log — anti-monotone revalidation "
                            f"with nothing to revalidate")
        elif k in ("mem_sample", "mem_watermark"):
            mem_sampled.add(ev.get("replica"))
            pk = ev.get("peak_bytes")
            if _is_num(pk):
                mem_peak = max(mem_peak, pk)
        elif k == "mem_pressure":
            mem_pressures += 1
            missing = [f for f in MEM_PRESSURE_REQUIRED if f not in ev]
            if missing:
                errs.append(f"{title}: mem_pressure missing "
                            f"field(s) {missing} — a forecast that "
                            f"cannot justify itself")
            if ev.get("replica") not in mem_sampled \
                    and None not in mem_sampled:
                errs.append(f"{title}: mem_pressure (reason="
                            f"{ev.get('reason')!r}, replica="
                            f"{ev.get('replica')!r}) with no "
                            f"preceding mem_sample/mem_watermark — "
                            f"the forecaster claims a burn rate no "
                            f"occupancy sample ever fed")
        elif k == "query_shed" and ev.get("reason") == "memory" \
                and not mem_sampled:
            errs.append(f"{title}: memory-reason query_shed qid="
                        f"{ev.get('qid')} with no preceding "
                        f"occupancy sample — an admission decision "
                        f"priced against a byte trail that was "
                        f"never observed")
        elif k == "compact_scheduled":
            missing = [f for f in COMPACT_SCHEDULED_REQUIRED
                       if f not in ev]
            if missing:
                errs.append(f"{title}: compact_scheduled missing "
                            f"economics field(s) {missing} — a "
                            f"scheduler fold that cannot justify "
                            f"itself")
        elif k == "epoch_advance":
            e = ev.get("to_epoch")
            if _is_int(e):
                _saw_epoch(ev.get("wal"), e)
        elif k == "compact_start":
            pending_gens.add(ev.get("generation"))
        elif k == "compact_done":
            g_ = ev.get("generation")
            if g_ not in pending_gens:
                errs.append(f"{title}: compact_done generation={g_} "
                            f"without a preceding compact_start — "
                            f"the compaction bracket is broken")
            else:
                pending_gens.discard(g_)
                compacts_done += 1
        elif k == "wal_replay":
            e = ev.get("epoch")
            seen = max_epoch_seen.get(ev.get("path"), 0)
            if _is_int(e) and e < seen:
                errs.append(f"{title}: wal_replay recovered epoch "
                            f"{e} < already-published epoch "
                            f"{seen} — replay-after-crash "
                            f"epoch regression (acknowledged "
                            f"mutations vanished)")
            if _is_int(e):
                _saw_epoch(ev.get("path"), e)
            anti_published.add(ev.get("path"))
        elif k == "replica_lost":
            if ev.get("replica"):
                heal_lost.add(ev["replica"])
                # a fresh death invalidates any earlier canary pass
                canary_passed.discard(ev["replica"])
        elif k == "canary":
            r_ = ev.get("replica")
            if not r_ or not isinstance(ev.get("ok"), bool):
                errs.append(f"{title}: canary without its "
                            f"replica/ok verdict: {ev!r}"[:200])
            elif ev["ok"]:
                canary_passed.add(r_)
            else:
                canary_passed.discard(r_)
        elif k == "replica_respawn":
            r_ = ev.get("replica")
            if not r_:
                errs.append(f"{title}: replica_respawn without its "
                            f"replica: {ev!r}"[:200])
            else:
                if r_ not in heal_lost:
                    errs.append(
                        f"{title}: replica_respawn {r_!r} without a "
                        f"preceding replica_lost — a resurrection "
                        f"of a replica that never died")
                if r_ not in canary_passed:
                    errs.append(
                        f"{title}: replica_respawn {r_!r} without a "
                        f"passing canary since its loss — the "
                        f"replica re-entered routing unproven (or "
                        f"with a FAILED canary): wrong answers "
                        f"could route")
        elif k == "replica_quarantine":
            if not ev.get("replica") or not ev.get("reason"):
                errs.append(f"{title}: replica_quarantine without "
                            f"its replica/reason: {ev!r}"[:200])
        elif k == "journal_replay":
            saw_journal_replay = True
        elif k == "query_enqueue" and ev.get("recovered"):
            if not saw_journal_replay:
                errs.append(
                    f"{title}: recovered query_enqueue qid="
                    f"{ev.get('qid')} with no preceding "
                    f"journal_replay — a re-dispatch that cannot "
                    f"name the journal it recovered from")
    if mem_sampled or mem_pressures:
        n_s = len(by.get("mem_sample", []))
        n_w = len(by.get("mem_watermark", []))
        print(f"  memory: {n_s} sample(s), {n_w} watermark(s), "
              f"peak {mem_peak} bytes"
              + (f", {mem_pressures} PRESSURE signal(s)"
                 if mem_pressures else ""), file=out)
    if muts:
        edges = sum(m.get("edges", 0) for m in muts
                    if _is_int(m.get("edges")))
        advances = len(by.get("epoch_advance", []))
        occ = max((m.get("occupancy", 0) for m in muts
                   if _is_num(m.get("occupancy"))), default=0)
        n_del = sum(1 for m in muts if m.get("op") == "delete")
        n_rew = sum(1 for m in muts if m.get("op") == "reweight")
        mix = (f" ({n_del} delete, {n_rew} reweight batch(es))"
               if (n_del or n_rew) else "")
        print(f"  live graph: {edges} edge(s) over {len(muts)} "
              f"mutation batch(es){mix}, {advances} epoch advance(s), "
              f"peak delta occupancy {occ}", file=out)
    reseeds = by.get("reseed", [])
    if reseeds:
        fb = sum(1 for r in reseeds if r.get("fallback"))
        cone = max((r.get("cone", 0) for r in reseeds
                    if _is_int(r.get("cone"))), default=0)
        print(f"  re-seed: {len(reseeds)} anti-monotone "
              f"revalidation(s), peak cone {cone} vertex(ices), "
              f"{fb} full-recompute fallback(s)", file=out)
    scheds = by.get("compact_scheduled", [])
    if scheds:
        reasons = {}
        for s_ in scheds:
            r_ = s_.get("reason", "?")
            reasons[r_] = reasons.get(r_, 0) + 1
        mix = ", ".join(f"{v} {k}" for k, v in sorted(reasons.items()))
        drag = max((s_.get("drag_ns", 0) for s_ in scheds
                    if _is_num(s_.get("drag_ns"))), default=0)
        print(f"  compaction scheduler: {len(scheds)} fold(s) "
              f"scheduled ({mix}), peak delta drag {drag} "
              f"ns/boundary", file=out)
    if by.get("compact_start") or compacts_done:
        folded = sum(c.get("folded", 0)
                     for c in by.get("compact_done", [])
                     if _is_int(c.get("folded")))
        open_note = (f", {len(pending_gens)} OPEN (crashed "
                     f"mid-compaction)" if pending_gens else "")
        print(f"  compaction: {compacts_done} completed, {folded} "
              f"edge(s) folded{open_note}", file=out)
    for wt in by.get("wal_truncate", []):
        print(f"  WAL torn tail truncated: {wt.get('torn_bytes')} "
              f"byte(s) after {wt.get('records')} good record(s) "
              f"({wt.get('path')})", file=out)
    for wr in by.get("wal_replay", []):
        print(f"  WAL replay: {wr.get('records')} record(s) -> "
              f"epoch {wr.get('epoch')} generation "
              f"{wr.get('generation')} delta {wr.get('delta_count')} "
              f"(truncated {wr.get('truncated_bytes')} B)", file=out)
    # round 24 (self-healing fleet): the respawn / quarantine /
    # canary trail and the admission-journal recovery records
    respawns_ = by.get("replica_respawn", [])
    quars_ = by.get("replica_quarantine", [])
    canaries_ = by.get("canary", [])
    if respawns_ or quars_ or canaries_:
        npass = sum(1 for c in canaries_ if c.get("ok") is True)
        qmix = {}
        for q_ in quars_:
            r_ = q_.get("reason", "?")
            qmix[r_] = qmix.get(r_, 0) + 1
        qnote = ("" if not qmix else " ("
                 + ", ".join(f"{n} {r}"
                             for r, n in sorted(qmix.items())) + ")")
        print(f"  self-healing: {len(respawns_)} respawn(s), "
              f"{len(quars_)} quarantine(s){qnote}, canaries "
              f"{npass}/{len(canaries_)} passed", file=out)
    for jt in by.get("journal_truncate", []):
        print(f"  admission journal torn tail truncated: "
              f"{jt.get('torn_bytes')} byte(s), {jt.get('open')} "
              f"open / {jt.get('retired')} retired record(s) "
              f"({jt.get('path')})", file=out)
    for jr_ in by.get("journal_replay", []):
        print(f"  admission journal replay: {jr_.get('replayed')} "
              f"re-dispatched, {jr_.get('retired')} already retired "
              f"(torn {jr_.get('torn_bytes')} B) ({jr_.get('path')})",
              file=out)
    cached = [q for q in qdone if q.get("cached")]
    if cached:
        n_live = sum(1 for q in qdone if "epoch" in q)
        print(f"  answer cache: {len(cached)} of {n_live or len(qdone)}"
              f" served cached (epoch-keyed)", file=out)

    # round 17: serving metrics snapshots, cross-audited against the
    # raw query_done stream they claim to aggregate
    qdone_by_kind = {}
    for q in by.get("query_done", []):
        k = q.get("query_kind", "?")
        qdone_by_kind[k] = qdone_by_kind.get(k, 0) + 1
    # the live file's newest log_rotate carries the cumulative
    # rotation count: more rotations than kept generations means the
    # oldest query_done events were dropped with their generation, so
    # the overcount audit would indict an honest long-lived trail
    truncated = any(
        _is_int(lr.get("rotation")) and _is_int(lr.get("generations"))
        and lr["rotation"] > lr["generations"]
        for lr in by.get("log_rotate", []))
    snaps = by.get("metrics_snapshot", [])
    for i, snap in enumerate(snaps):
        # audit EVERY snapshot; render only the newest (the periodic
        # cadence otherwise floods the table)
        errs += render_metrics_snapshot(title, snap, qdone_by_kind,
                                        out,
                                        render=i == len(snaps) - 1,
                                        truncated=truncated)
    for lr in by.get("log_rotate", []):
        print(f"  log rotated (#{lr.get('rotation')}): "
              f"{lr.get('path')} -> .1 at {lr.get('rotate_bytes')} "
              f"bytes, {lr.get('generations')} generation(s) kept",
              file=out)

    # program spans (telemetry.span): host seconds by span name; a
    # record whose t1 precedes its t0 cannot be a span and FAILS
    span_s = {}
    for sp in by.get("span", []):
        t0, t1 = sp.get("t0"), sp.get("t1")
        if not (_is_num(t0) and _is_num(t1) and t1 >= t0
                and isinstance(sp.get("name"), str)):
            errs.append(f"span event without a name and ordered "
                        f"numeric t0/t1: {sp!r}"[:160])
            continue
        n, tot, sums = span_s.get(sp["name"], (0, 0.0, {}))
        for k, v in (sp.get("counts") or {}).items():
            if _is_num(v) and not isinstance(v, bool):
                sums[k] = sums.get(k, 0) + v
        span_s[sp["name"]] = (n + 1, tot + (t1 - t0), sums)
    if span_s:
        print(f"  program spans: "
              f"{sum(n for n, _, _ in span_s.values())} "
              f"record(s), {len(span_s)} name(s); counts summed",
              file=out)
        for name, (n, tot, sums) in sorted(
                span_s.items(), key=lambda kv: -kv[1][1])[:12]:
            counts = " ".join(f"{k}={v:g}" for k, v in sums.items())
            print(f"    {name:<28s} x{n:<5d} {tot:10.6f} s  {counts}",
                  file=out)

    done = by.get("run_done", [])
    if done:
        total = sum(seconds_of("run_done"))
        print(f"  ELAPSED TIME = {total:.6f} s", file=out)
        # segments are slices OF the elapsed: summing past it means
        # they overlap or double-count (under-sum is fine — elapsed
        # also bills checkpoint saves and host driver time)
        if segs and seg_s > total * 1.2 + 0.05:
            errs.append(
                f"{title}: segment seconds sum to {seg_s:.3f}s > "
                f"run_done elapsed {total:.3f}s — segments overlap "
                f"or double-count")

    unknown = sorted(set(by) - KNOWN)
    if unknown:
        print(f"  (other events: "
              f"{', '.join(f'{k} x{len(by[k])}' for k in unknown)})",
              file=out)
    return errs


def audit_wal_replays(events) -> list[str]:
    """CROSS-process replay-after-crash epoch regression (round 20,
    lux_tpu/livegraph.py): a real crash and its recovery are
    DIFFERENT processes, so the per-run walk in render_run — scoped
    to one (session, pid) stream — can never see the publisher's
    epochs.  Publishes (mutation / epoch_advance events carrying a
    ``wal`` path) and recoveries (wal_replay, ``path``) pair on the
    log path; wall-clock ``t`` orders across processes (the tracing
    alignment convention — a crash and its recovery are seconds
    apart, far past clock skew).  A replay recovering a LOWER epoch
    than one already published to the same WAL by an earlier other
    process means acknowledged mutations vanished: FAIL.  Same-
    process regressions stay with render_run's in-order walk (no
    double report: this audit skips same-stream pairs)."""
    pubs, reps = [], []
    for ev in events:
        k = ev.get("kind")
        t = ev.get("t")
        if not _is_num(t):
            continue
        key = (ev.get("session"), ev.get("pid"))
        if k in ("mutation", "epoch_advance"):
            wal = ev.get("wal")
            e = (ev.get("epoch") if k == "mutation"
                 else ev.get("to_epoch"))
            if wal and _is_int(e):
                pubs.append((t, key, wal, e))
        elif k == "wal_replay":
            e = ev.get("epoch")
            if ev.get("path") and _is_int(e):
                reps.append((t, key, ev.get("path"), e))
    errs = []
    for rt, rkey, rpath, re_ in reps:
        prior = [e for (t, key, wal, e) in pubs
                 if wal == rpath and t < rt and key != rkey]
        if prior and re_ < max(prior):
            errs.append(
                f"wal_replay ({rpath}) recovered epoch {re_} < "
                f"epoch {max(prior)} published by an earlier "
                f"process — cross-process replay-after-crash epoch "
                f"regression (acknowledged mutations vanished)")
    return errs


def render_flight(path: str, out=sys.stdout) -> list[str]:
    """Render one crash-flight-recorder dump (lux_tpu/tracing.py
    FLIGHT.json): reason, placement, last health word, and the tail
    of the recent-event ring.  Audited like the event log: a dump
    without its events ring, or with unparseable structure, fails."""
    errs = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable flight dump ({e})"]
    if not isinstance(doc, dict) or not isinstance(doc.get("events"),
                                                  list):
        return [f"{path}: not a flight-recorder dump (no events "
                f"ring)"]
    print(f"== FLIGHT {path} ==", file=out)
    print(f"  session {doc.get('session')} pid {doc.get('pid')}",
          file=out)
    print(f"  reason: [{doc.get('classification')}] "
          f"{doc.get('reason')}", file=out)
    if doc.get("placement"):
        pl = doc["placement"]
        print("  placement: " + " ".join(f"{k}={v}" for k, v in
                                         sorted(pl.items())),
              file=out)
    h = doc.get("health")
    if h:
        flags = h.get("flags")
        print(f"  last health word: "
              f"{'+'.join(flags) if flags else 'clean'} "
              f"({h.get('engine')}, iteration "
              f"{h.get('iteration', '-')}, part {h.get('part', '-')})",
              file=out)
    cal = doc.get("calibration")
    if cal:
        print(f"  calibration: {cal.get('platform')} "
              f"grade={cal.get('grade')} "
              f"deviation={cal.get('deviation')}", file=out)
    # round 22: the memory trail at the moment of death — the flight
    # recorder keeps the last mem_sample/mem_watermark/mem_pressure
    # events so an OOM postmortem can read the occupancy ramp
    mt = doc.get("mem_trail")
    if mt:
        last = mt[-1] if isinstance(mt[-1], dict) else {}
        print(f"  memory trail: {len(mt)} sample(s), last "
              f"live={last.get('live_bytes', '-')} "
              f"peak={last.get('peak_bytes', '-')} "
              f"({last.get('grade', '-')})", file=out)
        for ev in mt[-4:]:
            if isinstance(ev, dict) and ev.get("kind") == \
                    "mem_pressure":
                print(f"    PRESSURE reason={ev.get('reason')} "
                      f"live={ev.get('live_bytes')} "
                      f"budget={ev.get('budget_bytes')} "
                      f"burn={ev.get('burn')}", file=out)
    evs = doc["events"]
    counts = doc.get("counts") or {}
    print(f"  ring: {len(evs)} event(s) "
          f"({', '.join(f'{k} x{v}' for k, v in sorted(counts.items()))})",
          file=out)
    for ev in evs[-12:]:
        if not isinstance(ev, dict) or "kind" not in ev:
            errs.append(f"{path}: malformed ring event {ev!r}"[:160])
            continue
        extra = " ".join(
            f"{k}={ev[k]}" for k in ("iteration", "part", "flags",
                                     "error", "seconds", "boundary",
                                     "attempt")
            if k in ev)
        print(f"    tm={ev.get('tm')} {ev['kind']} {extra}", file=out)
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render a lux_tpu telemetry event JSONL "
                    "(-events FILE) into the reference-style table")
    ap.add_argument("files", nargs="+", metavar="FILE")
    ap.add_argument("-flight", action="store_true",
                    help="FILEs are crash-flight-recorder dumps "
                         "(lux_tpu/tracing.py FLIGHT.json), not "
                         "event JSONLs — render the postmortem view")
    args = ap.parse_args(argv)

    all_errs = []
    if args.flight:
        for path in args.files:
            all_errs += render_flight(path)
        for e in all_errs:
            print(f"ERROR: {e}", file=sys.stderr)
        if all_errs:
            print(f"events_summary: {len(all_errs)} error(s)",
                  file=sys.stderr)
            return 1
        return 0
    for path in args.files:
        # a rotated EventLog (telemetry rotate_bytes) leaves .1/.2
        # generations beside the live file: consume the whole set,
        # oldest first, as ONE stream — runs spanning a rotation must
        # not split at the file boundary
        gens = rotated_set(path)
        events, errs = [], []
        try:
            for gen in gens:
                evs, es = load_events(gen)
                events += evs
                errs += [e if len(gens) == 1 else f"{gen}: {e}"
                         for e in es]
        except OSError as e:
            all_errs.append(f"{path}: unreadable ({e})")
            continue
        all_errs += [f"{path}: {e}" for e in errs]
        streams, serrs = split_streams(events)
        all_errs += [f"{path}: {e}" for e in serrs]
        all_errs += [f"{path}: {e}"
                     for e in audit_wal_replays(events)]
        for key, stream in streams:
            if key is not None and len(streams) > 1:
                print(f"-- process session={key[0]} pid={key[1]} --")
            for run in split_runs(stream):
                all_errs += [f"{path}: {e}" for e in render_run(run)]
    for e in all_errs:
        print(f"ERROR: {e}", file=sys.stderr)
    if all_errs:
        print(f"events_summary: {len(all_errs)} error(s)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
