#!/usr/bin/env python
"""Validate bench metric lines against the resilience-era schema.

The driver's BENCH_*.json artifacts wrap bench.py's stdout; each
metric line there is one JSON object.  Round 6 added an audit trail
(lux_tpu/resilience.py): ``attempts`` (total timed runs, outlier
reruns included), ``discarded`` (samples thrown out by the >3x
discard-and-rerun rule), and ``run_attempts`` when a whole config was
retried after a transient crash.  A headline number whose line lacks
that metadata can silently median over a collapsed sample — exactly
the round-5 pagerank-mp incident ([0.1116, 0.0107, 0.1118]) this
schema exists to make impossible — so missing metadata FAILS the
check.

Usage:
    python scripts/check_bench.py [-legacy-ok] FILE...

FILE is a driver artifact (JSON object with a ``tail`` transcript), a
raw JSONL of metric lines, or a single JSON metric object.
``-legacy-ok`` downgrades pre-round-6 metadata gaps (missing
samples/attempts/discarded) to warnings so pre-round-6 artifacts
still audit cleanly; structural errors (bad median,
inconsistent counts, malformed lines) always fail.

Checked per metric line:
- required keys: metric, value, unit, vs_baseline
- samples: non-empty list of finite numbers, value == median(samples)
  (to rounding)
- attempts: int, == len(samples) + len(discarded) — every discarded
  sample was either re-run (adding a kept sample) or counted
- discarded: list of finite numbers, each >FACTORx off the kept median
  is not re-checked here (the factor is a bench flag), but discarded
  samples must not also appear in samples
- run_attempts (optional): int >= 2
- *_FAILED lines: error message plus attempts and failure_class
  ("retryable" | "fatal")
- round-8 script lines: colfilter-netflix (scripts/bench_netflix.py)
  must carry a strictly-decreasing ``rmse`` trajectory plus the pair
  configuration; bigscale lines (scripts/bench_bigscale.py, e.g. the
  RMAT27 pair record) must carry scale/ne/iters/exchange consistent
  with the metric name — both now emit the same samples/attempts/
  discarded + telemetry audit schema as bench.py, so the outlier
  screen is checked on them too
- telemetry (round 7, lux_tpu/telemetry.py): ``runs`` — one
  {repeat, iters, seconds} per timed run, straight from the
  ``timed_run`` events — and ``counters`` (the device-side
  per-iteration digest, or null when -iter-stats was off).  Checked:
  len(runs) == attempts (every sample and every discard has its
  seconds on record), and with ``ne`` present each run's
  ne*iters/seconds re-derives a recorded sample — the per-run
  decomposition summing back to the published number, so a collapsed
  run can't hide behind its median.  Both loosen to >= / skip when
  the line carries run_attempts (whole config retried) or
  rerun_error (an outlier rerun crashed after its timed_run event
  landed) — those runs legitimately have no recorded sample.  Missing
  telemetry fails strict mode like the round-6 keys (the round-1..6
  artifacts predate it: -legacy-ok).

- audit (round 10, bench.py -audit / lux_tpu/audit.py): optional
  digest of the static program audit that ran at the config's engine
  build — {mode: warn|error, errors: int, warnings: int,
  failed_checks: [known check names]}.  A digest with errors (or any
  failed_checks) on a PUBLISHED metric line is rejected: the number
  was measured on a build that violates the framework's structural
  invariants (double gather, baked-in constants, broken collective
  schedule...), so it cannot stand as a metric of record.

- telemetry.imbalance (round 13, lux_tpu/tracing.py era): the
  per-part imbalance digest — {kind, index = max/mean per-part work,
  parts = per-part totals} — null when -iter-stats was off.  Checked:
  index recomputes from the parts, and the parts SUM to the counter
  digest's edges_sum/changed_sum (the same contradiction pattern as
  the health digest: per-part and scalar counters are the same
  device-side values reduced in a different order, so disagreement
  means the published skew signal is lying).

- telemetry.topology (round 11, lux_tpu/resilience.py elastic
  recovery): optional; null when the mesh never changed.  A non-null
  digest ({shrinks, ndev_final}) REJECTS the line — a mid-run mesh
  shrink means part of the measurement ran degraded, and a
  degraded-mesh GTEPS must never be compared against full-mesh lines
  silently.

- calibration (round 12, lux_tpu/observe.py): the session-calibration
  fingerprint digest every bench.py / bench_netflix / bench_bigscale
  line now carries — {session, platform, backend, ndev, grade,
  deviation, probe}.  Missing fails strict mode (pre-round-12
  artifacts: -legacy-ok); null (a crashed probe) or any grade other
  than "canonical" REJECTS the line: a session whose reference probe
  ran >3x off the canonical PERF_NOTES figures (in either
  direction) or on a non-canonical platform is detected
  and labeled at the source, and its numbers never enter the
  trajectory silently.

- serve-slo lines (round 17, bench.py -config serve-slo +
  scripts/loadgen.py): the value is the measured achieved qps of one
  open-loop Poisson load step; the line must carry offered_qps /
  achieved_qps / p50_ms / p99_ms / slo_target_ms / slo_good_fraction
  and is rejected on the contradictions an honest open-loop run
  cannot produce: p99 < p50, achieved > offered, a good fraction
  outside [0, 1], or a headline value disagreeing with the recorded
  achieved rate.

- serve-chaos lines (round 18, bench.py -config serve-chaos +
  lux_tpu/fleet.py): the serve-slo record under an injected replica
  kill, extended with replicas/failovers/shed/shed_fraction/
  slo_accounted plus the round-24 self-healing gauges respawns/
  quarantines/mttr_s/journal_replayed; rejected on shed_fraction
  outside [0, 1] (or disagreeing with shed/submitted), failovers or
  respawns with replicas=1, served+shed != submitted, slo_accounted
  > served (an SLO fraction computed over shed queries), mttr_s
  with neither failovers nor respawns (repair time without an
  outage), or journal_replayed > submitted (a recovery claiming
  queries the load never offered).

- comm (round 19, lux_tpu/comms.py): the per-collective byte-ledger
  digest engine metric lines now carry — {errors, ndev, exchange,
  tier, bytes_per_iter, comm_bytes_per_edge, messages, comm_frac}.
  Rejected on: a ledger-failing build (errors > 0 — the oracle/audit
  cross-check failed), comm_frac outside [0, 1], bytes or messages
  on a single device, a mesh owner/gather exchange shipping zero
  bytes, or a per-edge figure contradicting bytes_per_iter*ndev/ne.

- telemetry.health (round 9, bench.py -health): the device-side
  watchdog digest — optional and null when off; present it must be a
  clean bill ({engine, tripped=false, flags=[], iters >= 0}; known
  check names only) — a tripped watchdog fails its config with a
  _FAILED line, so a published metric line claiming a trip is a
  contradiction and fails the audit.

Exit status: 0 clean, 1 any error (loud, listed on stderr).
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from statistics import median

LEGACY_KEYS = ("samples", "attempts", "discarded")

# Round-8 script metric lines (scripts/bench_netflix.py and
# scripts/bench_bigscale.py emit the same resilience/telemetry schema
# as bench.py plus script-specific fields, validated below):
# colfilter-netflix carries the RMSE learning trajectory, bigscale
# carries the scale/exchange/pair configuration of record.
NETFLIX_METRIC = re.compile(
    r"^colfilter_netflix(\d+)m_np(\d+)_gteps_per_chip$")
BIGSCALE_METRIC = re.compile(
    r"^(pagerank|cc|sssp|sssp-w)_rmat(\d+)_np(\d+)_gteps_per_chip$")
# query-batched lines (bench.py ksssp-batch/ppr-batch, ROADMAP item
# 2): the metric name carries the batch width B, the line carries
# batch + query_gteps (= B x value, the delivered query-edge rate) —
# cross-checked below so a published per-query claim can never
# contradict the machine rate it was derived from
BATCH_METRIC = re.compile(
    r"^(ksssp|ppr)_b(\d+)_rmat(\d+)_gteps_per_chip$")
# paged-vs-flat A/B lines (bench.py -config gather-ab, round 15,
# ops/pagegather.py): the metric name carries the delivery mode, the
# line carries gather + the plan's measured page stats — the ratio
# the break-even claim rests on must be on the record, both sides.
# Round 16 grows the reorder token (none|native|hillclimb,
# lux_tpu/reorder.py — absent in the name means none), the pagemajor
# mode and the community shape; a reordered line is additionally
# cross-checked against its paired none line (check_reorder_pairs:
# the fill must not DECREASE under a reorder, or the published gain
# is a contradiction)
GATHER_AB_METRIC = re.compile(
    r"^pagerank_(paged|flat|pagemajor)_(?:(native|hillclimb)_)?"
    r"(rmat|comm)(\d+)_gteps_per_chip$")
REORDER_METHODS = ("none", "native", "hillclimb")
# round-23 MXU-vs-VPU reduce A/B lines (bench.py -config mxu-ab,
# ops/tiled.py): the metric name carries the reduce path, the line
# carries mxu (the mode of record), use_mxu (the engine's RESOLVED
# flag — a name/mode/flag disagreement is the mode-vs-name
# contradiction class), the scalemodel per-row rates for BOTH paths
# (the modeled step-change the measured pair is read against) and
# the plan fill.  An mxu line is only publishable NEXT TO its paired
# vpu baseline (check_mxu_pairs) — a lone MXU number has no
# step-change to show.
MXU_AB_METRIC = re.compile(
    r"^ppr_(mxu|vpu)_comm(\d+)_gteps_per_chip$")
# round-17 serving SLO lines (bench.py -config serve-slo +
# scripts/loadgen.py): one open-loop Poisson load step per line, the
# value is the MEASURED achieved qps.  The line must carry the whole
# latency-vs-offered-rate record (offered/achieved qps, snapshot
# p50/p99 ms, the per-kind SLO targets and the good fraction), and
# three contradictions reject outright: p99 < p50 (a percentile pair
# no real distribution produces), achieved > offered (the open-loop
# harness measures both from the same load-start clock, so service
# cannot outrun arrivals), and an SLO good fraction outside [0, 1].
SERVE_SLO_METRIC = re.compile(
    r"^serve_slo_q([0-9pm]+)_rmat(\d+)_qps_per_chip$")
# round-18 serving chaos lines (bench.py -config serve-chaos +
# lux_tpu/fleet.py): the serve-slo record under an injected replica
# kill, extended with replicas/failovers/shed/shed_fraction/
# slo_accounted.  Contradiction rejects on top of the serve-slo set:
# shed_fraction outside [0, 1] (or disagreeing with shed/submitted),
# failovers > 0 with replicas = 1 (no survivor to fail over TO),
# served + shed != submitted (admitted and shed must partition the
# offered load), and slo_accounted > served (the SLO fraction was
# computed over shed queries — the accounting covers ADMITTED
# retirements only).  Round 24 adds the self-healing gauges
# (respawns/quarantines/mttr_s/journal_replayed) and their rejects:
# respawns with replicas = 1, mttr_s without any failover or
# respawn, journal_replayed > submitted.
SERVE_CHAOS_METRIC = re.compile(
    r"^serve_chaos_q([0-9pm]+)_rmat(\d+)_qps_per_chip$")
# round-20 live-graph serving lines (bench.py -config serve-live +
# lux_tpu/livegraph.py): mixed traffic over a mutating graph with
# epoch-pinned answers, the epoch-keyed cache and threshold-triggered
# compaction.  Contradiction rejects: epochs_advanced > 0 with
# mutations = 0 (epochs only advance when a mutation batch publishes)
# and vice versa, cache_hit_fraction outside [0, 1], compactions > 0
# with peak_occupancy strictly under compact_threshold AND no
# pending anti-monotone op (neither trigger the line claims could
# have fired).  Round 21 adds the mutation-algebra counters
# (deletions / reweights / reseeds / scheduler_compactions) with
# their own contradictions: a re-seed without any deletion/reweight
# to re-seed from, algebra ops exceeding the mutation total, and
# scheduler folds exceeding the compaction count or justified by no
# evidenceable trigger.
SERVE_LIVE_METRIC = re.compile(
    r"^serve_live_rmat(\d+)_qps_per_chip$")


def iter_metric_lines(path: str):
    """Yield (lineno_label, dict) metric objects from ``path``."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict) and "tail" in doc:      # driver artifact
        src = doc["tail"].splitlines()
        label = "tail line"
    elif isinstance(doc, dict) and "metric" in doc:  # one bare object
        yield "object", doc
        return
    else:                                            # raw JSONL
        src = text.splitlines()
        label = "line"
    for i, line in enumerate(src, 1):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            yield f"{label} {i}", {"_unparseable": line[:120]}
            continue
        if isinstance(obj, dict) and "metric" in obj:
            yield f"{label} {i}", obj


def _is_num(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and x == x and abs(x) != float("inf")


def check_line(obj: dict, *, legacy_ok: bool):
    """Returns (errors, warnings) string lists for one metric line."""
    errs, warns = [], []
    if "_unparseable" in obj:
        return [f"unparseable JSON: {obj['_unparseable']}"], []
    name = obj.get("metric", "?")

    if name.endswith("_FAILED"):
        if not obj.get("error"):
            errs.append(f"{name}: failure line without an 'error'")
        missing = [k for k in ("attempts", "failure_class")
                   if k not in obj]
        if missing:
            (warns if legacy_ok else errs).append(
                f"{name}: failure line missing {missing}")
        elif obj["failure_class"] not in ("retryable", "fatal"):
            errs.append(f"{name}: failure_class="
                        f"{obj['failure_class']!r} not retryable|fatal")
        return errs, warns

    for k in ("metric", "value", "unit", "vs_baseline"):
        if k not in obj:
            errs.append(f"{name}: missing required key {k!r}")
    if "value" in obj and not _is_num(obj["value"]):
        errs.append(f"{name}: non-finite value {obj['value']!r}")

    missing = [k for k in LEGACY_KEYS if k not in obj]
    if missing:
        msg = (f"{name}: missing resilience metadata {missing} "
               f"(pre-round-6 schema)")
        (warns if legacy_ok else errs).append(msg)

    samples = obj.get("samples")
    if samples is not None:
        if (not isinstance(samples, list) or not samples
                or not all(_is_num(s) for s in samples)):
            errs.append(f"{name}: samples must be a non-empty list "
                        f"of finite numbers, got {samples!r}")
            samples = None
    if samples and _is_num(obj.get("value")):
        m = median(samples)
        # value = round(median(raw), 4) while samples are rounded
        # individually: the two medians agree to ~1e-4
        if abs(obj["value"] - m) > 2e-4:
            errs.append(f"{name}: value {obj['value']} is not the "
                        f"median of samples ({m:.4f}) — collapsed "
                        f"sample silently medianed?")

    discarded = obj.get("discarded")
    if discarded is not None:
        if (not isinstance(discarded, list)
                or not all(_is_num(d) for d in discarded)):
            errs.append(f"{name}: discarded must be a list of finite "
                        f"numbers, got {discarded!r}")
            discarded = None
    if samples and discarded:
        # a kept sample equal to a discarded one is a contradiction
        # (discards are >FACTORx off the median the keeps define) —
        # it means a discarded collapse was ALSO medianed
        overlap = sorted(set(samples) & set(discarded))
        if overlap:
            errs.append(f"{name}: {overlap} appear in both samples "
                        f"and discarded — discarded sample medianed")

    attempts = obj.get("attempts")
    if attempts is not None:
        if not isinstance(attempts, int) or attempts < 1:
            errs.append(f"{name}: attempts must be a positive int, "
                        f"got {attempts!r}")
        elif samples is not None and discarded is not None:
            want = len(samples) + len(discarded)
            if attempts != want:
                errs.append(
                    f"{name}: attempts={attempts} inconsistent with "
                    f"{len(samples)} samples + {len(discarded)} "
                    f"discarded (= {want})")

    ra = obj.get("run_attempts")
    if ra is not None and (not isinstance(ra, int) or ra < 2):
        errs.append(f"{name}: run_attempts={ra!r} (recorded only "
                    f"when >= 2)")

    if "telemetry" not in obj:
        (warns if legacy_ok else errs).append(
            f"{name}: missing telemetry field (pre-round-7 schema)")
    else:
        errs += check_telemetry(name, obj)

    errs += check_audit_field(name, obj)
    errs += check_comm_field(name, obj)
    errs += check_mem_field(name, obj)

    if "calibration" not in obj:
        (warns if legacy_ok else errs).append(
            f"{name}: missing calibration field (pre-round-12 "
            f"schema)")
    else:
        errs += check_calibration_field(name, obj)

    if NETFLIX_METRIC.match(name):
        errs += check_netflix_fields(name, obj)
    else:
        m = BIGSCALE_METRIC.match(name)
        if m:
            errs += check_bigscale_fields(name, obj, int(m.group(2)))
    m = BATCH_METRIC.match(name)
    if m or "batch" in obj:
        errs += check_batch_fields(name, obj,
                                   int(m.group(2)) if m else None)
    m = GATHER_AB_METRIC.match(name)
    if m or "gather" in obj:
        errs += check_gather_fields(name, obj,
                                    m.group(1) if m else None,
                                    (m.group(2) or "none") if m
                                    else None)
    m = MXU_AB_METRIC.match(name)
    if m or "mxu" in obj:
        errs += check_mxu_fields(name, obj, m.group(1) if m else None)
    if SERVE_SLO_METRIC.match(name) or SERVE_CHAOS_METRIC.match(name) \
            or "offered_qps" in obj:
        errs += check_serve_slo_fields(name, obj)
    if SERVE_CHAOS_METRIC.match(name) or "shed_fraction" in obj \
            or "failovers" in obj:
        errs += check_serve_chaos_fields(name, obj)
    if SERVE_LIVE_METRIC.match(name) or "epochs_advanced" in obj \
            or "cache_hit_fraction" in obj:
        errs += check_serve_live_fields(name, obj)
    return errs, warns


def _check_pair_cfg(name: str, obj: dict) -> list[str]:
    """pair_threshold / min_fill fields shared by the netflix and
    bigscale lines: positive int or null (min_fill also 'auto', the
    K-aware break-even)."""
    errs = []
    pt = obj.get("pair_threshold")
    if pt is not None and (not isinstance(pt, int) or pt < 1):
        errs.append(f"{name}: pair_threshold={pt!r} must be a "
                    f"positive int or null")
    mf = obj.get("min_fill")
    if mf is not None and mf != "auto" and (
            not isinstance(mf, int) or mf < 1):
        errs.append(f"{name}: min_fill={mf!r} must be a positive "
                    f"int, 'auto' or null")
    return errs


def check_netflix_fields(name: str, obj: dict) -> list[str]:
    """colfilter-netflix lines (scripts/bench_netflix.py): the RMSE
    trajectory must be recorded and STRICTLY DECREASING — a GTEPS
    number on a factorization that is not learning is noise (the
    script asserts this at run time; the audit re-checks the
    artifact), plus the pair configuration fields."""
    errs = []
    missing = [k for k in ("rmse", "ne", "np", "iters",
                           "pair_threshold") if k not in obj]
    if missing:
        errs.append(f"{name}: netflix line missing {missing}")
    rmse = obj.get("rmse")
    if rmse is not None:
        if (not isinstance(rmse, list) or len(rmse) < 2
                or not all(_is_num(r) for r in rmse)):
            errs.append(f"{name}: rmse must be a list of >= 2 finite "
                        f"numbers, got {rmse!r}")
        elif not all(b < a for a, b in zip(rmse, rmse[1:])):
            errs.append(f"{name}: rmse {rmse} is not strictly "
                        f"decreasing — the factorization did not "
                        f"learn; the GTEPS line is noise")
    return errs + _check_pair_cfg(name, obj)


def check_bigscale_fields(name: str, obj: dict,
                          name_scale: int) -> list[str]:
    """bigscale lines (scripts/bench_bigscale.py, e.g. the RMAT27
    pair record): configuration of record must be present and
    self-consistent with the metric name."""
    errs = []
    missing = [k for k in ("scale", "ne", "iters", "exchange")
               if k not in obj]
    if missing:
        errs.append(f"{name}: bigscale line missing {missing}")
    scale = obj.get("scale")
    if isinstance(scale, int) and scale != name_scale:
        errs.append(f"{name}: scale={scale} contradicts the metric "
                    f"name's rmat{name_scale}")
    ex = obj.get("exchange")
    if ex is not None and ex not in ("gather", "owner", "auto"):
        errs.append(f"{name}: exchange={ex!r} not "
                    f"gather|owner|auto")
    it = obj.get("iters")
    if it is not None and (not isinstance(it, int) or it < 1):
        errs.append(f"{name}: iters={it!r} must be a positive int")
    ne = obj.get("ne")
    if ne is not None and (not isinstance(ne, int) or ne < 1):
        errs.append(f"{name}: ne={ne!r} must be a positive int")
    return errs + _check_pair_cfg(name, obj)


def check_batch_fields(name: str, obj: dict,
                       name_b: int | None) -> list[str]:
    """Query-batched lines (bench.py batch-sweep, ROADMAP item 2):
    ``batch`` must be a positive int matching the metric name's _bN_,
    and ``query_gteps`` — the delivered query-edge rate the per-query
    amortization claim rests on — must equal batch x value (to
    rounding): a per-query number that contradicts the machine rate
    it was derived from is rejected, the same contradiction pattern
    as the imbalance/health digests."""
    errs = []
    b = obj.get("batch")
    if not isinstance(b, int) or isinstance(b, bool) or b < 1:
        errs.append(f"{name}: batch={b!r} must be a positive int")
        return errs
    if name_b is not None and b != name_b:
        errs.append(f"{name}: batch={b} contradicts the metric "
                    f"name's _b{name_b}_")
    qg = obj.get("query_gteps")
    if qg is None:
        errs.append(f"{name}: batched line missing query_gteps "
                    f"(= batch x value, the per-query metric of "
                    f"record)")
    elif not _is_num(qg):
        errs.append(f"{name}: query_gteps={qg!r} must be a finite "
                    f"number")
    elif _is_num(obj.get("value")):
        want = b * obj["value"]
        # value and query_gteps round independently to 4 decimals
        if abs(qg - want) > 1e-4 * (b + 1):
            errs.append(
                f"{name}: query_gteps={qg} != batch x value "
                f"({b} x {obj['value']} = {want:.4f}) — the "
                f"per-query claim contradicts the machine rate")
    pq = obj.get("per_query_edge_ns")
    if pq is not None and _is_num(qg) and qg > 0:
        if not _is_num(pq) or abs(pq - 1.0 / qg) > 2e-3 * max(
                1.0, 1.0 / qg):
            errs.append(
                f"{name}: per_query_edge_ns={pq!r} contradicts "
                f"1/query_gteps ({1.0 / qg:.4f})")
    return errs


def check_gather_fields(name: str, obj: dict,
                        name_mode: str | None,
                        name_reorder: str | None = None) -> list[str]:
    """Gather A/B lines (bench.py -config gather-ab, round 15): the
    ``gather`` mode must be paged|flat|pagemajor and match the metric
    name, and BOTH sides must record the plan's measured page stats —
    ``page_ratio`` (unique page elements per edge, finite > 0) and
    ``page_fill`` (live lanes per PADDED delivery row, (0, 128] —
    the exact padded_fill gather="auto" and the phase model consume,
    not the live-rows-only figure): the modeled break-even
    (scalemodel.page_gather_ns) is resolved FROM these numbers, so a
    published A/B without them cannot be audited.  Round 16: the
    ``reorder`` field (none|native|hillclimb, lux_tpu/reorder.py)
    must match the metric name's reorder token — a line claiming a
    reordered fill under an unreordered name (or vice versa) is the
    same contradiction class as mode-vs-name."""
    errs = []
    mode = obj.get("gather")
    if mode not in ("paged", "flat", "pagemajor"):
        errs.append(f"{name}: gather={mode!r} must be 'paged', "
                    f"'flat' or 'pagemajor'")
        return errs
    if name_mode is not None and mode != name_mode:
        errs.append(f"{name}: gather={mode!r} contradicts the metric "
                    f"name's _{name_mode}_")
    ro = obj.get("reorder")
    if ro is not None and ro not in REORDER_METHODS:
        errs.append(f"{name}: reorder={ro!r} must be one of "
                    f"{'|'.join(REORDER_METHODS)}")
    elif name_reorder is not None and (ro or "none") != name_reorder:
        errs.append(f"{name}: reorder={ro!r} contradicts the metric "
                    f"name's reorder token {name_reorder!r}")
    pr = obj.get("page_ratio")
    if not _is_num(pr) or pr <= 0:
        errs.append(f"{name}: page_ratio={pr!r} must be a finite "
                    f"number > 0 (the plan's measured unique-page "
                    f"ratio, the break-even model's input)")
    pf = obj.get("page_fill")
    if not _is_num(pf) or not 0.0 < pf <= 128.0:
        errs.append(f"{name}: page_fill={pf!r} must be a finite "
                    f"number in (0, 128] (live lanes per padded "
                    f"128-lane delivery row)")
    return errs


def check_mxu_fields(name: str, obj: dict,
                     name_mode: str | None) -> list[str]:
    """Round-23 MXU A/B lines (see MXU_AB_METRIC): ``mxu`` must be
    mxu|vpu and match the metric name, ``use_mxu`` must be the
    matching resolved boolean (the engine flag of record — a vpu line
    claiming use_mxu=true ran the wrong path), and BOTH modeled
    per-chunk-row rates (``mxu_row_ns``/``vpu_row_ns``,
    lux_tpu/scalemodel.py) must be present, finite > 0 and DISTINCT:
    the pair exists to show a step-change, and identical models mean
    the line was stamped without resolving the payload width."""
    errs = []
    mode = obj.get("mxu")
    if mode not in ("mxu", "vpu"):
        errs.append(f"{name}: mxu={mode!r} must be 'mxu' or 'vpu'")
        return errs
    if name_mode is not None and mode != name_mode:
        errs.append(f"{name}: mxu={mode!r} contradicts the metric "
                    f"name's _{name_mode}_")
    um = obj.get("use_mxu")
    if not isinstance(um, bool):
        errs.append(f"{name}: use_mxu={um!r} must be a bool (the "
                    f"engine's resolved flag)")
    elif um != (mode == "mxu"):
        errs.append(f"{name}: use_mxu={um} contradicts mxu={mode!r} "
                    f"— the engine ran the other reduce path")
    kind = obj.get("reduce_kind")
    if kind not in ("sum", "min", "max"):
        errs.append(f"{name}: reduce_kind={kind!r} must be "
                    f"sum|min|max")
    rates = {}
    for k in ("mxu_row_ns", "vpu_row_ns"):
        v = obj.get(k)
        if not _is_num(v) or v <= 0:
            errs.append(f"{name}: {k}={v!r} must be a finite number "
                        f"> 0 (the scalemodel per-chunk-row rate)")
        else:
            rates[k] = v
    if len(rates) == 2 and abs(
            rates["mxu_row_ns"] - rates["vpu_row_ns"]) < 1e-9:
        errs.append(f"{name}: mxu_row_ns == vpu_row_ns "
                    f"({rates['mxu_row_ns']}) — the modeled pair "
                    f"shows no step-change; the payload width was "
                    f"not resolved")
    pf = obj.get("page_fill")
    if not _is_num(pf) or not 0.0 < pf <= 128.0:
        errs.append(f"{name}: page_fill={pf!r} must be a finite "
                    f"number in (0, 128] (live lanes per padded "
                    f"128-lane row — the A/B's dense-fill evidence)")
    return errs


def check_serve_slo_fields(name: str, obj: dict) -> list[str]:
    """Round-17 serving SLO lines (see SERVE_SLO_METRIC): the full
    latency-vs-offered-rate record must be present, self-consistent
    (value == achieved qps), and free of the three contradictions an
    honest open-loop run cannot produce — p99 < p50, achieved >
    offered, SLO good fraction outside [0, 1]."""
    errs = []
    missing = [k for k in ("offered_qps", "achieved_qps", "p50_ms",
                           "p99_ms", "slo_target_ms",
                           "slo_good_fraction") if k not in obj]
    if missing:
        errs.append(f"{name}: serve-slo line missing {missing}")
    off, ach = obj.get("offered_qps"), obj.get("achieved_qps")
    if off is not None and (not _is_num(off) or off <= 0):
        errs.append(f"{name}: offered_qps={off!r} must be a finite "
                    f"number > 0")
        off = None
    if ach is not None and (not _is_num(ach) or ach < 0):
        errs.append(f"{name}: achieved_qps={ach!r} must be a finite "
                    f"number >= 0")
        ach = None
    if off is not None and ach is not None \
            and ach > off + 3e-4 * max(1.0, off):
        errs.append(
            f"{name}: achieved_qps={ach} > offered_qps={off} — the "
            f"open-loop harness measures both from the load-start "
            f"clock, so service cannot outrun arrivals; the line "
            f"contradicts its own schedule")
    if ach is not None and _is_num(obj.get("value")) \
            and abs(obj["value"] - ach) > 2e-4 * max(1.0, ach):
        errs.append(f"{name}: value={obj['value']} is not the "
                    f"recorded achieved_qps ({ach}) — the headline "
                    f"and the SLO record disagree")
    p50, p99 = obj.get("p50_ms"), obj.get("p99_ms")
    for k, v in (("p50_ms", p50), ("p99_ms", p99)):
        if v is not None and (not _is_num(v) or v < 0):
            errs.append(f"{name}: {k}={v!r} must be a finite "
                        f"number >= 0")
    if _is_num(p50) and _is_num(p99) \
            and p99 < p50 - 2e-4 * max(1.0, p50):
        errs.append(
            f"{name}: p99_ms={p99} < p50_ms={p50} — no latency "
            f"distribution has a 99th percentile under its median; "
            f"the published percentile pair is a contradiction")
    frac = obj.get("slo_good_fraction")
    if frac is not None and (not _is_num(frac)
                             or not 0.0 <= frac <= 1.0):
        errs.append(f"{name}: slo_good_fraction={frac!r} must be a "
                    f"finite number in [0, 1]")
    tgt = obj.get("slo_target_ms")
    if tgt is not None:
        if _is_num(tgt):
            ok = tgt > 0
        elif isinstance(tgt, dict) and tgt:
            ok = all(_is_num(v) and v > 0 for v in tgt.values())
        else:
            ok = False
        if not ok:
            errs.append(f"{name}: slo_target_ms={tgt!r} must be a "
                        f"positive number or a non-empty "
                        f"{{kind: positive ms}} dict")
    return errs


def check_serve_chaos_fields(name: str, obj: dict) -> list[str]:
    """Round-18 serving chaos lines (see SERVE_CHAOS_METRIC): the
    resilience record must be present and free of the contradictions
    an honest kill-under-load run cannot produce."""
    errs = []

    def _int(x) -> bool:
        # bool is an int subclass: a JSON-boolean chaos record must
        # not validate as 0/1
        return isinstance(x, int) and not isinstance(x, bool)

    missing = [k for k in ("replicas", "failovers", "shed",
                           "shed_fraction") if k not in obj]
    if missing:
        errs.append(f"{name}: serve-chaos line missing {missing}")
    reps = obj.get("replicas")
    if reps is not None and (not _int(reps) or reps < 1):
        errs.append(f"{name}: replicas={reps!r} must be an int >= 1")
        reps = None
    fo = obj.get("failovers")
    if fo is not None and (not _int(fo) or fo < 0):
        errs.append(f"{name}: failovers={fo!r} must be an int >= 0")
        fo = None
    if fo is not None and fo > 0 and reps == 1:
        errs.append(
            f"{name}: failovers={fo} with replicas=1 — there is no "
            f"surviving replica to fail over TO; the line "
            f"contradicts its own topology")
    shed = obj.get("shed")
    if shed is not None and (not _int(shed) or shed < 0):
        errs.append(f"{name}: shed={shed!r} must be an int >= 0")
        shed = None
    frac = obj.get("shed_fraction")
    if frac is not None and (not _is_num(frac)
                             or not 0.0 <= frac <= 1.0):
        errs.append(f"{name}: shed_fraction={frac!r} must be a "
                    f"finite number in [0, 1]")
        frac = None
    served, submitted = obj.get("served"), obj.get("submitted")
    ints = all(_int(x) for x in (served, submitted))
    if ints and shed is not None and served + shed != submitted:
        errs.append(
            f"{name}: served={served} + shed={shed} != "
            f"submitted={submitted} — admitted and shed queries "
            f"must partition the offered load")
    if ints and frac is not None and shed is not None \
            and submitted > 0 \
            and abs(frac - shed / submitted) > 2e-4:
        errs.append(
            f"{name}: shed_fraction={frac} disagrees with "
            f"shed/submitted = {shed / submitted:.4f}")
    acc = obj.get("slo_accounted")
    if acc is not None and (not _int(acc) or acc < 0):
        errs.append(f"{name}: slo_accounted={acc!r} must be an int "
                    f">= 0")
        acc = None
    if acc is not None and _int(served) and acc > served:
        errs.append(
            f"{name}: slo_accounted={acc} > served={served} — the "
            f"SLO good fraction was computed over shed queries; SLO "
            f"accounting covers ADMITTED retirements only")
    # round-24 self-healing gauges: respawns/quarantines/mttr_s/
    # journal_replayed ride every chaos line (the fleet runs with
    # the resurrection supervisor + durable admission journal armed)
    missing24 = [k for k in ("respawns", "quarantines", "mttr_s",
                             "journal_replayed") if k not in obj]
    if missing24:
        errs.append(f"{name}: serve-chaos line missing the "
                    f"self-healing record {missing24}")
    resp = obj.get("respawns")
    if resp is not None and (not _int(resp) or resp < 0):
        errs.append(f"{name}: respawns={resp!r} must be an int >= 0")
        resp = None
    if resp is not None and resp > 0 and reps == 1:
        errs.append(
            f"{name}: respawns={resp} with replicas=1 — a "
            f"single-replica fleet that lost its only member had "
            f"nothing serving to detect the loss mid-drain, and the "
            f"line claims resurrections without a surviving "
            f"supervisor; the topology contradicts the record")
    quar = obj.get("quarantines")
    if quar is not None and (not _int(quar) or quar < 0):
        errs.append(f"{name}: quarantines={quar!r} must be an int "
                    f">= 0")
        quar = None
    mttr = obj.get("mttr_s")
    if mttr is not None and (not _is_num(mttr) or mttr < 0):
        errs.append(f"{name}: mttr_s={mttr!r} must be null or a "
                    f"finite number >= 0")
        mttr = None
    if mttr is not None and fo is not None and fo == 0 \
            and resp is not None and resp == 0:
        errs.append(
            f"{name}: mttr_s={mttr} with failovers=0 and "
            f"respawns=0 — repair time without any recorded loss or "
            f"repair; nothing was killed, so there is no outage to "
            f"time")
    jr = obj.get("journal_replayed")
    if jr is not None and (not _int(jr) or jr < 0):
        errs.append(f"{name}: journal_replayed={jr!r} must be an "
                    f"int >= 0")
        jr = None
    if jr is not None and _int(submitted) and jr > submitted:
        errs.append(
            f"{name}: journal_replayed={jr} > submitted="
            f"{submitted} — a recovery cannot re-dispatch more "
            f"admitted-unretired queries than were ever submitted; "
            f"the journal claims queries the load never offered")
    return errs


def check_serve_live_fields(name: str, obj: dict) -> list[str]:
    """Round-20 live-graph serving lines (see SERVE_LIVE_METRIC): the
    mutation/epoch/compaction/cache record must be present and free
    of the contradictions an honest live-serving run cannot produce
    — epochs that advanced without mutations (the monotone counter
    only moves when an append batch publishes), a hit fraction
    outside [0, 1], and a compaction count whose claimed trigger
    (delta occupancy crossing the threshold) never happened."""
    errs = []

    def _int(x) -> bool:
        return isinstance(x, int) and not isinstance(x, bool)

    missing = [k for k in ("mutations", "epochs_advanced",
                           "compactions", "cache_hit_fraction",
                           "peak_occupancy", "compact_threshold",
                           "deletions", "reweights", "reseeds",
                           "scheduler_compactions")
               if k not in obj]
    if missing:
        errs.append(f"{name}: serve-live line missing {missing}")
    muts = obj.get("mutations")
    if muts is not None and (not _int(muts) or muts < 0):
        errs.append(f"{name}: mutations={muts!r} must be an int "
                    f">= 0")
        muts = None
    # round-21 mutation-algebra fields: simple int >= 0 counters
    algebra = {}
    for k in ("deletions", "reweights", "reseeds",
              "scheduler_compactions"):
        v = obj.get(k)
        if v is not None and (not _int(v) or v < 0):
            errs.append(f"{name}: {k}={v!r} must be an int >= 0")
            v = None
        algebra[k] = v
    anti = (None
            if algebra["deletions"] is None
            or algebra["reweights"] is None
            else algebra["deletions"] + algebra["reweights"])
    # round-22: the headline line is weighted (the reweight leg of
    # the mutation algebra was previously exercised only by tests —
    # a headline carrying reweights=0 measures half the algebra).
    # ``weighted`` is optional (pre-round-22 artifacts omit it) but
    # present it must agree with the reweight counter both ways:
    # a reweight needs a weight array to rewrite, and a weighted
    # live line that never reweights is the regression this field
    # exists to catch.
    wtd = obj.get("weighted")
    if "weighted" in obj and not isinstance(wtd, bool):
        errs.append(f"{name}: weighted={wtd!r} must be a bool")
        wtd = None
    if wtd is False and algebra["reweights"] is not None \
            and algebra["reweights"] > 0:
        errs.append(
            f"{name}: reweights={algebra['reweights']} on an "
            f"UNWEIGHTED line — a reweight rewrites an edge's "
            f"weight; with no weight array the counter cannot have "
            f"moved (lux_tpu/livegraph.py)")
    if wtd is True and algebra["reweights"] == 0:
        errs.append(
            f"{name}: weighted=True with reweights=0 — the weighted "
            f"headline exists to exercise the reweight leg of the "
            f"mutation algebra; a weighted run that never reweights "
            f"is the round-22 regression this field guards against")
    if algebra["reseeds"] is not None and anti is not None \
            and algebra["reseeds"] > 0 and anti == 0:
        errs.append(
            f"{name}: reseeds={algebra['reseeds']} with "
            f"deletions=0 and reweights=0 — the anti-monotone "
            f"re-seed only runs past a published deletion/reweight; "
            f"a re-seed with nothing to re-seed FROM contradicts "
            f"the line's own mutation record")
    if muts is not None and anti is not None and anti > muts:
        errs.append(
            f"{name}: deletions+reweights={anti} > "
            f"mutations={muts} — every deletion/reweight IS a "
            f"mutation; the algebra counters exceed their own "
            f"total")
    eps = obj.get("epochs_advanced")
    if eps is not None and (not _int(eps) or eps < 0):
        errs.append(f"{name}: epochs_advanced={eps!r} must be an "
                    f"int >= 0")
        eps = None
    if eps is not None and muts is not None:
        if eps > 0 and muts == 0:
            errs.append(
                f"{name}: epochs_advanced={eps} with mutations=0 — "
                f"the monotone epoch counter only advances when a "
                f"mutation batch publishes; the line contradicts "
                f"its own ingest record")
        if muts > 0 and eps == 0:
            errs.append(
                f"{name}: mutations={muts} with epochs_advanced=0 — "
                f"every published append batch IS one epoch "
                f"advance; acknowledged mutations cannot be "
                f"epoch-invisible")
        if eps > muts:
            errs.append(
                f"{name}: epochs_advanced={eps} > mutations={muts} "
                f"— one epoch per PUBLISHED BATCH of >= 1 edge(s); "
                f"more epochs than edges is a contradiction")
    frac = obj.get("cache_hit_fraction")
    if frac is not None and (not _is_num(frac)
                             or not 0.0 <= frac <= 1.0):
        errs.append(f"{name}: cache_hit_fraction={frac!r} must be a "
                    f"finite number in [0, 1]")
    occ = obj.get("peak_occupancy")
    if occ is not None and (not _is_num(occ)
                            or not 0.0 <= occ <= 1.0):
        errs.append(f"{name}: peak_occupancy={occ!r} must be a "
                    f"finite number in [0, 1] (count/capacity of a "
                    f"fixed-capacity block)")
        occ = None
    thr = obj.get("compact_threshold")
    if thr is not None and (not _is_num(thr) or not 0.0 < thr <= 1.0):
        errs.append(f"{name}: compact_threshold={thr!r} must be a "
                    f"finite number in (0, 1]")
        thr = None
    comp = obj.get("compactions")
    if comp is not None and (not _int(comp) or comp < 0):
        errs.append(f"{name}: compactions={comp!r} must be an int "
                    f">= 0")
        comp = None
    if comp is not None and comp > 0 and occ is not None \
            and thr is not None and occ < thr - 1e-9 \
            and (anti is None or anti == 0):
        errs.append(
            f"{name}: compactions={comp} but peak_occupancy={occ} "
            f"never reached compact_threshold={thr} (and no "
            f"deletion/reweight was pending) — the trigger the line "
            f"claims fired could not have; occupancy and the "
            f"compaction count contradict each other")
    sched = algebra["scheduler_compactions"]
    if sched is not None and comp is not None and sched > comp:
        errs.append(
            f"{name}: scheduler_compactions={sched} > "
            f"compactions={comp} — every scheduler fold IS a "
            f"compaction; the scheduler cannot have folded more "
            f"often than the log compacted")
    if sched is not None and sched > 0 and anti is not None \
            and anti == 0 and occ is not None and thr is not None \
            and occ < thr - 1e-9:
        errs.append(
            f"{name}: scheduler_compactions={sched} with "
            f"deletions=0, reweights=0 and peak_occupancy={occ} "
            f"under compact_threshold={thr} — neither scheduler "
            f"trigger the line can evidence (pending anti-monotone "
            f"ops, occupancy) could have fired")
    cap = obj.get("delta_capacity")
    if cap is not None and (not _int(cap) or cap < 1):
        errs.append(f"{name}: delta_capacity={cap!r} must be an int "
                    f">= 1")
    return errs


def check_telemetry(name: str, obj: dict) -> list[str]:
    """Round-7 telemetry field: schema, runs-vs-attempts count, and
    each run's seconds re-deriving a recorded sample."""
    errs = []
    tel = obj["telemetry"]
    if not isinstance(tel, dict) or "runs" not in tel \
            or "counters" not in tel:
        return [f"{name}: telemetry must be a dict with 'runs' and "
                f"'counters', got {tel!r}"]

    runs = tel["runs"]
    if not isinstance(runs, list) or not runs or not all(
            isinstance(r, dict)
            and isinstance(r.get("repeat"), int) and r["repeat"] >= 0
            and isinstance(r.get("iters"), int) and r["iters"] >= 0
            and _is_num(r.get("seconds")) and r["seconds"] > 0
            for r in runs):
        return [f"{name}: telemetry.runs must be a non-empty list of "
                f"{{repeat>=0, iters>=0, seconds>0}}, got {runs!r}"]

    attempts = obj.get("attempts")
    # a retried config (run_attempts) or a crashed outlier rerun
    # (rerun_error) legitimately leaves timed_run events whose sample
    # never made it into the line — only require >= then
    loose = "run_attempts" in obj or "rerun_error" in obj
    if isinstance(attempts, int):
        if (len(runs) < attempts) or (not loose
                                      and len(runs) != attempts):
            errs.append(
                f"{name}: telemetry.runs has {len(runs)} timed runs "
                f"but attempts={attempts}"
                + ("" if loose else " (and the config was never "
                                    "retried)"))

    # per-run decomposition: ne*iters/seconds must land on a recorded
    # sample (kept or discarded) — the telemetry-era analogue of
    # 'per-segment seconds sum to the elapsed'
    ne = obj.get("ne")
    recorded = [s for s in (obj.get("samples") or []) if _is_num(s)] \
        + [d for d in (obj.get("discarded") or []) if _is_num(d)]
    if _is_num(ne) and recorded and not loose:
        for r in runs:
            if r["iters"] <= 0:
                continue
            implied = ne * r["iters"] / r["seconds"] / 1e9
            if min(abs(implied - s) for s in recorded) > 2e-4:
                errs.append(
                    f"{name}: run (repeat {r['repeat']}) implies "
                    f"{implied:.4f} GTEPS — matches no recorded "
                    f"sample; seconds and samples disagree")

    errs += check_health_digest(name, tel)
    errs += check_topology_digest(name, tel)
    errs += check_imbalance_digest(name, tel)

    cnt = tel["counters"]
    if cnt is not None:
        if (not isinstance(cnt, dict)
                or cnt.get("kind") not in ("push", "pull")
                or not isinstance(cnt.get("iters"), int)
                or cnt["iters"] < 0
                or not isinstance(cnt.get("truncated"), bool)):
            errs.append(f"{name}: telemetry.counters malformed: "
                        f"{cnt!r}")
        else:
            numeric = [k for k in ("frontier_last", "frontier_max",
                                   "frontier_sum", "edges_sum",
                                   "residual_first", "residual_last",
                                   "changed_last", "changed_sum")
                       if k in cnt and not _is_num(cnt[k])]
            if numeric:
                errs.append(f"{name}: telemetry.counters non-finite "
                            f"fields {numeric}")
    return errs


CAL_GRADES = ("canonical", "degraded", "uncalibrated")
CAL_DEVIATION_BOUND = 3.0     # lux_tpu/observe.py DEVIATION_BOUND


def check_calibration_field(name: str, obj: dict) -> list[str]:
    """Round-12 session-calibration digest (lux_tpu/observe.py,
    bench.py): a null field means the probe crashed — LOUDLY rejected
    (the line is unlabeled).  Present it must be well-formed AND
    grade "canonical": a "degraded" line was measured in a session
    whose reference probe ran >3x off the canonical figures (either
    direction, detected), and an "uncalibrated" line was
    measured on a platform with no canonical figures at all (e.g. the
    CPU test mesh) — neither may enter the trajectory silently.  A
    "canonical" grade contradicting its own deviation number is also
    rejected."""
    cal = obj["calibration"]
    if cal is None:
        return [f"{name}: calibration is null — the session probe "
                f"crashed, so the line is unlabeled and cannot enter "
                f"the trajectory (rerun; lux_tpu/observe.py)"]
    if not isinstance(cal, dict):
        return [f"{name}: calibration must be null or a dict, got "
                f"{cal!r}"]
    errs = []
    if not isinstance(cal.get("session"), str) or not cal.get("session"):
        errs.append(f"{name}: calibration.session must be a non-empty "
                    f"string, got {cal.get('session')!r}")
    for k in ("platform", "backend"):
        if not isinstance(cal.get(k), str):
            errs.append(f"{name}: calibration.{k} must be a string, "
                        f"got {cal.get(k)!r}")
    nd = cal.get("ndev")
    if not isinstance(nd, int) or isinstance(nd, bool) or nd < 1:
        errs.append(f"{name}: calibration.ndev={nd!r} must be an "
                    f"int >= 1")
    probe = cal.get("probe")
    if (not isinstance(probe, dict) or not probe
            or not all(_is_num(v) and v >= 0 for v in probe.values())):
        errs.append(f"{name}: calibration.probe must be a dict of "
                    f"finite measured figures, got {probe!r}")
    grade = cal.get("grade")
    dev = cal.get("deviation")
    if grade not in CAL_GRADES:
        errs.append(f"{name}: calibration.grade={grade!r} not one of "
                    f"{CAL_GRADES}")
    elif grade != "canonical":
        errs.append(
            f"{name}: metric line from a {grade.upper()} session "
            f"(probe deviation {dev!r}x vs canonical) — degraded or "
            f"uncalibrated samples never enter the bench trajectory "
            f"silently; rerun on the chip, and if the probe is "
            f"steadily off the canon re-measure the canon "
            f"(lux_tpu/observe.py)")
    if not _is_num(dev) or dev <= 0:
        errs.append(f"{name}: calibration.deviation={dev!r} must be "
                    f"a finite positive number")
    elif grade == "canonical" and (dev > CAL_DEVIATION_BOUND
                                   or dev < 1.0 / CAL_DEVIATION_BOUND):
        errs.append(
            f"{name}: calibration claims grade=canonical but "
            f"deviation={dev} is outside "
            f"[1/{CAL_DEVIATION_BOUND:g}, {CAL_DEVIATION_BOUND:g}]x "
            f"— the digest contradicts itself")
    aud = cal.get("audit")
    if not isinstance(aud, dict) or not all(
            isinstance(aud.get(k), int) and not isinstance(aud[k], bool)
            and aud[k] >= 0 for k in ("errors", "warnings")):
        errs.append(f"{name}: calibration.audit must be a dict with "
                    f"int errors/warnings >= 0, got {aud!r}")
    elif aud["errors"]:
        errs.append(
            f"{name}: calibration.audit records {aud['errors']} "
            f"error(s) — the probe programs failed their own static "
            f"audit (hoistable loop body / baked constant), so the "
            f"fingerprint measured nothing and cannot label a line")
    return errs


AUDIT_CHECKS = {"gather-budget", "const-bytes", "dtype-discipline",
                "loop-invariant", "collective-schedule",
                "callback-in-loop", "identity-init", "ledger-drift"}


def check_audit_field(name: str, obj: dict) -> list[str]:
    """Round-10 static-audit digest (bench.py -audit,
    lux_tpu/audit.py): optional (older artifacts and -audit off omit
    it); present it must be well-formed AND a clean bill — a metric
    line produced by an audit-failing build is rejected outright."""
    if "audit" not in obj:
        return []
    a = obj["audit"]
    if a is None:
        return []
    if not isinstance(a, dict):
        return [f"{name}: audit must be null or a dict, got {a!r}"]
    errs = []
    if a.get("mode") not in ("warn", "error"):
        errs.append(f"{name}: audit.mode={a.get('mode')!r} not "
                    f"warn|error")
    for k in ("errors", "warnings"):
        v = a.get(k)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errs.append(f"{name}: audit.{k}={v!r} must be an "
                        f"int >= 0")
    fc = a.get("failed_checks")
    if not isinstance(fc, list) or not all(isinstance(c, str)
                                           for c in fc):
        errs.append(f"{name}: audit.failed_checks must be a list of "
                    f"check names, got {fc!r}")
    else:
        unknown = sorted(set(fc) - AUDIT_CHECKS)
        if unknown:
            errs.append(f"{name}: audit.failed_checks has unknown "
                        f"checks {unknown}")
        if a.get("errors") or fc:
            errs.append(
                f"{name}: metric line produced by an -audit-FAILING "
                f"build (errors={a.get('errors')}, "
                f"failed_checks={fc}) — a number measured on a build "
                f"that violates the structural invariants cannot be "
                f"a metric of record (lux_tpu/audit.py)")
    return errs


COMM_TIERS = ("local", "ici", "dcn")


def check_comm_field(name: str, obj: dict) -> list[str]:
    """Round-19 comm-ledger digest (bench.py, lux_tpu/comms.py):
    optional (pre-round-19 artifacts and non-engine lines omit it);
    present it must be a clean, self-consistent byte bill.
    Contradiction rejects: a digest whose ledger FAILED its
    oracle/audit cross-check (errors > 0 — the number was measured on
    a build whose communication cannot be accounted), comm_frac
    outside [0, 1], bytes on a single device (ndev=1 ships nothing),
    a mesh owner/gather exchange shipping ZERO bytes (the exchange's
    collectives cannot be free), and a per-edge figure disagreeing
    with bytes_per_iter * ndev / ne."""
    if "comm" not in obj:
        return []
    c = obj["comm"]
    if c is None:
        return [f"{name}: comm digest is null — the ledger never "
                f"ran, so the line's communication is unaccounted "
                f"(lux_tpu/comms.py)"]
    if not isinstance(c, dict):
        return [f"{name}: comm must be a dict, got {c!r}"]
    errs = []
    ce = c.get("errors")
    if not isinstance(ce, int) or isinstance(ce, bool) or ce < 0:
        errs.append(f"{name}: comm.errors={ce!r} must be an int >= 0")
        return errs
    if ce:
        errs.append(
            f"{name}: comm digest from a LEDGER-FAILING build "
            f"(errors={ce}{': ' + str(c.get('error')) if c.get('error') else ''}) "
            f"— a metric whose byte bill failed its oracle/audit "
            f"cross-check cannot stand (lux_tpu/comms.py)")
        return errs
    nd = c.get("ndev")
    if not isinstance(nd, int) or isinstance(nd, bool) or nd < 1:
        errs.append(f"{name}: comm.ndev={nd!r} must be an int >= 1")
        nd = None
    tier = c.get("tier")
    if tier not in COMM_TIERS:
        errs.append(f"{name}: comm.tier={tier!r} not one of "
                    f"{COMM_TIERS}")
    bpi = c.get("bytes_per_iter")
    if not isinstance(bpi, int) or isinstance(bpi, bool) or bpi < 0:
        errs.append(f"{name}: comm.bytes_per_iter={bpi!r} must be an "
                    f"int >= 0")
        bpi = None
    msgs = c.get("messages")
    if not isinstance(msgs, int) or isinstance(msgs, bool) or msgs < 0:
        errs.append(f"{name}: comm.messages={msgs!r} must be an "
                    f"int >= 0")
        msgs = None
    frac = c.get("comm_frac")
    if not _is_num(frac) or not 0.0 <= frac <= 1.0:
        errs.append(f"{name}: comm.comm_frac={frac!r} must be a "
                    f"finite number in [0, 1] (the modeled comm "
                    f"share of one iteration)")
    bpe = c.get("comm_bytes_per_edge")
    if not _is_num(bpe) or bpe < 0:
        errs.append(f"{name}: comm.comm_bytes_per_edge={bpe!r} must "
                    f"be a finite number >= 0")
        bpe = None
    if nd == 1:
        if bpi:
            errs.append(
                f"{name}: comm.bytes_per_iter={bpi} on a SINGLE "
                f"device — one device has no link to ship over; the "
                f"digest contradicts its own placement")
        if msgs:
            errs.append(
                f"{name}: comm.messages={msgs} on a single device — "
                f"no mesh axis exists to launch collectives over")
        if tier in ("ici", "dcn"):
            errs.append(f"{name}: comm.tier={tier!r} with ndev=1 — a "
                        f"single device sits on no link tier")
    ex = c.get("exchange")
    if nd is not None and nd > 1 and ex in ("owner", "gather") \
            and bpi == 0:
        errs.append(
            f"{name}: comm.bytes_per_iter=0 with exchange={ex!r} on "
            f"{nd} devices — the {ex} exchange's collectives cannot "
            f"ship zero bytes; the digest contradicts the exchange "
            f"mode")
    ne = obj.get("ne")
    if _is_num(ne) and ne > 0 and bpi is not None and bpe is not None \
            and nd is not None:
        want = bpi * nd / ne
        if abs(bpe - want) > 1e-4 * max(1.0, want):
            errs.append(
                f"{name}: comm.comm_bytes_per_edge={bpe} disagrees "
                f"with bytes_per_iter * ndev / ne = {want:.6f} — the "
                f"per-edge claim contradicts the per-iteration bill")
    return errs


MEM_GRADES = ("measured", "modeled")


def check_mem_field(name: str, obj: dict) -> list[str]:
    """Round-22 memory digest (bench.py, lux_tpu/memwatch.py):
    optional (pre-round-22 artifacts omit it); present it must be a
    clean watermark-vs-ledger verdict.  Rejects: a null digest (the
    observatory never ran, so the line's bytes are unaccounted), a
    drifting digest (errors > 0 — the measured peak disagrees with
    the unified byte ledger beyond tolerance, so the run's memory
    cannot be accounted), an unknown grade, a ratio that contradicts
    its own errors=0 claim, and byte counts that are not ints."""
    if "mem" not in obj:
        return []
    m = obj["mem"]
    if m is None:
        return [f"{name}: mem digest is null — the memory "
                f"observatory never ran, so the line's bytes are "
                f"unaccounted (lux_tpu/memwatch.py)"]
    if not isinstance(m, dict):
        return [f"{name}: mem must be null or a dict, got {m!r}"]
    errs = []
    me = m.get("errors")
    if not isinstance(me, int) or isinstance(me, bool) or me < 0:
        errs.append(f"{name}: mem.errors={me!r} must be an int >= 0")
        return errs
    if me:
        errs.append(
            f"{name}: mem digest from a DRIFTING build (errors={me}"
            f"{': ' + str(m.get('error')) if m.get('error') else ''}) "
            f"— a metric whose measured peak disagrees with its own "
            f"byte ledger cannot stand (lux_tpu/memwatch.py)")
        return errs
    if m.get("error"):
        # digest construction failed; _mem_build records the message
        # with errors=1, so errors=0 alongside an error string is a
        # self-contradiction
        errs.append(f"{name}: mem.error={m.get('error')!r} with "
                    f"errors=0 — a failed digest cannot claim a "
                    f"clean bill")
        return errs
    grade = m.get("grade")
    if grade not in MEM_GRADES:
        errs.append(f"{name}: mem.grade={grade!r} not one of "
                    f"{MEM_GRADES}")
    skipped = m.get("skipped")
    if "skipped" in m and not isinstance(skipped, str):
        errs.append(f"{name}: mem.skipped={skipped!r} must be a "
                    f"string (the withheld-verdict reason)")
    if "skipped" in m and not m.get("warnings"):
        errs.append(f"{name}: mem digest skipped "
                    f"({skipped!r}) with warnings=0 — a withheld "
                    f"verdict must count as a warning")
    lb = m.get("ledger_bytes")
    if not isinstance(lb, int) or isinstance(lb, bool) or lb < 0:
        errs.append(f"{name}: mem.ledger_bytes={lb!r} must be an "
                    f"int >= 0")
    # a skipped digest (backend without AOT stats, or a shape under
    # the check floor) withholds the verdict: peak/ratio may be
    # absent or out-of-tolerance and the warning count says why
    pk = m.get("peak_bytes")
    if "skipped" not in m and (not isinstance(pk, int)
                               or isinstance(pk, bool) or pk < 0):
        errs.append(f"{name}: mem.peak_bytes={pk!r} must be an "
                    f"int >= 0")
    tol = m.get("tol")
    if not _is_num(tol) or tol <= 0:
        errs.append(f"{name}: mem.tol={tol!r} must be a finite "
                    f"number > 0")
        tol = None
    ratio = m.get("ratio")
    if "skipped" not in m and (not _is_num(ratio) or ratio < 0):
        errs.append(f"{name}: mem.ratio={ratio!r} must be a finite "
                    f"number >= 0")
        ratio = None
    if _is_num(ratio) and tol is not None and "skipped" not in m \
            and not (1.0 / (1.0 + tol) - 1e-9 <= ratio
                     <= 1.0 + tol + 1e-9):
        errs.append(
            f"{name}: mem.ratio={ratio} outside [1/(1+tol), 1+tol] "
            f"for tol={tol} with errors=0 — the digest contradicts "
            f"its own clean verdict (lux_tpu/memwatch.py drift "
            f"tolerance)")
    return errs


HEALTH_FLAGS = {"nonfinite_state", "nonfinite_residual", "divergence",
                "oscillation", "frontier_stall"}


def check_health_digest(name: str, tel: dict) -> list[str]:
    """Round-9 watchdog digest (bench.py -health): optional (older
    artifacts predate it), null when the watchdog was off; present it
    must be {engine: push|pull, tripped: bool, flags: [known names],
    iters: int >= 0}.  tripped=true with no flags — or flags on a
    clean line at all — is a contradiction: a tripped watchdog fails
    the config, so a metric line's digest must be a clean bill."""
    if "health" not in tel:
        return []
    h = tel["health"]
    if h is None:
        return []
    if not isinstance(h, dict):
        return [f"{name}: telemetry.health must be null or a dict, "
                f"got {h!r}"]
    errs = []
    if h.get("engine") not in ("push", "pull"):
        errs.append(f"{name}: telemetry.health.engine="
                    f"{h.get('engine')!r} not push|pull")
    if not isinstance(h.get("tripped"), bool):
        errs.append(f"{name}: telemetry.health.tripped must be a "
                    f"bool, got {h.get('tripped')!r}")
    flags = h.get("flags")
    if (not isinstance(flags, list)
            or not all(isinstance(f, str) for f in flags)):
        errs.append(f"{name}: telemetry.health.flags must be a list "
                    f"of check names, got {flags!r}")
    else:
        unknown = sorted(set(flags) - HEALTH_FLAGS)
        if unknown:
            errs.append(f"{name}: telemetry.health.flags has unknown "
                        f"checks {unknown}")
        if h.get("tripped") is True or flags:
            errs.append(
                f"{name}: telemetry.health reports a TRIP "
                f"(tripped={h.get('tripped')}, flags={flags}) — a "
                f"tripped watchdog fails its config with a _FAILED "
                f"line and cannot publish a metric line")
    it = h.get("iters")
    if not isinstance(it, int) or isinstance(it, bool) or it < 0:
        errs.append(f"{name}: telemetry.health.iters={it!r} must be "
                    f"an int >= 0")
    return errs


def check_imbalance_digest(name: str, tel: dict) -> list[str]:
    """Round-13 per-part imbalance digest (lux_tpu/tracing.py era,
    telemetry.IterStats.imbalance_digest): optional (older artifacts
    predate it), null when -iter-stats was off.  Present it must be
    {kind: push|pull, index: finite >= 1, parts: non-empty list of
    ints >= 0}, the index must equal max/mean of its own parts (to
    rounding), and — the health-digest contradiction pattern — the
    parts must SUM to the scalar counter digest's edges_sum (push) /
    changed_sum (pull): a published imbalance that contradicts the
    counters it claims to decompose is rejected."""
    if "imbalance" not in tel:
        return []
    imb = tel["imbalance"]
    if imb is None:
        return []
    if not isinstance(imb, dict):
        return [f"{name}: telemetry.imbalance must be null or a "
                f"dict, got {imb!r}"]
    errs = []
    kind = imb.get("kind")
    if kind not in ("push", "pull"):
        errs.append(f"{name}: telemetry.imbalance.kind={kind!r} not "
                    f"push|pull")
    parts = imb.get("parts")
    ints = (isinstance(parts, list) and parts
            and all(isinstance(p, int) and not isinstance(p, bool)
                    and p >= 0 for p in parts))
    if not ints:
        errs.append(f"{name}: telemetry.imbalance.parts must be a "
                    f"non-empty list of ints >= 0, got {parts!r}")
    idx = imb.get("index")
    if not _is_num(idx) or idx < 1.0 - 1e-9:
        errs.append(f"{name}: telemetry.imbalance.index={idx!r} must "
                    f"be a finite number >= 1 (max/mean)")
    elif ints:
        mean = sum(parts) / len(parts)
        if mean <= 0:
            errs.append(f"{name}: telemetry.imbalance over zero "
                        f"total work — a digest with no work cannot "
                        f"carry an index")
        elif abs(idx - max(parts) / mean) > 1e-3 * max(
                1.0, max(parts) / mean):
            errs.append(
                f"{name}: telemetry.imbalance.index={idx} "
                f"contradicts its own parts (max/mean = "
                f"{max(parts) / mean:.4f})")
    cnt = tel.get("counters")
    if ints and isinstance(cnt, dict) and cnt.get("kind") == kind:
        scalar = cnt.get("edges_sum" if kind == "push"
                         else "changed_sum")
        # congruence mod 2^32: the scalar series entries are device
        # uint32 sums (wrapping past 2^32 edges in one iteration on
        # billion-edge graphs) while the parts totals sum exactly on
        # the host — Σ(wrapped) ≡ Σ(exact) (mod 2^32) always holds
        # for an honest line
        if isinstance(scalar, int) and not isinstance(scalar, bool) \
                and (sum(parts) - scalar) % (1 << 32):
            errs.append(
                f"{name}: telemetry.imbalance parts sum "
                f"{sum(parts)} contradicts the counter digest's "
                f"scalar {scalar} (mod 2^32) — per-part and scalar "
                f"counters are the same device-side values and must "
                f"agree")
    return errs


def check_topology_digest(name: str, tel: dict) -> list[str]:
    """Round-11 elastic-recovery digest (bench.py, lux_tpu/
    resilience.py): optional (older artifacts predate it), null when
    the mesh never changed.  Present-and-nonnull it must be
    {shrinks: int >= 1, ndev_final: int >= 1} — and it FAILS the
    line: a mid-run mesh shrink means the number was measured partly
    on N devices and partly on fewer, so a degraded-mesh GTEPS must
    never publish as (or be compared against) a full-mesh metric
    line.  Rerun on the stable topology instead."""
    if "topology" not in tel:
        return []
    topo = tel["topology"]
    if topo is None:
        return []
    if not isinstance(topo, dict):
        return [f"{name}: telemetry.topology must be null or a dict, "
                f"got {topo!r}"]
    errs = []
    sh = topo.get("shrinks")
    if not isinstance(sh, int) or isinstance(sh, bool) or sh < 1:
        # a null digest means "no shrink"; a non-null one must record
        # at least one — shrinks=0 here would be a digest that claims
        # degradation happened while dodging the rejection below
        errs.append(f"{name}: telemetry.topology.shrinks={sh!r} must "
                    f"be an int >= 1 (a null digest means no shrink)")
        sh = None
    nf = topo.get("ndev_final")
    if nf is not None and (not isinstance(nf, int)
                           or isinstance(nf, bool) or nf < 1):
        errs.append(f"{name}: telemetry.topology.ndev_final={nf!r} "
                    f"must be an int >= 1")
    if sh:
        errs.append(
            f"{name}: telemetry.topology records {sh} mid-run mesh "
            f"shrink(s) (final ndev {nf}) — a degraded-mesh GTEPS "
            f"must never be compared against full-mesh lines; rerun "
            f"the config on the stable topology")
    return errs


def iter_event_lines(path: str):
    """Telemetry event objects ({"t": ..., "kind": ...} JSONL, the
    -events FILE format) — so an event log handed to this checker
    audits as events instead of failing as 'no metric lines'."""
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict) and "kind" in obj \
                    and "metric" not in obj:
                yield f"line {i}", obj


def check_event_lines(path: str, events):
    """Minimal schema for a telemetry event log: string kind, numeric
    timestamp, numeric seconds where present."""
    errs = []
    for where, ev in events:
        if not isinstance(ev.get("kind"), str):
            errs.append(f"{path} ({where}): event kind must be a "
                        f"string, got {ev.get('kind')!r}")
        if not _is_num(ev.get("t")):
            errs.append(f"{path} ({where}): event without a numeric "
                        f"'t' timestamp")
        if "seconds" in ev and not _is_num(ev["seconds"]):
            errs.append(f"{path} ({where}): non-finite seconds "
                        f"{ev['seconds']!r}")
        if ev.get("kind") == "span" and not (
                isinstance(ev.get("name"), str)
                and _is_num(ev.get("t0")) and _is_num(ev.get("t1"))
                and ev["t1"] >= ev["t0"]
                and isinstance(ev.get("counts"), dict)):
            errs.append(f"{path} ({where}): span event needs a string "
                        f"name, numeric t0 <= t1 and a counts object")
    return errs


def check_reorder_pairs(lines) -> list[str]:
    """Cross-line audit of the round-16 reorder A/B (bench.py
    -reorder emits each reordered gather-ab line TOGETHER with its
    paired none baseline): for every reordered line whose paired
    none line (same gather mode, shape and scale) is in the same
    artifact, the measured ``page_fill`` must not DECREASE under the
    reorder — the reorder pass hill-climbs exactly this objective
    (lux_tpu/reorder.py), so a published pair where it fell is
    either a mislabeled line or a broken reorderer, both rejected."""
    errs = []
    by_key = {}
    for where, obj in lines:
        name = obj.get("metric", "")
        m = GATHER_AB_METRIC.match(name)
        if not m or not _is_num(obj.get("page_fill")):
            continue
        mode, ro, tag, scale = (m.group(1), m.group(2) or "none",
                                m.group(3), m.group(4))
        # num_parts is part of the pairing identity: padded fill
        # legitimately shifts with the common depth profile across
        # parts, so a cross-np comparison would reject correct data.
        # Keep EVERY line per key (repeated sessions all check).
        key = (mode, tag, scale, obj.get("np"))
        by_key.setdefault(key, {}).setdefault(ro, []).append(
            (where, name, obj["page_fill"]))
    for key, by_ro in by_key.items():
        for ro, entries in by_ro.items():
            if ro == "none":
                continue
            for where, name, pf in entries:
                for _bw, bname, bpf in by_ro.get("none", []):
                    if pf < bpf - 1e-9:
                        errs.append(
                            f"({where}): {name}: page_fill={pf} "
                            f"DECREASED vs its paired none line "
                            f"{bname} ({bpf}) — the reorder "
                            f"hill-climbs fill, a drop contradicts "
                            f"the published pair")
    return errs


def check_mxu_pairs(lines) -> list[str]:
    """Cross-line audit of the round-23 MXU A/B (bench.py -config
    mxu-ab always emits both sides): an mxu line may only publish
    NEXT TO its paired vpu baseline — same scale and num_parts, in
    the same artifact — and the pair must carry IDENTICAL modeled
    rates (both sides stamp the rates for both paths from one
    payload width, so a disagreement means the lines are not the
    same experiment).  A lone MXU number has no step-change to show
    and is rejected, the same pairing rule as the reorder A/B."""
    errs = []
    by_key = {}
    for where, obj in lines:
        m = MXU_AB_METRIC.match(obj.get("metric", ""))
        if not m:
            continue
        key = (m.group(2), obj.get("np"))
        by_key.setdefault(key, {}).setdefault(m.group(1), []).append(
            (where, obj.get("metric"), obj))
    for key, by_mode in by_key.items():
        for where, name, obj in by_mode.get("mxu", []):
            base = by_mode.get("vpu", [])
            if not base:
                errs.append(
                    f"({where}): {name}: mxu line has NO paired vpu "
                    f"baseline (same comm scale + np) in the "
                    f"artifact — a lone MXU number has no "
                    f"step-change to show")
                continue
            for _bw, bname, bobj in base:
                for k in ("mxu_row_ns", "vpu_row_ns"):
                    a, b = obj.get(k), bobj.get(k)
                    if _is_num(a) and _is_num(b) \
                            and abs(a - b) > 1e-9:
                        errs.append(
                            f"({where}): {name}: {k}={a} disagrees "
                            f"with its paired baseline {bname} "
                            f"({b}) — the sides modeled different "
                            f"payload widths; the pair is not one "
                            f"experiment")
    return errs


def check_file(path: str, *, legacy_ok: bool):
    errs, warns, n = [], [], 0
    try:
        lines = list(iter_metric_lines(path))
    except (OSError, UnicodeDecodeError) as e:
        return [f"{path}: unreadable ({e})"], [], 0
    if not lines:
        events = list(iter_event_lines(path))
        if events:
            return check_event_lines(path, events), [], len(events)
        return [f"{path}: no metric lines found"], [], 0
    for where, obj in lines:
        n += 1
        e, w = check_line(obj, legacy_ok=legacy_ok)
        errs += [f"{path} ({where}): {m}" for m in e]
        warns += [f"{path} ({where}): {m}" for m in w]
    errs += [f"{path} {m}" for m in check_reorder_pairs(lines)]
    errs += [f"{path} {m}" for m in check_mxu_pairs(lines)]
    return errs, warns, n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate bench metric lines against the "
                    "round-6 resilience schema")
    ap.add_argument("files", nargs="+", metavar="FILE")
    ap.add_argument("-legacy-ok", action="store_true",
                    dest="legacy_ok",
                    help="downgrade pre-round-6 metadata gaps "
                         "(missing samples/attempts/discarded) to "
                         "warnings — for auditing pre-round-6 "
                         "artifacts")
    args = ap.parse_args(argv)

    total_errs, total = [], 0
    for path in args.files:
        errs, warns, n = check_file(path, legacy_ok=args.legacy_ok)
        total += n
        total_errs += errs
        for w in warns:
            print(f"WARNING: {w}", file=sys.stderr)
    for e in total_errs:
        print(f"ERROR: {e}", file=sys.stderr)
    if total_errs:
        print(f"check_bench: {len(total_errs)} error(s) over {total} "
              f"metric line(s) — the bench schema audit FAILED",
              file=sys.stderr)
        return 1
    print(f"check_bench: {total} metric line(s) OK "
          f"({len(args.files)} file(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
