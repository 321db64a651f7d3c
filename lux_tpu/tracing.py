"""Run tracing & imbalance attribution: span timeline with Perfetto
export, plus the crash flight recorder.

The reference's whole observability story is per-part wall-clock
prints (reference sssp_gpu.cu:513-518, pagerank.cc:108-118); lux_tpu
rounds 7/9/11/12 built a structured event substrate (telemetry.py:
every event carries monotonic ``tm`` + ``pid`` + ``session``), but the
log stayed FLAT — no causality, no cross-process timeline, and a dead
run left no postmortem artifact.  This module is the attribution
layer on top of that substrate, three pillars:

1. **Span model + Perfetto export** (``trace_export``): reconstruct
   the run -> attempt -> segment/timed-run hierarchy from an
   event stream and emit Chrome-trace/Perfetto JSON
   (``chrome://tracing`` / ui.perfetto.dev loadable).  One trace
   process per (session, pid) stream — heartbeat drills appending
   several OS processes into one file become side-by-side tracks —
   with per-stream wall/monotonic alignment (``tm`` orders within a
   process, the median ``t - tm`` offset aligns across processes).
   Events carrying fenced ``seconds`` (segment, timed_run,
   checkpoint_save) become duration spans ending at their emit time;
   heartbeat/topology/retry/health/budget events become instant
   markers; and an elastic ``mesh_shrink`` moves subsequent execution
   spans onto a NEW track (a visible track transition at the moment
   the mesh changed).  ``validate_trace`` machine-checks the output:
   spans properly nest per track and every non-run span lies inside a
   run span (no orphans).

2. **Per-part counters** live in the engines (round 13 additions to
   the ``*_stats``/``*_health`` loop variants, lux_tpu/engine/*.py)
   and in telemetry.IterStats (``part_totals``/``imbalance``); this
   module's drills exercise them end-to-end and the export carries
   the ``iter_stats`` digest (imbalance index + per-part totals) on
   the run span.

3. **Crash flight recorder** (``FlightRecorder``): a bounded
   in-memory ring of recent events plus the last health word,
   calibration fingerprint and placement metadata, fed by a
   telemetry observer and dumped ATOMICALLY to ``FLIGHT.json`` by the
   resilience supervisor on FATAL failures (HealthError included) and
   topology faults — a run that dies mid-flight leaves a
   diagnosable artifact.  ``scripts/events_summary.py -flight``
   renders it.

CLI (``python -m lux_tpu.tracing``):

- no arguments: the tier-1 smoke — run the four apps on small CPU
  graphs with telemetry + per-part counters and export ``trace.json``.
- ``FILE...``: export existing ``-events`` JSONL file(s).
- ``-drill``: the 8-virtual-device elastic worker-kill drill — two
  jax.distributed subprocesses (4 CPU devices each) run a
  heartbeat-supervised checkpointed pagerank sharing ONE event file;
  worker 1 is hard-killed mid-run, worker 0 detects the death at the
  heartbeat deadline and agrees on the shrunken topology, and the
  solo relaunch resumes from the shared checkpoint (``replace``
  event).  The merged two-process timeline exports as one trace.  On
  jaxlib CPU builds without multi-process collectives the drill
  falls back to the in-process DEVICE_LOSS elastic drill (same
  recovery machinery, one process).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import sys
import tempfile
import time
from statistics import median

from lux_tpu import telemetry

SCHEMA = 1

# event kinds whose fenced ``seconds`` is a real duration ending at
# the emit time -> duration spans on the execution track
SPAN_KINDS = {"segment", "timed_run", "checkpoint_save"}
# run boundaries (one CLI invocation / bench config each)
RUN_BOUNDARIES = ("run_start", "config_start")
# instant markers promoted to PROCESS scope (big visual arrows)
PROCESS_INSTANTS = {"mesh_shrink", "topology_fault", "replace",
                    "failure", "health_trip", "flight_dump"}

# per-query serving spans (round 17): query tracks start here, one
# LANE per set of non-overlapping queries (greedy interval packing —
# an oversubscribed load renders as stacked lanes whose depth IS the
# concurrency), leaving tid 1..99 to the execution epochs.  Round 18
# (serving fleet, lux_tpu/fleet.py): lanes group PER REPLICA — each
# replica group gets a contiguous tid range starting at
# QUERY_TID_BASE, sized max(QUERY_REPLICA_STRIDE, its lane count)
# (so small traces keep stable base+group*stride tids and a deep
# group can never collide into the next group's range), and a
# failover renders as the qid's span SPLITTING onto the new
# replica's track group (the round-13 mesh-shrink epoch pattern
# applied to query lanes; ``validate_trace`` machine-checks the
# transition).
QUERY_TID_BASE = 100
QUERY_REPLICA_STRIDE = 40
# program spans (telemetry.span ``span`` events): lanes from here, one
# per set of non-overlapping ROOT spans; a child rides its parent's
# lane, so the nesting the records carry is the nesting drawn
SPAN_TID_BASE = 50


def _num(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and x == x and abs(x) != float("inf")


# ---------------------------------------------------------------------
# event loading / stream splitting (wire format of telemetry.EventLog)

def load_events(path: str):
    """Tolerant JSONL load -> (events, errors)."""
    events, errs = [], []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError as e:
                errs.append(f"{path}:{i}: unparseable JSON ({e})")
                continue
            if not isinstance(ev, dict) or "kind" not in ev:
                errs.append(f"{path}:{i}: event without a 'kind'")
                continue
            events.append(ev)
    if not events and not errs:
        errs.append(f"{path}: no events found")
    return events, errs


def split_streams(events):
    """[(key, events)] per (session, pid) stream in first-appearance
    order — the round-12 merge key; legacy events (no session/pid)
    share the ``None`` stream."""
    streams, order = {}, []
    for ev in events:
        key = None
        if "session" in ev or "pid" in ev:
            key = (ev.get("session"), ev.get("pid"))
        if key not in streams:
            streams[key] = []
            order.append(key)
        streams[key].append(ev)
    return [(k, streams[k]) for k in order]


def split_runs(events):
    """Group one stream into runs at run_start/config_start
    boundaries; a log without boundary events is one anonymous run.
    Program spans that precede a boundary and nothing else (a run's
    load / relabel / build, done before its ``run_start``) belong to
    the run they prepare."""
    runs, cur = [], []
    for ev in events:
        if ev["kind"] in RUN_BOUNDARIES and cur \
                and not all(e["kind"] == "span" for e in cur):
            runs.append(cur)
            cur = []
        cur.append(ev)
    if cur:
        runs.append(cur)
    return runs


# ---------------------------------------------------------------------
# pillar 1: span reconstruction + Chrome-trace/Perfetto export

@dataclasses.dataclass
class _Track:
    """Mutable per-stream export state (epoch = mesh-shrink count:
    execution spans after a shrink move to a new tid — the track
    transition that makes an elastic drill readable)."""
    pid: int
    epoch: int = 0
    shrink_labels: dict = dataclasses.field(default_factory=dict)


def _stream_offset(evs) -> float:
    """Median wall-minus-monotonic offset: aligns this process's
    monotonic timestamps onto the (roughly) shared wall clock."""
    ds = [ev["t"] - ev["tm"] for ev in evs
          if _num(ev.get("t")) and _num(ev.get("tm"))]
    return median(ds) if ds else 0.0


def _ats(ev, off) -> float | None:
    """Aligned absolute seconds of one event (monotonic + offset;
    wall-clock fallback for legacy events)."""
    if _num(ev.get("tm")):
        return ev["tm"] + off
    if _num(ev.get("t")):
        return ev["t"]
    return None


def _span(name, cat, ts, dur, pid, tid, args=None) -> dict:
    out = {"name": str(name), "cat": cat, "ph": "X",
           "ts": round(ts, 1), "dur": round(max(dur, 0.0), 1),
           "pid": pid, "tid": tid}
    if args:
        out["args"] = args
    return out


def _instant(name, ts, pid, tid, scope="t", args=None) -> dict:
    out = {"name": str(name), "cat": "marker", "ph": "i",
           "ts": round(ts, 1), "pid": pid, "tid": tid, "s": scope}
    if args:
        out["args"] = args
    return out


def _meta(name, pid, value, tid=None) -> dict:
    out = {"name": name, "ph": "M", "pid": pid,
           "args": {"name" if name.endswith("_name")
                    else "sort_index": value}}
    if tid is not None:
        out["tid"] = tid
    return out


def _clamp(ts, dur, lo, hi):
    ts = min(max(ts, lo), hi)
    return ts, max(0.0, min(dur, hi - ts))


def _span_name(ev) -> str:
    k = ev["kind"]
    if k == "segment":
        n = ev.get("n", ev.get("iters"))
        return f"segment[{ev.get('engine', '?')} n={n}]"
    if k == "timed_run":
        return f"timed_run[{ev.get('repeat', 0)}]"
    return k


def _run_spans(run, us, trk: _Track, te: list):
    """Emit one run's spans into ``te``: the run span + attempt spans
    on tid 0, execution spans on tid 1+epoch, everything else
    as instant markers.  Child spans are clamped into the run extent
    so the nesting invariant holds by construction."""
    times = [us(ev) for ev in run]
    rstart, rend = min(times), max(times)
    head = run[0] if run[0]["kind"] in RUN_BOUNDARIES else {}
    name = head.get("app") or head.get("config") or "run"
    args = {k: head[k] for k in ("app", "config", "file", "mesh",
                                 "drill", "worker") if k in head}
    # the counters digest (imbalance + per-part totals) rides the run
    # span so Perfetto's selection panel shows the attribution
    for ev in run:
        if ev["kind"] == "iter_stats":
            args["iter_stats"] = {
                k: v for k, v in ev.items()
                if k in ("engine", "iters", "imbalance", "parts",
                         "parts_edges", "parts_changed", "edges_sum",
                         "changed_sum")}
    te.append(_span(name, "run", rstart, rend - rstart, trk.pid, 0,
                    args=args or None))
    # attempt spans: boundaries at retry / handled-topology events
    # (supervise() retries immediately after a handled topology fault
    # and after the retry backoff otherwise)
    bounds = [rstart]
    for ev, ts in zip(run, times):
        if ev["kind"] == "retry" or (ev["kind"] == "topology_fault"
                                     and ev.get("handled")):
            bounds.append(ts)
    bounds.append(rend)
    for i in range(len(bounds) - 1):
        a, b = bounds[i], bounds[i + 1]
        te.append(_span(f"attempt {i}", "attempt", a, b - a,
                        trk.pid, 0))

    for ev, ts in zip(run, times):
        kind = ev["kind"]
        tid = 1 + trk.epoch
        if kind in SPAN_KINDS and _num(ev.get("seconds")):
            dur = ev["seconds"] * 1e6
            s, d = _clamp(ts - dur, dur, rstart, rend)
            te.append(_span(_span_name(ev), "exec", s, d, trk.pid,
                            tid, args={k: v for k, v in ev.items()
                                       if k in ("n", "done", "iters",
                                                "total", "active",
                                                "repeat", "iter",
                                                "path", "engine")}))
        elif kind == "mem_sample":
            # round-22 memory observatory: the occupancy trail draws
            # as a Chrome COUNTER track ("C" phase) — live bytes +
            # the peak watermark as stacked series, one counter row
            # per replica when the sample is labeled
            cname = "memory"
            if ev.get("replica"):
                cname = f"memory:{ev['replica']}"
            te.append({"name": cname, "ph": "C", "ts": ts,
                       "pid": trk.pid,
                       "args": {"live_bytes":
                                int(ev.get("live_bytes", 0)),
                                "peak_bytes":
                                int(ev.get("peak_bytes", 0))}})
        elif kind in RUN_BOUNDARIES:
            pass                       # represented by the run span
        elif kind == "span":
            pass                       # drawn by _program_spans
        else:
            scope = "p" if kind in PROCESS_INSTANTS else "t"
            iargs = {k: v for k, v in ev.items()
                     if k not in ("t", "tm", "pid", "session", "kind")
                     and isinstance(v, (int, float, str, bool))}
            te.append(_instant(kind, ts, trk.pid, tid, scope=scope,
                               args=iargs or None))
        if kind == "mesh_shrink":
            trk.epoch += 1
            to = ev.get("to_ndev", ev.get("to_nproc"))
            trk.shrink_labels[trk.epoch] = (
                f"exec (after shrink #{trk.epoch}"
                + (f", ndev={to}" if _num(to) else "") + ")")
    _program_spans(run, times, trk, te, rstart, rend)
    _query_spans(run, times, trk, te, rstart, rend)


def _program_spans(run, times, trk: _Track, te: list, rstart, rend):
    """``span`` events (telemetry.span records: id, parent, t0, t1 on
    ``perf_counter``) as complete events, each NESTED under its
    parent: the stream's perf_counter-to-trace offset is the median
    of (emit time - t1), roots pack greedily onto ``spans.N`` lanes
    and a child takes its parent's lane.  Zero-length records
    (``telemetry.mark``) become instants on that lane."""
    recs = [(ev, ts) for ev, ts in zip(run, times)
            if ev["kind"] == "span" and _num(ev.get("t0"))
            and _num(ev.get("t1")) and ev["t1"] >= ev["t0"]]
    if not recs:
        return
    off = median(ts - ev["t1"] * 1e6 for ev, ts in recs)
    lane_of, lane_ends = {}, []
    for ev, _ts in sorted(recs, key=lambda r: (r[0]["t0"],
                                                -r[0]["t1"])):
        s, d = _clamp(ev["t0"] * 1e6 + off,
                      (ev["t1"] - ev["t0"]) * 1e6, rstart, rend)
        lane = lane_of.get(ev.get("parent"))
        if lane is None:
            lane = next((i for i, end in enumerate(lane_ends)
                         if end <= s), len(lane_ends))
            if lane == len(lane_ends):
                lane_ends.append(s + d)
                te.append(_meta("thread_name", trk.pid,
                                f"spans.{lane}",
                                tid=SPAN_TID_BASE + lane))
            lane_ends[lane] = max(lane_ends[lane], s + d)
        lane_of[ev.get("id")] = lane
        args = dict(ev.get("counts") or {}, id=ev.get("id"),
                    parent=ev.get("parent"))
        tid = SPAN_TID_BASE + lane
        if ev["t1"] > ev["t0"]:
            te.append(_span(ev.get("name", "?"), "span", s, d,
                            trk.pid, tid, args=args))
        else:
            te.append(_instant(ev.get("name", "?"), s, trk.pid, tid,
                               args=args))


def _merge_windows(windows):
    """Sorted, overlap-merged [(s, e)] — sibling spans on one track
    must be disjoint for the nesting validator."""
    out = []
    for s, e in sorted(windows):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _query_spans(run, times, trk: _Track, te: list, rstart, rend):
    """Per-query serving spans (round 17, lux_tpu/serve.py events):
    each retired query becomes a ``query`` span from its enqueue to
    its retirement, with ``query_phase`` children splitting the life
    into the queue WAIT (enqueue -> column assignment) and the
    engine segments that carried it — so a query's wait-vs-compute
    renders visibly in Perfetto.  Queries pack greedily onto
    ``queries.N`` lanes (one lane per set of non-overlapping
    queries); everything is clamped into the run extent so the
    run-nesting invariant holds by construction, and
    ``validate_trace`` machine-checks the query/query_phase nesting
    rule.

    Round 18 (serving fleet): lanes group PER REPLICA (thread name
    ``queries[replica].N``), and a ``failover`` event SPLITS the
    qid's span at the failover instant — the pre-failover segment
    stays on the dead replica's track group, the post-failover
    segment (named ``... (failover)``, args carrying
    ``failover_from``/``failover_to``) moves onto the survivor's —
    the same track-transition idiom the mesh-shrink epochs use, now
    on the query lanes."""
    enq, starts, done, fo = {}, {}, {}, {}
    segs = []
    for ev, ts in zip(run, times):
        kind = ev["kind"]
        qid = ev.get("qid")
        if kind == "query_enqueue":
            enq.setdefault(qid, ts)
        elif kind == "query_start":
            starts.setdefault(qid, []).append((ts, ev))
        elif kind == "failover":
            fo.setdefault(qid, []).append((ts, ev))
        elif kind == "query_done":
            done[qid] = (ts, ev)
        elif kind == "segment" and _num(ev.get("seconds")):
            d = ev["seconds"] * 1e6
            segs.append((ts - d, ts))
    if not done:
        return
    segs = _merge_windows(segs)
    qs = []
    for qid, (tend, ev) in done.items():
        t0 = enq.get(qid)
        if t0 is None and _num(ev.get("latency_s")):
            t0 = tend - ev["latency_s"] * 1e6
        sl = starts.get(qid) or []
        t1 = sl[0][0] if sl else None
        if t1 is None and t0 is not None and _num(ev.get("wait_s")):
            t1 = t0 + ev["wait_s"] * 1e6
        t0 = tend if t0 is None else t0
        t1 = t0 if t1 is None else t1
        t0 = min(max(t0, rstart), rend)          # clamp + order
        t1 = min(max(t1, t0), rend)
        tend = min(max(tend, t1), rend)
        qs.append((t0, t1, tend, qid, ev))
    qs.sort(key=lambda x: (x[0], x[2]))
    groups: dict = {}           # replica -> lane-group index
    lane_ends: dict = {}        # group -> per-lane end times
    lane_labels: dict = {}      # (group, lane) -> thread label
    placed: list = []           # (group, lane, span dict) pending tid

    def lane_of(replica, s, e):
        group = groups.setdefault(replica, len(groups))
        ends = lane_ends.setdefault(group, [])
        lane = next((i for i, x in enumerate(ends) if x <= s), None)
        if lane is None:
            lane = len(ends)
            ends.append(e)
            lane_labels[(group, lane)] = (
                f"queries.{lane}" if replica is None
                else f"queries[{replica}].{lane}")
        else:
            ends[lane] = max(ends[lane], e)
        return group, lane

    for t0, t1, tend, qid, ev in qs:
        sl = starts.get(qid) or []
        fos = sorted(fo.get(qid) or [], key=lambda x: x[0])
        cuts = [t0]
        for ts_f, _fev in fos:
            cuts.append(min(max(ts_f, cuts[-1]), tend))
        cuts.append(tend)

        def replica_of(i):
            if i == 0:
                # the failover record is authoritative for the
                # pre-failover replica: a query killed while still
                # QUEUED on the dead replica has its first
                # query_start on the survivor, but its first life
                # segment belongs to the replica it was assigned to
                if fos:
                    return fos[0][1].get("from_replica")
                if sl:
                    return sl[0][1].get("replica")
                return ev.get("replica")
            return fos[i - 1][1].get("to_replica")

        base = {k: v for k, v in ev.items()
                if k in ("qid", "query_kind", "col", "iters",
                         "segments", "latency_s", "wait_s",
                         "converged", "slo_ms", "slo_ok")}
        for i in range(len(cuts) - 1):
            s, e = cuts[i], cuts[i + 1]
            replica = replica_of(i)
            gl = lane_of(replica, s, e)
            args = dict(base)
            if replica is not None:
                args["replica"] = replica
            name = f"q{qid} [{ev.get('query_kind', '?')}]"
            if i > 0:
                fev = fos[i - 1][1]
                args["failover_from"] = fev.get("from_replica")
                args["failover_to"] = fev.get("to_replica")
                name += " (failover)"
            placed.append((*gl, _span(name, "query", s, e - s,
                                      trk.pid, 0, args=args)))
            lo = min(max(t1, s), e) if i == 0 else s
            if i == 0 and lo > s:
                placed.append((*gl, _span("wait", "query_phase", s,
                                          lo - s, trk.pid, 0)))
            resident = False
            for s0, s1 in segs:
                a, b = max(s0, lo), min(s1, e)
                if b > a:
                    placed.append((*gl, _span("seg", "query_phase",
                                              a, b - a, trk.pid, 0)))
                    resident = True
            if not resident and e > lo:
                # no overlapping segment events (sparse log): one
                # undifferentiated residency child keeps
                # wait-vs-compute readable
                placed.append((*gl, _span("resident", "query_phase",
                                          lo, e - lo, trk.pid, 0)))

    # tid assignment is a SECOND pass: each replica group gets a
    # contiguous lane range sized by its ACTUAL lane count (at least
    # QUERY_REPLICA_STRIDE, so small traces keep the stable
    # base+group*stride tids) — a group needing more lanes than the
    # stride can never collide into the next group's track range
    offsets, off = {}, 0
    for group in sorted(lane_ends):
        offsets[group] = off
        off += max(QUERY_REPLICA_STRIDE, len(lane_ends[group]))
    for (group, lane), label in sorted(lane_labels.items()):
        te.append(_meta("thread_name", trk.pid, label,
                        tid=QUERY_TID_BASE + offsets[group] + lane))
    for group, lane, span in placed:
        span["tid"] = QUERY_TID_BASE + offsets[group] + lane
        te.append(span)


def trace_export(events, out: str | None = None) -> dict:
    """Chrome-trace/Perfetto JSON for a (possibly multi-process)
    telemetry event list.  One trace process per (session, pid)
    stream; ``out`` additionally writes the JSON atomically.  Returns
    the trace dict ({"traceEvents": [...], ...})."""
    streams = split_streams(events)
    offs = {key: _stream_offset(evs) for key, evs in streams}
    t0s = [t for key, evs in streams
           for t in (_ats(ev, offs[key]) for ev in evs)
           if t is not None]
    t0 = min(t0s) if t0s else 0.0
    te: list = []
    for si, (key, evs) in enumerate(streams):
        trk = _Track(pid=si)
        off = offs[key]

        def us(ev, _off=off):
            a = _ats(ev, _off)
            return 0.0 if a is None else (a - t0) * 1e6

        session, ospid = key if key is not None else (None, None)
        pname = (f"session {session} pid {ospid}"
                 if key is not None else "events")
        te.append(_meta("process_name", si, pname))
        te.append(_meta("process_sort_index", si, si))
        te.append(_meta("thread_name", si, "run/attempt", tid=0))
        for run in split_runs(evs):
            _run_spans(run, us, trk, te)
        te.append(_meta("thread_name", si, "exec", tid=1))
        for epoch, label in trk.shrink_labels.items():
            te.append(_meta("thread_name", si, label, tid=1 + epoch))
    trace = {"traceEvents": te, "displayTimeUnit": "ms",
             "otherData": {"schema": SCHEMA,
                           "generator": "lux_tpu.tracing",
                           "streams": len(streams)}}
    if out:
        _atomic_write_json(out, trace)
    return trace


def _atomic_write_json(path: str, doc) -> None:
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# span endpoints inherit the 1e-6 s rounding of ``tm``/``seconds``:
# tolerate up to 2 us of slack before calling two spans overlapping
_EPS_US = 2.0


def validate_trace(trace, eps_us: float = _EPS_US) -> list[str]:
    """Machine-check a trace: known phases only, numeric
    ts/dur, PROPER NESTING per (pid, tid) track (two spans either
    disjoint or one contains the other), no orphan spans (every
    non-run span lies inside some run span of its process), and —
    round 17 — the per-query nesting rule: every ``query`` span
    carries its qid, and every ``query_phase`` span (wait / seg /
    resident) lies inside some ``query`` span of its own track.
    Returns error strings; empty = valid."""
    errs: list[str] = []
    evs = trace.get("traceEvents") if isinstance(trace, dict) else None
    if not isinstance(evs, list) or not evs:
        return ["traceEvents missing or empty"]
    spans: dict = {}
    runs: dict = {}
    qspans: dict = {}
    qphases: dict = {}
    qrecords: list = []
    for i, e in enumerate(evs):
        if not isinstance(e, dict):
            errs.append(f"traceEvents[{i}]: not an object")
            continue
        ph = e.get("ph")
        if ph not in ("X", "i", "I", "M"):
            errs.append(f"traceEvents[{i}]: unknown ph {ph!r}")
            continue
        if ph == "M":
            continue
        if not isinstance(e.get("name"), str):
            errs.append(f"traceEvents[{i}]: non-string name")
        if not _num(e.get("ts")):
            errs.append(f"traceEvents[{i}]: non-numeric ts")
            continue
        if ph == "X":
            if not _num(e.get("dur")) or e["dur"] < 0:
                errs.append(f"traceEvents[{i}] {e.get('name')!r}: "
                            f"bad dur {e.get('dur')!r}")
                continue
            spans.setdefault((e.get("pid"), e.get("tid")),
                             []).append(e)
            if e.get("cat") == "run":
                runs.setdefault(e.get("pid"), []).append(
                    (e["ts"], e["ts"] + e["dur"]))
            elif e.get("cat") == "query":
                if not isinstance((e.get("args") or {}).get("qid"),
                                  int):
                    errs.append(f"traceEvents[{i}] {e.get('name')!r}:"
                                f" query span without an integer "
                                f"args.qid")
                qspans.setdefault((e.get("pid"), e.get("tid")),
                                  []).append(
                    (e["ts"], e["ts"] + e["dur"]))
                qrecords.append(e)
            elif e.get("cat") == "query_phase":
                qphases.setdefault((e.get("pid"), e.get("tid")),
                                   []).append(e)
    for (pid, tid), sp in spans.items():
        sp.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: list[float] = []
        for e in sp:
            s, end = e["ts"], e["ts"] + e["dur"]
            while stack and stack[-1] <= s + eps_us:
                stack.pop()
            if stack and end > stack[-1] + eps_us:
                errs.append(
                    f"track (pid {pid}, tid {tid}): span "
                    f"{e['name']!r} [{s}, {end}] overlaps the "
                    f"enclosing span ending at {stack[-1]} — spans "
                    f"must nest")
            stack.append(end)
    for (pid, _tid), sp in spans.items():
        rl = runs.get(pid)
        if not rl:
            continue            # hand-made trace without run spans
        for e in sp:
            if e.get("cat") == "run":
                continue
            s, end = e["ts"], e["ts"] + e["dur"]
            if not any(rs - eps_us <= s and end <= re + eps_us
                       for rs, re in rl):
                errs.append(f"orphan span {e['name']!r} (pid {pid}): "
                            f"[{s}, {end}] lies in no run span")
    # round 17: a query phase (wait/seg/resident) outside every query
    # span of its track is an orphan — the wait-vs-compute split
    # would be attributed to no query
    for key, phases in qphases.items():
        ql = qspans.get(key, [])
        for e in phases:
            s, end = e["ts"], e["ts"] + e["dur"]
            if not any(qs - eps_us <= s and end <= qe + eps_us
                       for qs, qe in ql):
                errs.append(
                    f"track (pid {key[0]}, tid {key[1]}): "
                    f"query_phase span {e['name']!r} [{s}, {end}] "
                    f"lies in no query span — per-query phases must "
                    f"nest inside their query")
    # round 18 (serving fleet): a qid appearing as MULTIPLE query
    # spans is a failover split — every span after the first must
    # carry its failover record and sit on a DIFFERENT track (the
    # new replica's lane group); anything else is either a duplicate
    # retirement or a failover that did not transition tracks.
    # Scoped to the containing run window so qids legitimately reused
    # across runs in one stream don't conflate.
    by_qid: dict = {}
    for e in qrecords:
        qid = (e.get("args") or {}).get("qid")
        if not isinstance(qid, int):
            continue            # reported above
        pid = e.get("pid")
        rl = runs.get(pid) or []
        w = next((i for i, (rs, re) in enumerate(rl)
                  if rs - eps_us <= e["ts"]
                  and e["ts"] + e["dur"] <= re + eps_us), None)
        by_qid.setdefault((pid, w, qid), []).append(e)
    for (pid, _w, qid), lst in by_qid.items():
        if len(lst) < 2:
            continue
        lst.sort(key=lambda e: e["ts"])
        for prev, cur in zip(lst, lst[1:]):
            args = cur.get("args") or {}
            if "failover_from" not in args:
                errs.append(
                    f"qid {qid} (pid {pid}): {len(lst)} query spans "
                    f"but the span at ts {cur['ts']} carries no "
                    f"failover record — a qid must retire exactly "
                    f"once")
                continue
            if cur.get("tid") == prev.get("tid"):
                errs.append(
                    f"qid {qid} (pid {pid}): post-failover segment "
                    f"at ts {cur['ts']} sits on the SAME track (tid "
                    f"{cur.get('tid')}) as the segment it continues "
                    f"— a failover must transition onto the new "
                    f"replica's track")
            rep = args.get("replica")
            if rep is not None and args.get("failover_to") is not None \
                    and rep != args["failover_to"]:
                errs.append(
                    f"qid {qid} (pid {pid}): post-failover segment "
                    f"claims replica {rep!r} but its failover record "
                    f"names {args['failover_to']!r} — the span "
                    f"contradicts its own transition")
    return errs


# ---------------------------------------------------------------------
# pillar 3: crash flight recorder

FLIGHT_DEFAULT = "FLIGHT.json"
FLIGHT_CAPACITY = 256


class FlightRecorder:
    """Bounded postmortem ring: the last ``capacity`` telemetry
    events plus the most recent health word, calibration fingerprint
    and placement metadata, dumped atomically on demand.  Installed
    as a telemetry observer (``install_flight_recorder``); the
    resilience supervisor dumps it on FATAL failures and topology
    faults, so a run that dies mid-flight leaves
    ``FLIGHT.json`` behind."""

    def __init__(self, path: str = FLIGHT_DEFAULT,
                 capacity: int = FLIGHT_CAPACITY):
        self.path = path
        self.ring: collections.deque = collections.deque(
            maxlen=max(1, int(capacity)))
        self.last_health: dict | None = None
        self.last_calibration: dict | None = None
        self.placement: dict = {}
        self.dumps = 0
        # round-22 memory observatory: the occupancy trail survives
        # the main ring's churn — a fatal's postmortem always shows
        # the memory history even when chatty per-query events have
        # already rotated the samples out of the ring
        self.mem_trail: collections.deque = collections.deque(
            maxlen=64)

    def record(self, ev: dict) -> None:
        self.ring.append(ev)
        k = ev.get("kind")
        if k in ("health", "health_trip"):
            self.last_health = ev
        elif k == "calibration":
            self.last_calibration = ev
            if _num(ev.get("ndev")):
                self.placement["ndev"] = ev["ndev"]
        elif k == "header":
            for f in ("nv", "ne", "num_parts"):
                if f in ev:
                    self.placement[f] = ev[f]
        elif k == "mesh_shrink":
            to = ev.get("to_ndev", ev.get("to_nproc"))
            if _num(to):
                self.placement["ndev"] = to
            self.placement["shrinks"] = \
                self.placement.get("shrinks", 0) + 1
        elif k == "replace":
            if _num(ev.get("to_ndev")):
                self.placement["ndev"] = ev["to_ndev"]
        elif k in ("mem_sample", "mem_watermark", "mem_pressure"):
            self.mem_trail.append(ev)

    def snapshot(self, reason=None, classification=None) -> dict:
        counts: dict = {}
        for ev in self.ring:
            counts[ev.get("kind")] = counts.get(ev.get("kind"), 0) + 1
        return {"schema": SCHEMA, "t": round(time.time(), 6),
                "session": telemetry.session_id(), "pid": os.getpid(),
                "reason": reason, "classification": classification,
                "placement": self.placement or None,
                "health": self.last_health,
                "calibration": self.last_calibration,
                "counts": counts,
                "mem_trail": list(self.mem_trail) or None,
                "events": list(self.ring)}

    def dump(self, reason=None, classification=None) -> str:
        """Atomic write (tmp + rename: a crash mid-dump can never
        leave a torn FLIGHT.json) -> the dump path."""
        doc = self.snapshot(reason, classification)
        _atomic_write_json(self.path, doc)
        self.dumps += 1
        telemetry.current().emit(
            "flight_dump", path=self.path,
            reason=None if reason is None else str(reason)[:300],
            classification=classification, events=len(doc["events"]))
        return self.path


_RECORDER: FlightRecorder | None = None


def install_flight_recorder(path: str = FLIGHT_DEFAULT,
                            capacity: int = FLIGHT_CAPACITY
                            ) -> FlightRecorder:
    """Install (or replace) the process flight recorder as a
    telemetry observer.  Idempotent per path; the CLI's ``-flight``
    and bench.py's ``-flight`` call this."""
    global _RECORDER
    uninstall_flight_recorder()
    _RECORDER = FlightRecorder(path, capacity)
    telemetry.add_observer(_RECORDER.record)
    return _RECORDER


def uninstall_flight_recorder() -> None:
    global _RECORDER
    if _RECORDER is not None:
        telemetry.remove_observer(_RECORDER.record)
        _RECORDER = None


def flight_recorder() -> FlightRecorder | None:
    return _RECORDER


def flight_dump(reason=None, classification=None) -> str | None:
    """Dump the installed recorder (no-op None when none is
    installed) — the resilience supervisor's crash hook."""
    if _RECORDER is None:
        return None
    return _RECORDER.dump(reason, classification)


def load_flight(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "events" not in doc:
        raise ValueError(f"{path}: not a flight-recorder dump "
                         f"(no 'events')")
    return doc


# ---------------------------------------------------------------------
# drills + CLI

SMOKE_APPS = ("pagerank", "cc", "sssp", "colfilter")


def _emit_iter_stats(tel, st) -> None:
    if st.kind is None:
        return
    tel.emit("iter_stats", **{("engine" if k == "kind" else k): v
                              for k, v in st.summary().items()})


def run_smoke(events_path: str, apps=SMOKE_APPS, scale: int = 8,
              ef: int = 8, np_parts: int = 2) -> None:
    """The tier-1 smoke: run each app once on a small CPU graph with
    telemetry + per-part counters, leaving an events JSONL the
    exporter (and events_summary) consume."""
    from lux_tpu.observe import _build_app_engine
    from lux_tpu.timing import timed_converge, timed_fused_run

    ev = telemetry.EventLog(events_path)
    st = telemetry.IterStats()
    with telemetry.use(events=ev, iter_stats=st) as tel:
        for app in apps:
            eng = _build_app_engine(app, scale, ef, np_parts, None)
            tel.emit("run_start", schema=telemetry.SCHEMA, app=app)
            tel.emit("header", schema=telemetry.SCHEMA,
                     **eng.sg.telemetry_header())
            if hasattr(eng, "converge"):           # push engines
                _labels, iters, elapsed = timed_converge(eng,
                                                         repeats=1)
            else:
                _state, elapsed = timed_fused_run(eng, 5, repeats=1)
                iters = 5
            tel.emit("run_done", seconds=round(elapsed[0], 6),
                     iters=iters)
            _emit_iter_stats(tel, st)
    ev.close()


def run_loss_drill(workdir: str, events_path: str, ni: int = 12,
                   segment: int = 3) -> None:
    """In-process elastic drill: an 8-virtual-device supervised
    pagerank run hit by an injected DEVICE_LOSS at segment boundary 1
    re-places onto the surviving half-mesh and finishes — the event
    trail carries topology_fault/mesh_shrink/replace."""
    import jax

    from lux_tpu import faults, resilience
    from lux_tpu.apps import pagerank
    from lux_tpu.convert import uniform_random_edges
    from lux_tpu.graph import Graph
    from lux_tpu.parallel.mesh import make_mesh

    ndev = len(jax.devices())
    nd = max(n for n in (2, 4, 8) if n <= ndev) if ndev >= 2 else 0
    if not nd:
        raise RuntimeError(
            "the elastic drill needs >= 2 devices (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    src, dst = uniform_random_edges(256, 2000, seed=7)
    g = Graph.from_edges(src, dst, 256)

    def factory(mesh):
        return pagerank.build_engine(g, num_parts=nd, mesh=mesh)

    eng = factory(make_mesh(nd))
    plan = faults.FaultPlan(schedule={1: faults.DEVICE_LOSS},
                            lose=nd // 2)
    path = os.path.join(workdir, "drill.ckpt.npz")
    ev = telemetry.EventLog(events_path)
    st = telemetry.IterStats()
    with telemetry.use(events=ev, iter_stats=st) as tel:
        tel.emit("run_start", schema=telemetry.SCHEMA, app="pagerank",
                 drill="device_loss", mesh=nd)
        tel.emit("header", schema=telemetry.SCHEMA,
                 **eng.sg.telemetry_header())
        t0 = time.perf_counter()
        _state, report = resilience.supervised_run(
            eng, ni, path, segment=segment, faults=plan,
            elastic=factory,
            policy=resilience.RetryPolicy(retries=2, jitter=0,
                                          sleep=lambda s: None))
        tel.emit("run_done",
                 seconds=round(time.perf_counter() - t0, 6), iters=ni)
        _emit_iter_stats(tel, st)
        if not report.topology:
            raise RuntimeError("drill fault never fired")
    ev.close()


# -- the 2-subprocess worker-kill drill (tests/test_worker_kill.py's
#    shape, with one SHARED event file exercising the line-atomic
#    multi-writer appends) ----------------------------------------------

_DRILL_NI, _DRILL_SEG, _DRILL_PARTS = 10, 3, 8

import re as _re

_CPU_MP_UNSUPPORTED = _re.compile(
    r"[Mm]ultiprocess computations aren'?t implemented on the CPU "
    r"backend")


def _drill_graph():
    from lux_tpu.convert import uniform_random_edges
    from lux_tpu.graph import Graph

    src, dst = uniform_random_edges(128, 900, seed=5)
    return Graph.from_edges(src, dst, 128)


def _drill_worker_distributed(pid: int, nproc: int, port: str,
                              workdir: str) -> int:
    from lux_tpu import faults, heartbeat, resilience
    from lux_tpu.apps import pagerank
    from lux_tpu.parallel import multihost

    multihost.initialize(coordinator_address=f"127.0.0.1:{port}",
                         num_processes=nproc, process_id=pid)
    g = _drill_graph()
    mesh = multihost.global_mesh()
    eng = pagerank.build_engine(g, num_parts=_DRILL_PARTS, mesh=mesh)
    hb = heartbeat.Heartbeat(path=os.path.join(workdir, "hb"),
                             pid=pid, nproc=nproc, deadline_s=20.0)
    plan = None
    if pid == 1:
        plan = faults.FaultPlan(schedule={1: faults.WORKER_KILL},
                                hard_kill=True)
    ckpt = os.path.join(workdir, "drill.ckpt.npz")
    ev = telemetry.EventLog(os.path.join(workdir, "events.jsonl"))
    with telemetry.use(events=ev) as tel:
        tel.emit("run_start", schema=telemetry.SCHEMA, app="pagerank",
                 drill="worker_kill", worker=pid)
        t0 = time.perf_counter()
        try:
            # guard=False: the finite guard fetches the global state
            # at every boundary; the heartbeat IS the boundary check
            resilience.supervised_run(
                eng, _DRILL_NI, ckpt, segment=_DRILL_SEG, faults=plan,
                heartbeat=hb, guard=False,
                policy=resilience.RetryPolicy(retries=0, jitter=0,
                                              sleep=lambda s: None))
        except heartbeat.WorkerLostError:
            survivors = hb.survivors()
            hb.propose_shrink(survivors, generation=1)
            print(f"DRILL_SHRINK pid={pid} survivors={survivors}",
                  flush=True)
            ev.close()
            return 3                  # degraded relaunch requested
        tel.emit("run_done",
                 seconds=round(time.perf_counter() - t0, 6),
                 iters=_DRILL_NI)
    ev.close()
    print(f"DRILL_OK pid={pid}", flush=True)
    return 0


def _drill_worker_solo(workdir: str) -> int:
    import jax

    from lux_tpu import resilience
    from lux_tpu.apps import pagerank
    from lux_tpu.parallel.mesh import make_mesh

    g = _drill_graph()
    nd = min(4, len(jax.devices()))
    eng = pagerank.build_engine(g, num_parts=_DRILL_PARTS,
                                mesh=make_mesh(nd))
    ckpt = os.path.join(workdir, "drill.ckpt.npz")
    ev = telemetry.EventLog(os.path.join(workdir, "events.jsonl"))
    st = telemetry.IterStats()
    with telemetry.use(events=ev, iter_stats=st) as tel:
        tel.emit("run_start", schema=telemetry.SCHEMA, app="pagerank",
                 drill="worker_kill_solo")
        t0 = time.perf_counter()
        _state, _report = resilience.supervised_run(
            eng, _DRILL_NI, ckpt, segment=_DRILL_SEG, resume=True,
            policy=resilience.RetryPolicy(retries=0, jitter=0,
                                          sleep=lambda s: None))
        tel.emit("run_done",
                 seconds=round(time.perf_counter() - t0, 6),
                 iters=_DRILL_NI)
        _emit_iter_stats(tel, st)
        # the heartbeat protocol's shrink record, merged into the
        # same stream so the exporter shows the track transition
        tel.emit("mesh_shrink", protocol="heartbeat", from_nproc=2,
                 to_nproc=1, survivors=[0], generation=1)
    ev.close()
    print("DRILL_SOLO_OK", flush=True)
    return 0


def _drill_env() -> dict:
    """Subprocess env: the drill's two workers are CPU processes,
    ALWAYS — ``JAX_PLATFORMS=cpu`` is pinned here, on the chip
    machine too (a chip belongs to one process; the parent that
    spawns the drill may already hold it).  The drill proves the
    kill/shrink/relaunch protocol, not anything about the device."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    pp = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([repo] + pp)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return env


def run_kill_drill(workdir: str) -> str | None:
    """Drive the 2-subprocess worker-kill drill (2 processes x 4 CPU
    devices, one shared event file) and the degraded solo relaunch.
    Returns the merged events path, or None when this jaxlib's CPU
    backend cannot run multi-process collectives (caller falls back
    to the in-process DEVICE_LOSS drill)."""
    import socket
    import subprocess

    from lux_tpu import faults

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = _drill_env()
    nproc = 2
    procs = [subprocess.Popen(
        [sys.executable, "-m", "lux_tpu.tracing", "-drill-worker",
         str(i), str(nproc), str(port), workdir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in range(nproc)]
    try:
        outs = [p.communicate(timeout=600)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(_CPU_MP_UNSUPPORTED.search(o) for o in outs):
        return None
    if procs[1].returncode != faults.HARD_KILL_CODE \
            or procs[0].returncode != 3:
        raise RuntimeError(
            f"worker-kill drill went off-script (rc="
            f"{[p.returncode for p in procs]}):\n" + "\n".join(outs))
    solo = subprocess.run(
        [sys.executable, "-m", "lux_tpu.tracing", "-drill-worker",
         "solo", "0", "0", workdir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=600)
    if solo.returncode != 0:
        raise RuntimeError(f"solo relaunch failed:\n{solo.stdout}")
    return os.path.join(workdir, "events.jsonl")


def _drill_worker_main(argv) -> int:
    """Internal subprocess entry (python -m lux_tpu.tracing
    -drill-worker PID NPROC PORT WORKDIR; PID='solo' for the
    relaunch)."""
    pid, nproc, port, workdir = argv[0], int(argv[1]), argv[2], \
        argv[3]
    if pid == "solo":
        return _drill_worker_solo(workdir)
    return _drill_worker_distributed(int(pid), nproc, port, workdir)


def _summarize(trace, out_path, errs, to=sys.stdout) -> None:
    te = trace["traceEvents"]
    n_span = sum(1 for e in te if e.get("ph") == "X")
    n_inst = sum(1 for e in te if e.get("ph") == "i")
    marks = sorted({e["name"] for e in te if e.get("ph") == "i"})
    print(f"trace: {out_path}  "
          f"({trace['otherData']['streams']} stream(s), {n_span} "
          f"spans, {n_inst} instant markers)", file=to)
    if marks:
        print(f"  markers: {', '.join(marks)}", file=to)
    shrinks = [e for e in te
               if e.get("ph") == "i" and e["name"] == "mesh_shrink"]
    if shrinks:
        print(f"  mesh-shrink marker present "
              f"(x{len(shrinks)}) — load in chrome://tracing / "
              f"ui.perfetto.dev", file=to)
    print(("trace VALID (spans nest, no orphans)" if not errs
           else f"trace INVALID: {len(errs)} error(s)"), file=to)


def main(argv=None) -> int:
    import argparse

    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "-drill-worker":
        return _drill_worker_main(argv[1:])

    ap = argparse.ArgumentParser(
        prog="python -m lux_tpu.tracing",
        description="span timeline export (Chrome trace / Perfetto "
                    "JSON) from lux_tpu telemetry event logs; with "
                    "no FILE, runs the 4-app CPU smoke first")
    ap.add_argument("files", nargs="*", metavar="EVENTS_JSONL",
                    help="existing -events files to export (merged "
                         "onto one timeline)")
    ap.add_argument("-o", default="trace.json", dest="out",
                    metavar="TRACE_JSON")
    ap.add_argument("-drill", action="store_true",
                    help="run the 8-virtual-device elastic "
                         "worker-kill drill (2 subprocesses x 4 CPU "
                         "devices, shared event file, hard kill + "
                         "degraded relaunch) and export its merged "
                         "timeline; falls back to the in-process "
                         "DEVICE_LOSS drill where the CPU backend "
                         "has no multi-process collectives")
    ap.add_argument("-workdir", default=None,
                    help="working directory for drill/smoke "
                         "artifacts (default: a fresh temp dir)")
    ap.add_argument("-scale", type=int, default=8,
                    help="smoke RMAT scale (default 8)")
    ap.add_argument("-ef", type=int, default=8)
    ap.add_argument("-np", type=int, default=2, dest="np_parts")
    ap.add_argument("-apps", nargs="+", default=list(SMOKE_APPS),
                    choices=SMOKE_APPS, metavar="APP")
    args = ap.parse_args(argv)

    workdir = args.workdir or tempfile.mkdtemp(prefix="lux_trace_")
    os.makedirs(workdir, exist_ok=True)
    if args.files:
        # a rotated EventLog (rotate_bytes) leaves a .1/.2 generation
        # set beside the live file: consume the whole set, oldest
        # first, as one stream (telemetry.rotated_paths)
        paths = [g for p in args.files
                 for g in telemetry.rotated_paths(p)]
    elif args.drill:
        path = run_kill_drill(workdir)
        if path is None:
            print("# CPU backend has no multi-process collectives; "
                  "falling back to the in-process DEVICE_LOSS drill",
                  file=sys.stderr)
            # a FRESH file: the aborted workers' partial trails must
            # not merge into the fallback drill's timeline
            path = os.path.join(workdir, "events_loss.jsonl")
            run_loss_drill(workdir, path)
        paths = [path]
    else:
        path = os.path.join(workdir, "events.jsonl")
        run_smoke(path, apps=args.apps, scale=args.scale, ef=args.ef,
                  np_parts=args.np_parts)
        paths = [path]

    events, errs = [], []
    for p in paths:
        evs, es = load_events(p)
        events += evs
        errs += es
    trace = trace_export(events, out=args.out)
    verrs = validate_trace(trace)
    _summarize(trace, args.out, verrs)
    for e in errs + verrs:
        print(f"ERROR: {e}", file=sys.stderr)
    return 1 if (errs or verrs) else 0


if __name__ == "__main__":
    sys.exit(main())
