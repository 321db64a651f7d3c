"""The share, in percent, of the traced window's device idle time that
lies inside a LEAF span of the program (``lux_tpu.telemetry.spans()``:
a record with a duration that is no other such record's parent).

Idle time is the gaps between device programs of the first used device
(``run.trace_summary``), on the profiler's clock; the program's records
are on ``time.perf_counter``.  The offset between the two is MEASURED,
from what the harness recorded on both: the ends of its own spans
(``run.spans`` against ``trace_summary.host_spans`` of the same name),
and in the serving cell the ``clock`` of a ``serve_refill`` event
against the end of its ``bench:boundary`` (the runner closes that
annotation in the same observer call that stamps the clock).  Which
end belongs to which is not assumed: every pairing of one name is a
candidate, and the offset is the median of the densest cluster of
candidates (the true pairings agree within microseconds, the wrong
ones are scattered).  Prints the idle seconds by span name."""

from statistics import median

from benchmarks.readers import program_span

CLUSTER_S = 0.002       # candidates this close are one offset


def clock_offset(run):
    """profiler seconds minus ``perf_counter`` seconds, or None."""
    ts = run.trace_summary
    host = {}
    for name, _s, e in ts.host_spans:
        host.setdefault(name, []).append(e / 1e12)
    mine = {}
    for name, _s, e in run.spans:
        if e >= run.t_window:
            mine.setdefault(name, []).append(e)
    mine.setdefault("boundary", []).extend(
        e["clock"] for e in run.events
        if e.get("kind") == "serve_refill" and "clock" in e)
    cand = sorted(h - m for name, ends in host.items() for h in ends
                  for m in mine.get(name, ()))
    if not cand:
        return None
    best, j = [], 0
    for i in range(len(cand)):
        while cand[i] - cand[j] > CLUSTER_S:
            j += 1
        if i - j + 1 > len(best):
            best = cand[j:i + 1]
    if len(best) < 2 and len(cand) > 1:
        return None         # nothing agrees with anything
    return median(best)


def leaf_spans(records):
    timed = [r for r in records if r["t1"] > r["t0"]]
    parents = {r["parent"] for r in timed}
    return [r for r in timed if r["id"] not in parents]


def attribute(gaps, spans, offset):
    """(idle seconds inside ``spans``, {span name: idle seconds});
    ``gaps`` in seconds on the profiler's clock."""
    by_name, inside = {}, 0.0
    for gs, ge in gaps:
        covered = []
        for r in spans:
            s, e = max(r["t0"] + offset, gs), min(r["t1"] + offset, ge)
            if e > s:
                by_name[r["name"]] = by_name.get(r["name"], 0.0) + e - s
                covered.append({"t0": s, "t1": e})
        inside += program_span.union_seconds(covered)
    return inside, by_name


def read(spec, run):
    records = program_span.ring()
    ts = run.trace_summary
    if records is None or ts is None or run.t_window is None:
        return None
    used = [d for d in ts.devices if d.busy_s > 0]
    if not used:
        return None
    gaps = [(s / 1e12, e / 1e12) for s, e in used[0].gaps if e > s]
    idle = sum(e - s for s, e in gaps)
    offset = clock_offset(run)
    if offset is None or idle <= 0:
        return None
    inside, by_name = attribute(gaps, leaf_spans(records), offset)
    # what no leaf covers: between the children of a span, or where
    # the program has no span at all
    in_any, _ = attribute(gaps, [r for r in records
                                 if r["t1"] > r["t0"]], offset)
    print(f"idle {idle:.6f} s of the traced window, by leaf span "
          f"(clock offset {offset:.6f} s):", flush=True)
    for name, s in sorted(by_name.items(), key=lambda kv: -kv[1]):
        print(f"  {name:<28s} {s:.6f} s", flush=True)
    print(f"  {'between the children of a span':<28s} "
          f"{in_any - inside:.6f} s", flush=True)
    print(f"  {'outside every span':<28s} {idle - in_any:.6f} s",
          flush=True)
    return 100.0 * inside / idle
