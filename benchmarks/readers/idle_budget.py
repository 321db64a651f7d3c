"""Where the device's idle time of the traced part goes, in POINTS OF
THE WINDOW: every idle second gets exactly one label, so a cell's
``idle_pct.*`` add up to ``100 x (1 - busy_s / window_s)``, the
driver's ``idle_share`` (``benchmarks/README.idle-budget.md``).

Labels, from the LEAF records of the program's span ring
(``idle_attributed.leaf_spans``) moved onto the profiler's clock:

- ``inside:<leaf>``: the second lies under that leaf (where the leaves
  of two threads overlap, under the one that opened first);
- ``after:<leaf>``: under no leaf, and ``<leaf>`` closed last before
  it (``after:-``: no leaf has closed in the traced part yet).  The
  seconds after ``state.init.put`` / ``state.place`` /
  ``serve.boundary.place``, each of which ends at dispatch, are how
  long the device went on waiting for what they sent: the ARRIVAL,
  read where both clocks meet instead of by a fence.

Idle time is that of EVERY used device plane, averaged, from the
start of the traced part (the harness's ``_trace_t0``) to its end
(``+ trace_window_s``): head and tail count, as they do in
``busy_s / window_s``.  The clock offset is measured as
``idle_attributed.clock_offset`` measures it, from ends recorded on
both clocks (``agreeing_offsets``: tighter, and with one more source
of pairings): fewer than ``MIN_AGREEING`` that agree give ``None``.

Spec: ``labels`` (exact, or a prefix ending in ``*``; ``"*"`` alone is
every label), ``except`` (the same, taken out again), ``of``:
``window`` (default: 100 x labelled seconds over the traced window) or
``idle`` (over the idle seconds).  The first metric read in a run
prints the whole table, and for each ``after:`` row the benchmark's own
span (``run.spans``) that holds most of it.
"""

from bisect import bisect_right
from statistics import median

from benchmarks.readers.idle_attributed import leaf_spans
from benchmarks.readers.program_span import ring

MIN_AGREEING = 3
AGREE_S = 50e-6         # true pairings lie microseconds apart


def agreeing_offsets(run, records):
    """The candidate offsets (profiler seconds minus ``perf_counter``
    seconds) that agree within ``AGREE_S``, sorted: the largest such
    group, the tightest among equals.  Candidates as in
    ``idle_attributed.clock_offset`` (every pairing of two ends of one
    name), and for ``bench:boundary`` also the close of the program's
    ``serve.boundary`` record: the mixed runner closes its annotation
    there, about a millisecond (``.place``) after the ``serve_refill``
    whose clock the one-kind runners pair it with."""
    host = {}
    for name, _s, e in run.trace_summary.host_spans:
        host.setdefault(name, []).append(e / 1e12)
    mine = {}
    for name, _s, e in run.spans:
        if e >= run.t_window:
            mine.setdefault(name, []).append(e)
    mine.setdefault("boundary", []).extend(
        [e["clock"] for e in run.events
         if e.get("kind") == "serve_refill" and "clock" in e]
        + [r["t1"] for r in records if r["name"] == "serve.boundary"
           and r["t1"] >= run.t_window])
    cand = sorted(h - m for name, ends in host.items() for h in ends
                  for m in mine.get(name, ()))
    best, j = [], 0
    for i in range(len(cand)):
        while cand[i] - cand[j] > AGREE_S:
            j += 1
        group = cand[j:i + 1]
        if len(group) > len(best) or (
                len(group) == len(best)
                and group[-1] - group[0] < best[-1] - best[0]):
            best = group
    return best


def timeline(leaves, t0, t1):
    """[(start, end, label)] that tile ``[t0, t1)``; ``leaves`` are
    ``(start, end, name)`` on the same clock."""
    out, at, last = [], t0, "-"
    for s, e, name in sorted(leaves):
        if e <= at or s >= t1:
            continue            # closed before, under an earlier leaf, or late
        s, e = max(s, at), min(e, t1)
        if s > at:
            out.append((at, s, "after:" + last))
        out.append((s, e, "inside:" + name))
        at, last = e, name
    if at < t1:
        out.append((at, t1, "after:" + last))
    return out


def idle_of(dev, t0, t1):
    """The plane's idle intervals in ``[t0, t1)``, in seconds: the
    gaps between its programs, and the head and the tail."""
    gaps = [(s / 1e12, e / 1e12) for s, e in dev.gaps]
    gaps += [(t0, dev.first_ps / 1e12), (dev.last_ps / 1e12, t1)]
    clipped = ((max(s, t0), min(e, t1)) for s, e in gaps)
    return sorted((s, e) for s, e in clipped if e > s)


def _pieces(idle, tiles):
    """``idle`` cut at the tiles' edges -> (start, end, label)."""
    starts = [s for s, _e, _l in tiles]
    for gs, ge in idle:
        i = max(bisect_right(starts, gs) - 1, 0)
        while i < len(tiles) and tiles[i][0] < ge:
            s, e = max(tiles[i][0], gs), min(tiles[i][1], ge)
            if e > s:
                yield s, e, tiles[i][2]
            i += 1


def budget(run):
    """``{"window", "idle", "labels": {label: seconds}, "held":
    {after-label: {benchmark span: seconds}}, "offset", "agreeing",
    "spread"}`` (seconds: the mean over the used planes), or None."""
    records, ts = ring(), run.trace_summary
    t_trace = getattr(run, "_trace_t0", None)
    window = getattr(run, "trace_window_s", None)
    if records is None or ts is None or run.t_window is None \
            or t_trace is None or not window:
        return None
    used = [d for d in ts.devices if d.busy_s > 0]
    cluster = agreeing_offsets(run, records)
    if not used or len(cluster) < MIN_AGREEING:
        return None
    offset = median(cluster)
    t0, t1 = t_trace + offset, t_trace + offset + window
    tiles = timeline([(r["t0"] + offset, r["t1"] + offset, r["name"])
                      for r in leaf_spans(records)], t0, t1)
    # the benchmark's spans: its own list, and the annotations that
    # only the trace holds (``bench:boundary``); one that holds the
    # whole traced part (``server_run``) tells nothing apart
    bench = [(n, s + offset, e + offset) for n, s, e in run.spans]
    listed = {n for n, _s, _e in bench}
    bench += [(n, s / 1e12, e / 1e12) for n, s, e in ts.host_spans
              if n not in listed]
    bench = [b for b in bench if not (b[1] <= t0 and b[2] >= t1)]
    labels, held = {}, {}
    for dev in used:
        for s, e, label in _pieces(idle_of(dev, t0, t1), tiles):
            labels[label] = labels.get(label, 0.0) + (e - s) / len(used)
            if label.startswith("after:"):
                by = held.setdefault(label, {})
                for name, bs, be in bench:
                    both = min(e, be) - max(s, bs)
                    if both > 0:
                        by[name] = by.get(name, 0.0) + both / len(used)
    return {"window": window, "idle": sum(labels.values()),
            "labels": labels, "held": held, "offset": offset,
            "agreeing": len(cluster), "spread": cluster[-1] - cluster[0]}


def _print(b, run):
    print(f"idle budget: {b['idle']:.6f} s idle of the traced "
          f"{b['window']:.6f} s, mean over the used device planes "
          f"(clock offset {b['offset']:.6f} s, {b['agreeing']} pairings "
          f"agree within {b['spread'] * 1e6:.1f} us)", flush=True)
    for label, s in sorted(b["labels"].items(), key=lambda kv: -kv[1]):
        line = f"  {label:<36s} {s:.6f} s {100 * s / b['window']:7.3f}"
        by = b["held"].get(label)
        if by:
            name, most = max(by.items(), key=lambda kv: kv[1])
            line += f"   (bench:{name} holds {most:.6f} s)"
        print(line, flush=True)
    total = 100 * b["idle"] / b["window"]
    driver = 100 * (1 - run.trace_summary.busy_s / b["window"])
    print(f"  sum {total:.3f} points; 100 x (1 - busy_s / window_s) = "
          f"{driver:.3f} (the difference is device work the trace "
          f"holds from outside the traced part)", flush=True)


def _matches(label, patterns):
    return any(p == label or (p.endswith("*")
                              and label.startswith(p[:-1]))
               for p in patterns)


def read(spec, run):
    if not hasattr(run, "_idle_budget"):
        run._idle_budget = budget(run)
        if run._idle_budget is not None:
            _print(run._idle_budget, run)
    b = run._idle_budget
    if b is None:
        return None
    picked = sum(s for label, s in b["labels"].items()
                 if _matches(label, spec["labels"])
                 and not _matches(label, spec.get("except", ())))
    over = b["idle"] if spec.get("of") == "idle" else b["window"]
    return 100.0 * picked / over if over > 0 else None
