"""Seconds, mean milliseconds or the number of the PROGRAM's own span
records (``lux_tpu.telemetry.spans()``: ``{id, parent, name, t0, t1,
counts}`` on ``time.perf_counter``, the harness's clock).

``spans``: the record names that count (``"jit.*"`` matches a prefix).
``when``: ``setup`` = ended before ``run.t_window``; ``window`` = began
inside the measured window (from ``run.t_window`` to the start of the
harness's ``check`` span, or to the last event the runner kept for the
window where it kept any); ``traced`` = began AND ended inside the
traced part of the window, the same seconds the device-trace metrics
are taken from (the whole window where nothing was traced).  Stopping
the profiler stalls whatever host region it lands in for seconds, and
for half a minute afterwards the host's large copies run twice as
fast as in any ``--trace 0`` run (recycled pages; my chip runs,
PR 24): a mean over the whole window of a traced run describes
neither.  ``where``: an equality on one count, e.g.
``{"worked": 1}``.  ``value``: ``seconds`` (default: the length of the
union of the records' intervals, so nested records are not counted
twice), ``mean_ms`` or ``count`` (0 when nothing matched; each record
counted is printed with its counts, so a ``jit.compile`` in the window
names its function through ``fun``).  ``per``: a
span name; the records' summed milliseconds are divided by the number
of ITS records (``where`` then selects among those, and only their
children are summed).

A program without a span ring (a commit before the primitive) gives
``None``: the line leaves the metric out.
"""


def ring():
    """The program's span records, or None where it has no ring."""
    from lux_tpu import telemetry
    snapshot = getattr(telemetry, "spans", None)
    return None if snapshot is None else snapshot()


def window_of(run):
    """(start, end) of the measured window on the harness's clock."""
    end = min([s for n, s, _e in run.spans if n == "check"],
              default=float("inf"))
    clocks = [e["clock"] for e in run.events if "clock" in e]
    if clocks:
        end = min(end, max(clocks))
    return run.t_window, end


def _named(name, patterns):
    return any(name == p or (p.endswith(".*")
                             and name.startswith(p[:-1]))
               for p in patterns)


def select(records, run, names, when, where=None):
    """The records named in ``names`` that lie in ``when`` and carry
    every ``where`` count."""
    t0, t1 = window_of(run)
    traced = getattr(run, "trace_window_s", None)
    out = []
    for r in records:
        if not _named(r["name"], names):
            continue
        if when == "setup" and not r["t1"] <= t0:
            continue
        if when != "setup" and not t0 <= r["t0"] < t1:
            continue
        if when == "traced" and traced and not r["t1"] <= t0 + traced:
            continue
        if where and any(r["counts"].get(k) != v
                         for k, v in where.items()):
            continue
        out.append(r)
    return out


def union_seconds(records) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted((r["t0"], r["t1"]) for r in records):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def read(spec, run):
    records = ring()
    if records is None or run.t_window is None:
        return None
    when, where = spec["when"], spec.get("where")
    if "per" in spec:
        parents = select(records, run, [spec["per"]], when, where)
        ids = {r["id"] for r in parents}
        picked = [r for r in select(records, run, spec["spans"], when)
                  if r["parent"] in ids]
        if not parents:
            return None
        return sum(r["t1"] - r["t0"] for r in picked) / len(parents) * 1e3
    picked = select(records, run, spec["spans"], when, where)
    value = spec.get("value", "seconds")
    if value == "count":
        for r in picked:        # e.g. WHICH function compiled in the window
            print(f"  {r['name']} {r['t1'] - r['t0']:.6f} s {r['counts']}",
                  flush=True)
        return float(len(picked))
    if not picked:
        return None
    if value == "mean_ms":
        return sum(r["t1"] - r["t0"] for r in picked) / len(picked) * 1e3
    return union_seconds(picked)
