"""Device milliseconds per iteration of the ops under one named scope
(``scopes``: an op under any of them counts once), or of the collective ops (``collective: true``), from the
profiler's trace, averaged over the chips used."""


def seconds_per_iter(spec, run):
    ts = run.trace_summary
    iters = run.counters.get("traced_iters", 0)
    if ts is None or not iters:
        return None
    if spec.get("collective"):
        total = ts.collective_seconds()
    else:
        total = ts.scope_seconds(*spec["scopes"])
    return total / iters if total > 0 else None


def read(spec, run):
    s = seconds_per_iter(spec, run)
    return None if s is None else s * 1e3
