"""Milliseconds per engine iteration: seconds inside the timed loops
(solves, searches) over the iterations the engine reports."""


def read(spec, run):
    iters = run.counters.get("loop_iters", 0)
    if not iters:
        return None
    return run.counters["loop_seconds"] / iters * 1e3
