"""Milliseconds a counted unit of the program's work: the seconds the
RUNNER measured on the benchmark's own clock (``run.counters`` under
``counter``) over the sum of the ``over`` counts of the program's span
records in the same window (see ``program_span``: ``spans``, ``when``,
``where``), e.g. seconds inside the searches over the loop's trips.
``None`` where the runner left no such counter, the program has no
span ring, or the counts add up to 0."""

from benchmarks.readers import program_span
from benchmarks.readers.program_count import _total


def read(spec, run):
    seconds = run.counters.get(spec["counter"])
    records = program_span.ring()
    if seconds is None or records is None or run.t_window is None:
        return None
    picked = program_span.select(records, run, spec["spans"],
                                 spec["when"], spec.get("where"))
    units = _total(picked, spec["over"])
    if not units:
        return None
    return float(seconds) / units * 1e3
