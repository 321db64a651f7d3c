"""The mean over the program's span records (see ``program_span``:
``spans``, ``when``, ``where``) of the sum of their ``field`` counts
(one count or a list of counts to add up): a count a record, e.g.
loop trips a search.  ``None`` where the program has no span ring, no
record matched, or no matched record carries any of the counts."""

from benchmarks.readers import program_span
from benchmarks.readers.program_count import _total


def read(spec, run):
    records = program_span.ring()
    if records is None or run.t_window is None:
        return None
    picked = program_span.select(records, run, spec["spans"],
                                 spec["when"], spec.get("where"))
    fields = spec["field"]
    fields = [fields] if isinstance(fields, str) else fields
    if not any(f in r["counts"] for r in picked for f in fields):
        return None
    return _total(picked, fields) / len(picked)
