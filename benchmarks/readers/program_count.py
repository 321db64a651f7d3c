"""The sum of count fields over the program's span records (see
``program_span``: ``spans``, ``when``, ``where``), or the ratio of two
such sums: ``field`` and ``over`` each name one count or a list of
counts to add up; with ``percent`` times 100.  ``None`` where the
program has no span ring, no record matched, or the divisor is 0."""

from benchmarks.readers import program_span


def _total(records, fields):
    fields = [fields] if isinstance(fields, str) else fields
    return sum(float(r["counts"].get(f, 0)) for r in records
               for f in fields)


def read(spec, run):
    records = program_span.ring()
    if records is None or run.t_window is None:
        return None
    picked = program_span.select(records, run, spec["spans"],
                                 spec["when"], spec.get("where"))
    if not picked:
        return None
    value = _total(picked, spec["field"])
    if "over" in spec:
        over = _total(picked, spec["over"])
        if not over:
            return None
        value /= over
    return value * (100.0 if spec.get("percent") else 1.0)
