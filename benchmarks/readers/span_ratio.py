"""The share that some of the program's span records have in others
(see ``program_span``: ``spans``, ``when``, ``where`` select the
numerator; ``of`` names the records of the divisor, in the same part of
the run and without ``where``).  ``value``: ``seconds`` (default: summed
durations) or ``count`` (the number of records); with ``percent`` times
100.  ``None`` where the program has no span ring or nothing matched
``of``; prints both sides."""

from benchmarks.readers import program_span


def _amount(records, value):
    if value == "count":
        return float(len(records))
    return sum(r["t1"] - r["t0"] for r in records)


def read(spec, run):
    records = program_span.ring()
    if records is None or run.t_window is None:
        return None
    value = spec.get("value", "seconds")
    part = _amount(program_span.select(
        records, run, spec["spans"], spec["when"], spec.get("where")),
        value)
    whole = _amount(program_span.select(
        records, run, spec["of"], spec["when"]), value)
    if not whole:
        return None
    print(f"  {spec['spans']} {part:.6f} of {spec['of']} {whole:.6f} "
          f"({value})", flush=True)
    return part / whole * (100.0 if spec.get("percent") else 1.0)
