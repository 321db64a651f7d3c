"""Seconds inside the benchmark's own host span ``span``."""


def read(spec, run):
    total = run.span_seconds(spec["span"])
    return total if total > 0 else None
