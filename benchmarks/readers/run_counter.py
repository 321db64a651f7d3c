"""One number the RUNNER measured on the benchmark's own clock and
left in ``run.counters`` under the name ``counter``; ``None`` where it
left none."""


def read(spec, run):
    value = run.counters.get(spec["counter"])
    return None if value is None else float(value)
