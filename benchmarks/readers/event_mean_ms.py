"""``event_mean`` of a field in seconds, given in milliseconds."""

from benchmarks.readers import event_mean


def read(spec, run):
    value = event_mean.read(spec, run)
    return None if value is None else value * 1e3
