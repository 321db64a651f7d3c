"""How much longer the slowest chip computes than the average one, in
percent: over the device planes of the traced part that ran anything,
``(max - mean) / mean`` of ``busy_s - collective_s``.  A chip that
waits in a collective for the others is busy there, so the collective
seconds are taken off: what is left is the chip's own work, and the
skew is what the deal of edges over the parts and the per-part rung
leave of an uneven frontier.  One chip reads 0; no trace, ``None``."""


def read(spec, run):
    ts = run.trace_summary
    if ts is None:
        return None
    own = [d.busy_s - d.collective_s for d in ts.devices if d.busy_s > 0]
    if not own:
        return None
    mean = sum(own) / len(own)
    return 100.0 * (max(own) - mean) / mean if mean > 0 else None
