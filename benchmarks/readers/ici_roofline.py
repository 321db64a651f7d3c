"""The owner exchange's share of the interconnect's peak, in percent:
the least time the dense iterations' candidates could take to cross at
the chip's published inter-chip bandwidth, over the device time the
traced part's collective ops took.

The bytes are a LOWER bound (``benchmarks/ici_rooflines.py``) and the
seconds are those of EVERY collective op of the traced part (the
sparse iterations' and the pair rows' too), so the share cannot pass
100%.  Dense iterations: ``iters`` less ``sparse_iters`` of the traced
``spans`` records (``push.converge``).  ``None`` where nothing was
traced, the program keeps no such record, no iteration was dense (a
share of no bytes is no share, not 0) or no collective ran."""

from benchmarks import ici_rooflines
from benchmarks.readers import program_span


def read(spec, run):
    ts = run.trace_summary
    records = program_span.ring()
    if ts is None or run.peaks is None or records is None:
        return None
    marks = program_span.select(records, run, spec["spans"], "traced")
    dense = sum((r["counts"].get("iters") or 0)
                - (r["counts"].get("sparse_iters") or 0) for r in marks)
    seconds = ts.collective_seconds()
    if dense <= 0 or seconds <= 0:
        return None
    least = dense * ici_rooflines.least_owner_exchange_bytes_per_chip(
        run.graph["nv"], run.chips)
    return 100.0 * (least / (run.peaks["ici_bits_per_s"] / 8)) / seconds
