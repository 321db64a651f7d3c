"""A delivery scope's share of its HBM roofline, in percent: the least
time one iteration's bytes could take at the chip's published HBM
bandwidth, over the device time the scope took per iteration.

The bytes are a LOWER bound (``benchmarks/rooflines.py``), so the
share cannot pass 100%; the bound is bandwidth, not compute (a gather
and an add per edge is 1 FLOP per 4+ bytes)."""

from benchmarks import rooflines
from benchmarks.readers import scope_ms


def read(spec, run):
    if run.peaks is None:
        return None
    for scopes in spec["scope_sets"]:       # the first set that ran
        s = scope_ms.seconds_per_iter({"scopes": scopes}, run)
        if s is not None:
            least = rooflines.least_bytes_per_iteration(
                run.graph["nv"], run.graph["stored_edges"]) / run.chips
            return 100.0 * (least / run.peaks["hbm_bytes_per_s"]) / s
    return None
