"""The dot (SDDMM) delivery's share of its roofline, in percent: the
least time one sweep's work could take on the chip, which is the
LARGER of its least bytes over the published HBM bandwidth and its
least operations over the published bf16 peak, over the device time
the scope took per iteration.

Bytes and operations are lower bounds (``benchmarks/dot_rooflines.py``),
so the share cannot pass 100%.  K is the configuration's
(``program_constants``).  ``None`` where the scope did not run, as on
a program without it."""

from benchmarks import dot_rooflines
from benchmarks.readers import scope_ms


def least_seconds(run):
    """(least seconds an iteration, which bound sets it)."""
    k = int(run.config["program_constants"]["K"])
    nv, edges = run.graph["nv"], run.graph["stored_edges"]
    by_bytes = (dot_rooflines.least_bytes_per_iteration(nv, edges, k)
                / run.chips / run.peaks["hbm_bytes_per_s"])
    by_flops = (dot_rooflines.least_flops_per_iteration(edges, k)
                / run.chips / run.peaks["bf16_flops_per_s"])
    return max(by_bytes, by_flops), ("hbm" if by_bytes >= by_flops
                                     else "mxu")


def read(spec, run):
    if run.peaks is None:
        return None
    s = scope_ms.seconds_per_iter({"scopes": spec["scopes"]}, run)
    if s is None:
        return None
    return 100.0 * least_seconds(run)[0] / s
