#!/usr/bin/env python3
"""The control of the limits that ``kron20-serve-mixed`` brings (what
``control.py`` is to the cells before it; ``control.py --workload
mixed.kron20.closed`` covers ``hops_mismatched``): what the comparison
reads when an answer is computed one step below what the configuration
guarantees.  It has to come out as NOT correct.

- personalized PageRank: the plain reference with the per-vertex share
  stored in bfloat16 and the sums in float32, against the float64
  reference, from the first ``--sources`` of the cell's own pagerank
  sources (``serve_mixed.fixed_sources``) and for
  ``--iterations`` iterations (what a response reports), at the cell's
  own size;
- exact reachability: the reference's own labels with one reached
  vertex marked unreached.

Plain NumPy, no device.

    python3 benchmarks/control_mixed.py --workload mixed.kron20.closed \
        --seed 1 --iterations 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def ppr_control(offsets, neighbours, source, iterations):
    """The two personalized-PageRank numbers of the bfloat16-state
    control."""
    import ml_dtypes
    from benchmarks.reference import ppr
    want = ppr.personalized_pagerank(offsets, neighbours, source,
                                     iterations)
    low = ppr.personalized_pagerank(offsets, neighbours, source,
                                    iterations,
                                    state_dtype=ml_dtypes.bfloat16)
    return ppr.compare_ranks(low, want)


def reach_control(offsets, neighbours, seed):
    """Mismatches of a reachability answer that misses one vertex."""
    from benchmarks.reference import reach
    want = reach.reach_labels(offsets, neighbours, seed)
    off = want.copy()
    off[int(np.flatnonzero(want == seed)[-1])] = -1
    return int(np.count_nonzero(off != want))


def control_numbers(offsets, neighbours, sources, iterations):
    """The SMALLEST reading of each number over ``sources``: every one
    of them has to fail its limit."""
    runs = [ppr_control(offsets, neighbours, int(s), iterations)
            for s in sources]
    out = {k: min(r[k] for r in runs) for k in runs[0]}
    out["reach_mismatched"] = min(
        reach_control(offsets, neighbours, int(s)) for s in sources)
    return out


def main(argv=None) -> int:
    from benchmarks import graphs, harness
    from benchmarks.runners import serve_mixed
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True,
                    help="graph seed of the control's own instance")
    ap.add_argument("--iterations", type=int, required=True)
    ap.add_argument("--sources", type=int, default=3)
    args = ap.parse_args(argv)
    _cell, config, traffic = harness.cell_of(harness.load_benchmark(),
                                             args.workload)
    paths = graphs.ensure(config["scale"], config["edge_factor"],
                          config["symmetrized"], args.seed)
    offsets, neighbours = graphs.load_reference(paths)
    # the first of the cell's own pagerank sources, as the runner
    # draws them on this instance
    instance = types.SimpleNamespace(
        config={**config, "graph_seed": args.seed}, traffic=traffic)
    sources = serve_mixed.fixed_sources(
        instance, offsets)["pagerank"][:args.sources]
    nums = control_numbers(offsets, neighbours, sources,
                           args.iterations)
    limits = config["guarantees"]
    failed = [k for k, v in nums.items() if not v <= limits[k]]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "iterations": args.iterations,
                      "sources": [int(s) for s in sources],
                      "control": nums,
                      "limits": {k: limits[k] for k in nums},
                      "control_fails": failed}))
    # a control that passes any of its limits is a fault
    return 0 if len(failed) == len(nums) else 1


if __name__ == "__main__":
    sys.exit(main())
