#!/usr/bin/env python3
"""The control of "how correct is decided": what the comparison reads
when the answer is computed one step below what the configuration
guarantees.  It has to come out as NOT correct.

- float32 PageRank ranks: the plain reference with the per-vertex
  share stored in bfloat16 and the sums in float32, against the
  float64 reference, at the cell's own size;
- exact BFS levels / hop distances: the reference's own answer with
  the level of one reached vertex off by one.

Plain NumPy, no device: run on the chip machine after a cell's runs
(the graph cache is then filled) or anywhere else.

    python3 benchmarks/control.py --workload pr.kron21 --seed 11
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def rank_control(offsets, neighbours, iterations):
    """The two rank numbers of the bfloat16-state control."""
    import ml_dtypes
    from benchmarks.reference import pagerank as ref
    want = ref.pagerank(offsets, neighbours, iterations)
    low = ref.pagerank(offsets, neighbours, iterations,
                       state_dtype=ml_dtypes.bfloat16)
    return ref.compare_ranks(low, want)


def level_control(offsets, neighbours, root):
    """Mismatches of a BFS answer with one level off by one."""
    from benchmarks.reference import bfs as ref
    want = ref.bfs_levels(offsets, neighbours, root)
    off = want.copy()
    victim = int(np.flatnonzero(want > 0)[0])
    off[victim] += 1
    return int(np.count_nonzero(off != want))


def control_numbers(config, paths, seed):
    from benchmarks import graphs
    offsets, neighbours = graphs.load_reference(paths)
    limits = config["guarantees"]
    out = {}
    if "rank_max_rel_err" in limits:
        out.update(rank_control(offsets, neighbours,
                                config["iterations"]))
    for name in ("bfs_mismatched_levels", "hops_mismatched"):
        if name in limits:
            deg = np.diff(offsets)
            root = int(np.flatnonzero(deg > 0)[seed % 97])
            out[name] = level_control(offsets, neighbours, root)
    return out


def main(argv=None) -> int:
    from benchmarks import graphs, harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True,
                    help="graph seed of the control's own instance")
    args = ap.parse_args(argv)
    _cell, config, _traffic = harness.cell_of(harness.load_benchmark(),
                                              args.workload)
    paths = graphs.ensure(config["scale"], config["edge_factor"],
                          config["symmetrized"], args.seed)
    nums = control_numbers(config, paths, args.seed)
    limits = config["guarantees"]
    failed = [k for k, v in nums.items() if not v <= limits[k]]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control": nums,
                      "limits": {k: limits[k] for k in nums},
                      "control_fails": failed}))
    return 0 if failed else 1      # a control that passes is a fault


if __name__ == "__main__":
    sys.exit(main())
