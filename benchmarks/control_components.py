#!/usr/bin/env python3
"""The control of how ``cc.indochina`` decides ``correct``: what its
comparison reads on two answers that are wrong by the least a
propagation can be.  Each has to come out as NOT correct.

- ``one_label``: the reference's own fixed point with ONE vertex's
  label lowered by one;
- ``one_sweep_short``: the propagation stopped one synchronous sweep
  before its fixed point.

Plain NumPy, no device, nothing of ``lux_tpu``: at the cell's own size
on a crawl of its own ``--seed`` (generated, not cached; about four
minutes and 6 GB), the vertices numbered by a permutation drawn from
the seed, as the engine's relabel numbers them by one of its own.
Exit code 0 when both controls FAIL the limit, as they must.

    python3 benchmarks/control_components.py --workload cc.indochina --seed 2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def control_numbers(offsets, src, label0, seed: int) -> dict:
    """{"one_label": mismatches, "one_sweep_short": mismatches,
    "sweeps": the propagation's length}."""
    import numpy as np
    from benchmarks.reference import components as ref
    want, sweeps, short = ref.fixed_point(offsets, src, label0,
                                          before_last=True)
    off = want.copy()
    v = int(np.random.default_rng([int(seed), 9]).integers(len(want)))
    off[v] -= 1
    return {"one_label": ref.mismatched(off, want),
            "one_sweep_short": ref.mismatched(short, want),
            "sweeps": int(sweeps)}


def main(argv=None) -> int:
    import numpy as np
    from benchmarks import harness
    from benchmarks.reference import webgraph as gen
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="cc.indochina")
    ap.add_argument("--seed", type=int, required=True,
                    help="graph seed of the control's own crawl")
    ap.add_argument("--rehearsal", action="store_true",
                    help="the configuration's rehearsal size")
    args = ap.parse_args(argv)
    _cell, c, _traffic = harness.cell_of(harness.load_benchmark(),
                                         args.workload)
    if args.rehearsal:
        c = {**c, **c["rehearsal"]}
    nv = c["vertices"]
    src, dst = gen.web_arcs(nv, c["arcs"], args.seed,
                            **{k: c[k] for k in gen.PARAMETERS})
    offsets, by_src = gen.by_destination(src, dst, nv)
    del src, dst
    label0 = np.random.default_rng([args.seed, 8]).permutation(nv)
    nums = control_numbers(offsets, by_src, label0, args.seed)
    limit = c["guarantees"]["cc_mismatched_labels"]
    fails = {k: nums[k] > limit
             for k in ("one_label", "one_sweep_short")}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "stored_edges": int(offsets[-1]),
                      "control": nums, "limit": limit,
                      "control_fails": fails}))
    return 0 if all(fails.values()) else 1   # a control that passes
                                             # is a fault


if __name__ == "__main__":
    sys.exit(main())
