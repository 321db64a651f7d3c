#!/usr/bin/env python3
"""The control of how ``cf.netflix`` decides ``correct``: what its
comparison reads when the sweep is computed one step below float32,
with the operands of the contractions rounded to bfloat16 as a TPU
does at the default precision.  It has to come out as NOT correct.

Two controls, each the plain float64 reference
(``benchmarks/reference/colfilter.py``) with a rounding put in:

- ``dot``: the operands of the inner product <old[s], old[d]> in
  bfloat16 (what ``D = S @ T^T`` at the default precision does);
- ``dot_msgs``: the messages ``err * old[s]`` rounded too (the one-hot
  gradient matmul's float operand at the default precision).

Plain NumPy, no device, at the cell's own size on a rating matrix of
its own ``--seed`` (generated, not cached).  Exit code 0 when both
controls FAIL a limit, as they must.

    python3 benchmarks/control_colfilter.py --workload cf.netflix --seed 2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def control_numbers(offsets, src, rating, iterations: int) -> dict:
    """{"dot": numbers, "dot_msgs": numbers}: the check's numbers of
    each control against the float64 reference."""
    import ml_dtypes
    from benchmarks.reference import colfilter as ref
    bf16 = ml_dtypes.bfloat16
    want = ref.sweeps(offsets, src, rating, iterations)
    init = ref.initial_factors(len(offsets) - 1)
    rmse_init = ref.rmse(offsets, src, rating, init)
    rmse_want = ref.rmse(offsets, src, rating, want)
    out = {}
    for name, kw in (("dot", dict(dot_dtype=bf16)),
                     ("dot_msgs", dict(dot_dtype=bf16, msg_dtype=bf16))):
        low = ref.sweeps(offsets, src, rating, iterations, **kw)
        out[name] = ref.compare_factors(
            low, want, init, ref.rmse(offsets, src, rating, low),
            rmse_want, rmse_init)
    return out


def failed_limits(numbers: dict, limits: dict) -> list:
    return [k for k, v in numbers.items() if not v <= limits[k]]


def main(argv=None) -> int:
    from benchmarks import harness
    from benchmarks.reference import ratings as gen
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="cf.netflix")
    ap.add_argument("--seed", type=int, required=True,
                    help="graph seed of the control's own matrix")
    ap.add_argument("--rehearsal", action="store_true",
                    help="the configuration's rehearsal size")
    args = ap.parse_args(argv)
    _cell, c, _traffic = harness.cell_of(harness.load_benchmark(),
                                         args.workload)
    if args.rehearsal:
        c = {**c, **c["rehearsal"]}
    user, item, rating = gen.rating_pairs(
        c["users"], c["items"], c["ratings"], args.seed,
        c["user_skew"], c["item_skew"], c["rating_marginal"])
    edges = gen.by_destination(user, item, rating, c["users"],
                               c["items"])
    del user, item, rating
    nums = control_numbers(*edges, c["iterations"])
    limits = {k: v for k, v in c["guarantees"].items()
              if not k.startswith("_")}
    fails = {name: failed_limits(n, limits) for name, n in nums.items()}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "stored_edges": int(edges[0][-1]),
                      "control": nums, "limits": limits,
                      "control_fails": fails}))
    return 0 if all(fails.values()) else 1   # a control that passes
                                             # is a fault


if __name__ == "__main__":
    sys.exit(main())
