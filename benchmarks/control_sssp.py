#!/usr/bin/env python3
"""The control of how ``ssspw.kron21.delta`` decides ``correct``: what
its two numbers read on answers that are wrong by the least a search
can be, and on the nearest precision below the configuration's.  Each
has to come out as NOT correct.

- ``one_ulp``: the reference's own fixed point with ONE reached
  vertex's distance raised by one unit in the last place;
- ``one_sweep_short``: the relaxation stopped one synchronous sweep
  before its fixed point;
- ``bfloat16_weights``: the fixed point of the same arcs with their
  weights rounded to bfloat16 (float32 arithmetic).

Each reads (``sssp_mismatched_dists``, ``sssp_edges_violated``) as the
runner's ``verify`` computes them, against the float32 fixed point and
the float32 weights.  Plain NumPy, no device, nothing of ``lux_tpu``:
at the cell's own size on a graph of its own ``--seed`` (generated,
not cached; a few minutes), from one root of non-zero degree drawn
from the seed.  Exit code 0 when every control FAILS a limit, as it
must.

    python3 benchmarks/control_sssp.py --workload ssspw.kron21.delta --seed 2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def control_numbers(offsets, src, w, root: int, seed: int) -> dict:
    """{control: [mismatched, edges violated], "sweeps": the
    relaxation's length}."""
    import numpy as np
    from benchmarks.reference import sssp as ref
    want, sweeps, short = ref.fixed_point_f32(offsets, src, w, root,
                                              before_last=True)
    nv = len(offsets) - 1
    dst = np.repeat(np.arange(nv, dtype=np.int32), np.diff(offsets))

    def numbers(got):
        return [ref.mismatched(got, want),
                ref.edges_violated(got, src, dst, w)
                + ref.roots_nonzero(got, root)]

    off = want.copy()
    reached = np.flatnonzero(np.isfinite(want) & (want > 0))
    v = int(np.random.default_rng([int(seed), 9]).choice(reached))
    off[v] = np.nextafter(off[v], np.float32(np.inf))
    rounded = ref.fixed_point_f32(offsets, src, ref.to_bfloat16(w),
                                  root)[0]
    return {"one_ulp": numbers(off),
            "one_sweep_short": numbers(short),
            "bfloat16_weights": numbers(rounded),
            "sound": numbers(want),
            "sweeps": int(sweeps)}


def main(argv=None) -> int:
    import numpy as np
    from benchmarks import harness
    from benchmarks.reference import edge_weights
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ssspw.kron21.delta")
    ap.add_argument("--seed", type=int, required=True,
                    help="graph seed of the control's own instance")
    ap.add_argument("--rehearsal", action="store_true",
                    help="the configuration's rehearsal size")
    args = ap.parse_args(argv)
    _cell, c, _traffic = harness.cell_of(harness.load_benchmark(),
                                         args.workload)
    if args.rehearsal:
        c = {**c, **c["rehearsal"]}
    nv = 1 << c["scale"]
    src, dst, w = edge_weights.kernel3_arcs(
        c["scale"], c["edge_factor"], c["symmetrized"], args.seed)
    offsets, by_src, by_w = edge_weights.by_destination(src, dst, w, nv)
    del src, dst, w
    has_edge = np.flatnonzero(np.bincount(by_src, minlength=nv))
    root = int(np.random.default_rng([args.seed, 8]).choice(has_edge))
    nums = control_numbers(offsets, by_src, by_w, root, args.seed)
    limits = [c["guarantees"]["sssp_mismatched_dists"],
              c["guarantees"]["sssp_edges_violated"]]
    controls = ("one_ulp", "one_sweep_short", "bfloat16_weights")
    fails = {k: any(n > lim for n, lim in zip(nums[k], limits))
             for k in controls}
    sound = all(n <= lim for n, lim in zip(nums["sound"], limits))
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "root": root, "stored_edges": int(offsets[-1]),
                      "control": nums, "limits": limits,
                      "control_fails": fails, "sound_passes": sound}))
    # a control that passes is a fault, and so is a sound answer that
    # does not
    return 0 if all(fails.values()) and sound else 1


if __name__ == "__main__":
    sys.exit(main())
