#!/usr/bin/env python3
"""Rehearsal entry: drives a whole run of one cell at the
configuration's own ``rehearsal`` sizes on whatever backend is here
(``JAX_PLATFORMS=cpu``), without the look for a chip.  It finds wrong
paths, arguments and control flow; it measures nothing.  Its output
can never be read as a chip result: the values are printed under
``rehearsal_values`` and the last line is not the contract's object.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py --workload pr.kron21 \
        --seed 3 --seconds 2 --trace 0
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmarks import harness
    args = harness.parse_args(sys.argv[1:] if argv is None else argv)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), rehearsal=True)
    result["rehearsal_values"] = result.pop("metrics")
    print(json.dumps(result))
    print("REHEARSAL, NOT A CHIP RUN")
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
