#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Prints, as the last line of its standard output, the contract's result
object.  Exits non-zero, with no result, on any platform but a TPU.
"""

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import os       # noqa: E402
import sys      # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks import harness
    sys.exit(harness.main(t_process=T_PROCESS))
