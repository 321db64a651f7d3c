#!/usr/bin/env python3
"""The control of the limits that ``kron20-serve-open`` brings: what
the comparison reads when the service does one step less than the
configuration guarantees.  It has to come out as NOT correct.

- delivery held to the drain's end, which is what ``Server.run()`` did
  before the serving loop (a caller got its response when NO kind had
  work left): a plain model of the service (``batch`` columns, a turn
  of ``--segment-s`` + ``--boundary-s``, ``--turns`` turns a query:
  the closed cell's figures, ``PERF.md`` section 5) is fed the cell's
  own arrival schedule, and the instants it produces go through the
  same count the runner uses (``reference/arrivals.delivered_late``).
  Below the knee a drain seldom ends, so it has to read in the
  hundreds; the same model with the hand-over at each turn's end has
  to read 0;
- exact hop distances: the reference's own answer with the level of
  one reached vertex off by one (``control.level_control``).

Plain NumPy and lists, no device.

    python3 benchmarks/control_open.py --workload ksssp.kron20.open80 --seed 1
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def service_model(instants, batch, segment_s, boundary_s, turns,
                  held: bool):
    """(retired, received, turn_starts): a continuous-batching service
    over the arrival ``instants``.  A turn is a segment and its
    boundary; the boundary retires the queries that have stayed
    ``turns`` turns and gives free columns to the queued arrivals,
    first come first served.  Responses reach the caller at the end of
    the turn that retired them, or (``held``) when the drain ends:
    nothing queued, nothing resident."""
    queue = list(instants)
    resident = []                   # turns each column's query has left
    retired, received, turn_starts, waiting = [], [], [], []
    t = 0.0
    while queue or resident:
        if not resident and queue[0] > t:
            t = queue[0]            # idle until the next arrival
        while queue and queue[0] <= t and len(resident) < batch:
            queue.pop(0)
            resident.append(turns)
        turn_starts.append(t)
        t_retire = t + segment_s + boundary_s / 2
        t += segment_s + boundary_s
        resident = [n - 1 for n in resident]
        for _ in range(resident.count(0)):
            retired.append(t_retire)
            waiting.append(len(retired) - 1)
        resident = [n for n in resident if n]
        drained = not resident and not (queue and queue[0] <= t)
        if waiting and (drained or not held):
            received += [t] * len(waiting)
            waiting = []
    return retired, received, turn_starts


def delivery_control(config, traffic, seconds, segment_s, boundary_s,
                     turns):
    """``delivered_late`` of the held and of the sound service."""
    from benchmarks.reference import arrivals
    instants = arrivals.until(float(traffic["rate_qps"]),
                              int(traffic["arrival_seed"]),
                              float(traffic["warm_s"]) + seconds)
    out = {}
    for name, held in (("delivered_late", True),
                       ("delivered_late_sound", False)):
        out[name] = arrivals.delivered_late(*service_model(
            instants, int(config["batch"]), segment_s, boundary_s,
            turns, held))
    out["queries"] = len(instants)
    return out


def main(argv=None) -> int:
    from benchmarks import control, graphs, harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True,
                    help="graph seed of the control's own instance")
    ap.add_argument("--scale", type=int, default=None,
                    help="the instance's scale (default: the "
                         "configuration's)")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--segment-s", type=float, default=0.973)
    ap.add_argument("--boundary-s", type=float, default=0.043)
    ap.add_argument("--turns", type=int, default=2)
    args = ap.parse_args(argv)
    _cell, config, traffic = harness.cell_of(harness.load_benchmark(),
                                             args.workload)
    limits = config["guarantees"]
    model = delivery_control(config, traffic, args.seconds,
                             args.segment_s, args.boundary_s,
                             args.turns)
    paths = graphs.ensure(args.scale or config["scale"],
                          config["edge_factor"], config["symmetrized"],
                          args.seed)
    offsets, neighbours = graphs.load_reference(paths)
    root = int(np.flatnonzero(np.diff(offsets) > 0)[args.seed % 97])
    nums = {"delivered_late": model["delivered_late"],
            "hops_mismatched": control.level_control(
                offsets, neighbours, root)}
    failed = [k for k, v in nums.items() if not v <= limits[k]]
    # the hand-over at each turn's end has to pass where the held one
    # fails, and the held one has to fail by hundreds, not by a few
    sound = model["delivered_late_sound"] <= limits["delivered_late"]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "model": model, "control": nums,
                      "limits": {k: limits[k] for k in nums},
                      "control_fails": failed,
                      "sound_model_passes": sound}))
    ok = (len(failed) == len(nums) and sound
          and nums["delivered_late"] >= 100)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
