#!/usr/bin/env python3
"""The control of how ``ssspw.road.delta`` decides ``correct``: what
its two numbers read on answers that are wrong by the least a search
can be, and on the nearest precision below the configuration's.  Each
has to come out as NOT correct.

- ``one_more``: the reference's own answer with ONE reached vertex's
  distance raised by 1;
- ``one_sweep_short``: the relaxation (synchronous sweeps over the
  fronts, what a search by plain frontiers does trip by trip) stopped
  one sweep before its fixed point;
- ``float32_labels``: the fixed point of the same relaxation on the
  same weights with float32 labels, which is what the program gave
  integer weights before it had int32 distances: exact while every
  sum stays under 2^24 = 16,777,216, rounded to even beyond.  It has
  to FAIL like the other two, whatever the data: a ground on which
  no distance passes 2^24 (``USA-road-d.FLA``'s counts on 800 x 850
  km: the largest is 0.93 x 2^24) cannot tell the configuration's
  precision from the one below it, and is no size for the cell.

Each reads (``road_mismatched_dists``, ``road_certificate_violations``)
as the runner's ``verify`` computes them, against Dijkstra and the
certificate of ``reference/dijkstra.py``, summed over the cell's own
roots on the cell's own graph (its ``graph_seed``, or ``--seed``).
Plain NumPy, no device, nothing of ``lux_tpu``.  Exit code 0 when
every control FAILS a limit, as it must, and the sound answer passes.

    python3 benchmarks/control_road.py [--seed N] [--rehearsal]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def sweeps(out_off, out_dst, out_w, root: int, dtype, unreached):
    """Synchronous relaxation by fronts in ``dtype`` (labels and
    sums): a sweep offers ``label[u] + w`` over every arc out of the
    vertices the last sweep improved and keeps the minimum -> (fixed
    point, the labels one sweep before it, sweeps).  ``fl(a + w)`` is
    monotone in ``a`` for float32 too, so the fixed point is the one
    every schedule reaches."""
    import numpy as np
    nv = len(out_off) - 1
    w = out_w.astype(dtype)
    label = np.full(nv, unreached, dtype=dtype)
    label[int(root)] = 0
    short = label
    front = np.asarray([int(root)])
    n = 0
    while len(front):
        lo = out_off[front]
        deg = out_off[front + 1] - lo
        idx = np.repeat(lo - (np.cumsum(deg) - deg), deg) \
            + np.arange(int(deg.sum()))
        cand = np.repeat(label[front], deg) + w[idx]
        new = label.copy()
        np.minimum.at(new, out_dst[idx], cand)
        front = np.flatnonzero(new < label)
        n += 1
        if len(front):
            short, label = label, new
    return label, short, n


def control_numbers(offsets, src, w, roots, seed: int,
                    unreached: int) -> dict:
    """{control: [mismatched, certificate violations]} summed over
    ``roots``, with the relaxation's length and the largest
    distance."""
    import numpy as np
    from benchmarks.reference import dijkstra as ref
    nv = len(offsets) - 1
    dst = np.repeat(np.arange(nv, dtype=np.int32), np.diff(offsets))
    out = ref.by_source(offsets, src, w)
    names = ("sound", "one_more", "one_sweep_short", "float32_labels")
    nums = {k: [0, 0] for k in names}
    info = {"sweeps": [], "largest_distance": [], "float32_rounded": []}
    rng = np.random.default_rng([int(seed), 9])
    for root in roots:
        want = ref.dijkstra(offsets, src, w, root, unreached)

        def add(name, got):
            nums[name][0] += ref.mismatched(got, want)
            nums[name][1] += ref.certificate(
                got, src, dst, w, root, unreached)["violations"]

        exact, short, n = sweeps(*out, root, np.int64, unreached)
        add("sound", exact)
        reached = np.flatnonzero((want != unreached) & (want > 0))
        off = want.copy()
        off[int(rng.choice(reached))] += 1
        add("one_more", off)
        add("one_sweep_short", short)
        f32 = sweeps(*out, root, np.float32, np.float32(np.inf))[0]
        as_int = np.where(np.isfinite(f32), f32, unreached
                          ).astype(np.int64)
        add("float32_labels", as_int)
        info["sweeps"].append(int(n))
        info["largest_distance"].append(int(want[reached].max()))
        info["float32_rounded"].append(
            int(np.count_nonzero(as_int != want)))
    return {**nums, **info}


def main(argv=None) -> int:
    import numpy as np
    from benchmarks import harness
    from benchmarks.reference import roadnet
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ssspw.road.delta")
    ap.add_argument("--seed", type=int, default=None,
                    help="graph seed of the control's instance "
                         "(default: the cell's graph_seed)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="the configuration's rehearsal size")
    args = ap.parse_args(argv)
    _cell, c, traffic = harness.cell_of(harness.load_benchmark(),
                                        args.workload)
    if args.rehearsal:
        c = {**c, **c["rehearsal"]}
    seed = c["graph_seed"] if args.seed is None else args.seed
    nv = int(c["vertices"])
    u, v, w, _info = roadnet.road_edges(
        nv, c["arcs"], seed, **c["shape"], extent_km=c["extent_km"])
    offsets, by_src, by_w = roadnet.by_destination(
        *roadnet.both_directions(u, v, w), nv)
    del u, v, w
    has_edge = np.flatnonzero(np.diff(offsets))
    roots = [int(r) for r in np.random.default_rng([seed, 2]).choice(
        has_edge, size=int(traffic["roots"]), replace=False)]
    nums = control_numbers(offsets, by_src, by_w, roots, seed,
                           int(c["unreached"]))
    limits = [c["guarantees"]["road_mismatched_dists"],
              c["guarantees"]["road_certificate_violations"]]
    controls = ("one_more", "one_sweep_short", "float32_labels")
    fails = {k: any(n > lim for n, lim in zip(nums[k], limits))
             for k in controls}
    sound = all(n <= lim for n, lim in zip(nums["sound"], limits))
    print(json.dumps({"workload": args.workload, "seed": seed,
                      "roots": roots, "stored_edges": int(offsets[-1]),
                      "control": nums, "limits": limits,
                      "two_to_24": 1 << 24,
                      "control_fails": fails, "sound_passes": sound}))
    # a control that passes is a fault (float32 labels pass where no
    # distance passes 2^24: such a size is no size for the cell), and
    # so is a sound answer that does not
    return 0 if all(fails.values()) and sound else 1


if __name__ == "__main__":
    sys.exit(main())
