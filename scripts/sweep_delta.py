"""Delta-stepping bucket-width sweep at the bench shape (VERDICT r3
next #7): BENCH_r03 measured sssp-delta (delta=mean weight) BELOW
plain frontier relaxation.  Structural context: every iteration of
the push engine is fixed-shape (dense = all edges; sparse = static
queue_cap/edge_budget), so delta-stepping cannot shrink per-iteration
cost — it can only (a) flip iterations from dense to the much cheaper
sparse path by keeping frontiers under nv/16, or (b) waste time on
relax-free bucket advances.  This sweep measures where that trade
lands.

Usage:
  PYTHONPATH=/root/repo python scripts/sweep_delta.py \
      [scale=21] [ef=16] [repeats=3]

Prints one JSON line per width: the timed converge (median of
repeats), iterations, and GTEPS alongside the plain (delta=None) run.
"""

from __future__ import annotations

import json
import sys
import time


def main():
    scale = int(sys.argv[1]) if len(sys.argv) > 1 else 21
    ef = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    repeats = int(sys.argv[3]) if len(sys.argv) > 3 else 3

    import numpy as np

    from lux_tpu.apps import sssp
    from lux_tpu.convert import rmat_graph
    from lux_tpu.graph import pair_relabel
    from lux_tpu.timing import timed_converge

    t0 = time.time()
    g = rmat_graph(scale=scale, edge_factor=ef, seed=0)
    rng = np.random.default_rng(1)
    g.weights = rng.integers(1, 6, size=g.ne).astype(np.int32)
    g2, perm, starts = pair_relabel(g, 1, pair_threshold=16)
    rank = np.empty(g.nv, np.int64)
    rank[perm] = np.arange(g.nv)
    start = int(rank[0])
    print(f"# graph ready nv={g.nv} ne={g.ne} ({time.time()-t0:.0f}s)",
          flush=True)

    want = None
    for delta in [None, 1.0, 2.0, "auto", 5.0, 8.0, 16.0, 64.0]:
        eng = sssp.build_engine(g2, start_vertex=start, num_parts=1,
                                weighted=True, delta=delta,
                                pair_threshold=16, starts=starts)
        labels, iters, elapsed = timed_converge(eng, repeats=repeats)
        if want is None:
            want = labels
        else:
            np.testing.assert_allclose(labels, want, rtol=1e-6)
        med = sorted(elapsed)[len(elapsed) // 2]
        print(json.dumps({
            "delta": ("none" if delta is None else
                      round(eng.delta or 0, 3) if delta == "auto"
                      else delta),
            "iters": int(iters),
            "elapsed": [round(e, 3) for e in elapsed],
            "gteps": round(g.ne * iters / med / 1e9, 4)}), flush=True)


if __name__ == "__main__":
    main()
