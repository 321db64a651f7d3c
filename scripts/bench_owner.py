"""A/B the pull exchanges: gather (all-gather + big-table gather) vs
owner (per-src-part small-shard gathers + reduce_scatter), driver
methodology (fused iterations, host-fetch fence).

Usage: PYTHONPATH=/root/repo python \
    scripts/bench_owner.py [scale] [ef] [np] [pair] [owner_E] [ni]
"""

import sys
import time

import numpy as np

scale = int(sys.argv[1]) if len(sys.argv) > 1 else 21
ef = int(sys.argv[2]) if len(sys.argv) > 2 else 16
nparts = int(sys.argv[3]) if len(sys.argv) > 3 else 4
pair = int(sys.argv[4]) if len(sys.argv) > 4 else 0
owner_E = int(sys.argv[5]) if len(sys.argv) > 5 else 256
ni = int(sys.argv[6]) if len(sys.argv) > 6 else 10

from lux_tpu.apps import pagerank
from lux_tpu.convert import rmat_graph
from lux_tpu.engine.pull import PullEngine
from lux_tpu.graph import ShardedGraph, pair_relabel
from lux_tpu.timing import timed_fused_run

t0 = time.time()
g = rmat_graph(scale=scale, edge_factor=ef, seed=0)
print(f"graph nv={g.nv} ne={g.ne} ({time.time() - t0:.0f}s)",
      flush=True)
pair_t = pair if pair > 0 else None
t0 = time.time()
g2, _perm, starts = pair_relabel(g, nparts, pair_threshold=pair_t or 16)
sg = ShardedGraph.build(g2, nparts, starts=starts,
                        pair_threshold=pair_t or 16)
print(f"relabel+build ({time.time() - t0:.0f}s) vpad={sg.vpad} "
      f"epad={sg.epad}", flush=True)


def bench(tag, **kw):
    t0 = time.time()
    eng = PullEngine(sg, pagerank.make_program(), pair_threshold=pair_t,
                     **kw)
    own = getattr(eng, "owner", None)
    extra = f" owner_stats={own.stats}" if own is not None else ""
    print(f"{tag}: engine ({time.time() - t0:.0f}s){extra}", flush=True)
    state, [el] = timed_fused_run(eng, ni)
    assert np.isfinite(eng.unpad(state)).all()
    gteps = g.ne * ni / el / 1e9
    print(f"{tag}: {el / ni * 1e3:.0f} ms/iter  "
          f"{el / ni / g.ne * 1e9:.1f} ns/edge  {gteps:.4f} GTEPS",
          flush=True)
    del eng


order = sys.argv[7] if len(sys.argv) > 7 else "go"
for c in order:           # interleavable A/B: e.g. "gogo"
    if c == "g":
        bench("gather", tile_e=128 if pair_t else 512)
    else:
        bench("owner", exchange="owner", owner_tile_e=owner_E)
