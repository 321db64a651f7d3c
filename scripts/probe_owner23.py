"""Probe the owner engine's real-graph cost vs the synthetic floor
(profile_owner2.py measured gather+partials+combine = 9.9 ns/slot on
the same geometry; the engine A/B read 21-33 ns/edge).

Caches the pair-relabeled graph + starts in /tmp so repeated probes
skip the ~6 min gen+relabel.

Usage: PYTHONPATH=/root/repo python \
    scripts/probe_owner23.py [scale np E ni]
"""

import os
import sys
import time

import numpy as np

args = [a for a in sys.argv[1:] if not a.startswith("-")]
flags = {a for a in sys.argv[1:] if a.startswith("-")}
scale = int(args[0]) if len(args) > 0 else 23
nparts = int(args[1]) if len(args) > 1 else 4
owner_E = int(args[2]) if len(args) > 2 else 256
ni = int(args[3]) if len(args) > 3 else 6

from lux_tpu.apps import pagerank
from lux_tpu.convert import rmat_graph
from lux_tpu.engine.pull import PullEngine
from lux_tpu.graph import Graph, ShardedGraph, pair_relabel
from lux_tpu.timing import timed_fused_run

cache = f"/tmp/relab_s{scale}_np{nparts}.npz"
t0 = time.time()
if os.path.exists(cache):
    z = np.load(cache)
    g2 = Graph(nv=int(z["nv"]), ne=int(z["ne"]), row_ptrs=z["row_ptrs"],
               col_idx=z["col_idx"], weights=None,
               out_degrees=z["deg"])
    starts = z["starts"]
    print(f"cache hit ({time.time() - t0:.0f}s)", flush=True)
else:
    g = rmat_graph(scale=scale, edge_factor=16, seed=0)
    g2, _perm, starts = pair_relabel(g, nparts, pair_threshold=16)
    np.savez(cache, nv=g2.nv, ne=g2.ne, row_ptrs=g2.row_ptrs,
             col_idx=g2.col_idx, deg=g2.out_degrees, starts=starts)
    print(f"gen+relabel+cache ({time.time() - t0:.0f}s)", flush=True)

t0 = time.time()
sg = ShardedGraph.build(g2, nparts, starts=starts, pair_threshold=16)
print(f"sg build ({time.time() - t0:.0f}s) vpad={sg.vpad} "
      f"({sg.vpad * 4 / 1e6:.0f} MB/shard)", flush=True)

t0 = time.time()
eng = PullEngine(sg, pagerank.make_program(), exchange="owner",
                 owner_tile_e=owner_E)
print(f"owner engine ({time.time() - t0:.0f}s) stats={eng.owner.stats} "
      f"C={eng.owner.n_chunks} streams={eng.owner.streams()}",
      flush=True)

# phase split (separate fenced programs; relative weights)
if "-no-phases" not in flags:
    _s, rep = eng.timed_phases(eng.init_state(), 3)
    for i, t in enumerate(rep):
        print(f"iter {i}: " + "  ".join(f"{k}={v * 1e3:7.1f}ms"
                                        for k, v in t.items()),
              flush=True)

from lux_tpu.timing import fence

if "-stepwise" in flags:
    # per-iteration jitted steps (async dispatch, one final fence) —
    # isolates the fori_loop program from the step program
    state = eng.init_state()
    state = eng.step(state)
    fence(state)                       # compile + settle
    state = eng.init_state()
    fence(state)
    t0 = time.time()
    for _ in range(ni):
        state = eng.step(state)
    fence(state)
    el = time.time() - t0
    print(f"owner stepwise: {el / ni * 1e3:.0f} ms/iter  "
          f"{el / ni / g2.ne * 1e9:.1f} ns/edge  "
          f"{g2.ne * ni / el / 1e9:.4f} GTEPS", flush=True)

# fused timing
state, [el] = timed_fused_run(eng, ni)
assert np.isfinite(eng.unpad(state)).all()
print(f"owner fused: {el / ni * 1e3:.0f} ms/iter  "
      f"{el / ni / g2.ne * 1e9:.1f} ns/edge  "
      f"{g2.ne * ni / el / 1e9:.4f} GTEPS", flush=True)
