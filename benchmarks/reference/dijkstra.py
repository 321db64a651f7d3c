"""Exact single-source shortest paths on integer weights, plain Python
and NumPy: a binary-heap Dijkstra in unbounded integers over the
reference's own adjacency, and an O(E) certificate that any claimed
answer is THE answer.

WHY THE CERTIFICATE IS ENOUGH.  Let every weight be a positive
integer and ``d`` a claimed answer with ``unreached`` for "no path".
Three rules, each one vectorised pass over the stored arcs:

1. ``d[root] = 0``, and no reached distance is negative;
2. FEASIBLE: every arc ``u -> v`` out of a reached ``u`` has
   ``d[v] <= d[u] + w`` (an unreached ``v`` there breaks it).  By
   induction along a true shortest path, ``d[v]`` is then at most the
   true distance, and every vertex with a path is reached;
3. SUPPORTED: every reached ``v != root`` has an arc ``u -> v`` from a
   reached ``u`` with ``d[v] = d[u] + w``.  Following such arcs back,
   the distance falls by at least 1 a step (weights are positive), so
   the walk ends, and only the root may lack support: ``d[v]`` is the
   length of a real path from the root, at least the true distance,
   and a vertex without a path cannot be reached.

Together: ``d`` is exact on every vertex, reached or not.  Nothing
here needs the Dijkstra below; the runner applies it to EVERY search
of a window and Dijkstra to the checked ones.

This module imports nothing of the program under test.
"""

from __future__ import annotations

import heapq

import numpy as np


def by_source(offsets, src, w):
    """The arcs sorted by destination -> the same arcs as out-lists
    (out_offsets int64 [nv + 1], out_dst int64, out_w in ``w``'s
    type)."""
    offsets = np.asarray(offsets)
    nv = len(offsets) - 1
    dst = np.repeat(np.arange(nv, dtype=np.int64), np.diff(offsets))
    order = np.argsort(src, kind="stable")
    out_off = np.zeros(nv + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=nv), out=out_off[1:])
    return out_off, dst[order], np.asarray(w)[order]


def dijkstra(offsets, src, w, root: int, unreached: int):
    """Binary-heap Dijkstra over the arcs sorted by destination ->
    distances int64 [nv], ``unreached`` where there is no path.
    Python integers throughout: nothing rounds and nothing wraps."""
    # plain Python lists of ints: the loop below indexes them one by one
    out_off, out_dst, out_w = (x.tolist()
                               for x in by_source(offsets, src, w))
    nv = len(out_off) - 1
    dist = [-1] * nv                      # -1: not settled yet
    best = {int(root): 0}
    heap = [(0, int(root))]
    while heap:
        d, u = heapq.heappop(heap)
        if dist[u] >= 0:
            continue
        dist[u] = d
        for e in range(out_off[u], out_off[u + 1]):
            v = out_dst[e]
            if dist[v] < 0:
                nd = d + out_w[e]
                if nd < best.get(v, nd + 1):
                    best[v] = nd
                    heapq.heappush(heap, (nd, v))
    out = np.asarray(dist, dtype=np.int64)
    out[out < 0] = unreached
    return out


def certificate(d, src, dst, w, root: int, unreached: int) -> dict:
    """The three rules over the stored arcs ``src -> dst`` with
    weights ``w`` (module docstring) -> {"root": root's distance is
    not 0 (0 / 1) plus negative distances, "infeasible": arcs that
    break rule 2, "unsupported": reached vertices that break rule 3,
    "violations": their sum}.  int64 arithmetic: exact for every
    int32 answer."""
    d = np.asarray(d, dtype=np.int64)
    w = np.asarray(w, dtype=np.int64)
    if int(w.min(initial=1)) < 1:
        raise ValueError("the certificate needs positive weights")
    reached = d != int(unreached)
    from_reached = reached[src]
    through = d[src] + w
    infeasible = from_reached & (~reached[dst] | (d[dst] > through))
    tight = from_reached & reached[dst] & (d[dst] == through)
    supported = np.zeros(len(d), dtype=bool)
    supported[dst[tight]] = True
    supported[int(root)] = True
    counts = {
        "root": int(d[int(root)] != 0)
        + int(np.count_nonzero(reached & (d < 0))),
        "infeasible": int(np.count_nonzero(infeasible)),
        "unsupported": int(np.count_nonzero(reached & ~supported))}
    counts["violations"] = sum(counts.values())
    return counts


def mismatched(got, want) -> int:
    """How many distances differ (``unreached`` is one value on both
    sides)."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise ValueError(f"answer of shape {got.shape}, reference of "
                         f"shape {want.shape}")
    return int(np.count_nonzero(got.astype(np.int64)
                                != want.astype(np.int64)))
