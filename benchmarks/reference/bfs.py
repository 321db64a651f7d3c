"""Plain frontier breadth-first search, NumPy: hop distance from one
root over out-edges; -1 where the root does not reach."""

from __future__ import annotations

import numpy as np


def bfs_levels(offsets, neighbours, root: int):
    nv = len(offsets) - 1
    if not 0 <= root < nv:
        raise ValueError(f"root {root} out of range [0, {nv})")
    level = np.full(nv, -1, dtype=np.int32)
    level[root] = 0
    frontier = np.array([root], dtype=np.int64)
    depth = 0
    while frontier.size:
        lo = offsets[frontier]
        cnt = offsets[frontier + 1] - lo
        total = int(cnt.sum())
        if not total:
            break
        # positions of every out-edge of the frontier, without a loop
        first = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
        nb = neighbours[first + np.arange(total, dtype=np.int64)]
        seen = np.zeros(nv, dtype=bool)
        seen[nb] = True
        seen &= level < 0
        frontier = np.flatnonzero(seen)
        depth += 1
        level[frontier] = depth
    return level


def hops_to_levels(hops, nv: int):
    """The program's hop labels -> the reference's convention: any
    label that no path can have (>= nv; the program's 'infinity'
    sentinel) reads as -1."""
    hops = np.asarray(hops)
    return np.where((hops < 0) | (hops >= nv), -1, hops).astype(np.int32)
