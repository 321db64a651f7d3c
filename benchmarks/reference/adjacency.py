"""The reference's own adjacency: out-neighbours by source vertex,
built from the generated edge list by the benchmark's own code (the
program's converter is not involved)."""

from __future__ import annotations

import numpy as np


def by_source(src, dst, nv: int):
    """(offsets int64 [nv+1], neighbours int32 [ne]): the out-edges of
    vertex v are ``neighbours[offsets[v]:offsets[v+1]]``, sorted by
    destination.  Duplicates and self-loops are kept."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    if src.size and (int(src.max()) >= nv or int(dst.max()) >= nv):
        raise ValueError("edge endpoint out of range")
    key = src.astype(np.uint64) << np.uint64(32)
    key |= dst.astype(np.uint64)
    key.sort()
    neighbours = (key & np.uint64(0xFFFFFFFF)).astype(np.int32)
    offsets = np.zeros(nv + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=nv), out=offsets[1:])
    return offsets, neighbours
