"""Single-source shortest paths with real-valued weights (Graph500
kernel 3), plain NumPy: the float32 fixed point the program must
reach bit for bit, a float64 Dijkstra that shows what that fixed
point is worth, and kernel 3's validation rule over the edges.

WHY THE COMPARISON IS EXACT.  The program relaxes in float32: a
candidate is ``fl(d[u] + w)``, one rounded addition, and a label only
ever takes the minimum of its candidates.  ``fl(a + w)`` is monotone
in ``a`` (rounding to nearest is monotone), so the sweep below is a
monotone map on labels that start at their upper bound, and such a
map has ONE greatest fixed point below its start: every fair schedule
that only applies ``label[v] <- min(label[v], fl(label[u] + w))``
(dense, sparse, bucketed under any width, in any order) ends in the
same labels, to the bit.  It is the argument ``components.py`` rests
on, with ``min`` and a rounded sum in place of ``max`` and an id.  An
unreached vertex keeps ``+inf`` on both sides.

One departure to keep in mind: the TPU flushes subnormal float32
values to zero, NumPy keeps them.  None can arise here: the weights
are multiples of 2^-24 (``edge_weights.py``) and so is every sum of
them below 2, hence every label is 0 or at least 2^-24, far above the
smallest normal number 2^-126.

This module imports nothing of the program under test.
"""

from __future__ import annotations

import heapq

import numpy as np


def fixed_point_f32(offsets, src, w, root: int,
                    before_last: bool = False):
    """-> (labels float32 [nv], sweeps).  ``offsets`` / ``src`` / ``w``
    are the arcs sorted by destination (``edge_weights.by_destination``);
    labels start at ``+inf``, 0 at ``root``; a sweep offers every arc's
    ``fl32(label[src] + w)`` to its destination and keeps the minimum,
    until a sweep changes nothing.  ``before_last``: also the labels
    one sweep before the fixed point was reached, as a third value (the
    control's relaxation stopped one sweep short)."""
    offsets = np.asarray(offsets)
    nv = len(offsets) - 1
    w = np.asarray(w, np.float32)
    label = np.full(nv, np.inf, dtype=np.float32)
    label[int(root)] = 0
    short = label
    has_in = offsets[:-1] < offsets[1:]
    starts = offsets[:-1][has_in]
    sweeps = 0
    while len(starts):
        cand = label[src]
        cand += w                       # float32 + float32, rounded once
        best = np.minimum.reduceat(cand, starts)
        new = label.copy()
        new[has_in] = np.minimum(label[has_in], best)
        sweeps += 1
        if np.array_equal(new, label):
            break
        short, label = label, new
    return (label, sweeps, short) if before_last else (label, sweeps)


def to_bfloat16(x):
    """float32 -> the nearest bfloat16 (ties to even), as float32: the
    nearest precision below float32, for the control."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits += 0x7FFF + ((bits >> 16) & 1)
    return (bits & 0xFFFF0000).astype(np.uint32).view(np.float32)


def dijkstra_f64(offsets, src, w, root: int):
    """Heap Dijkstra in float64 over the same arcs -> distances
    float64 [nv] (``inf`` unreached): the true shortest-path lengths
    of the float32 weights.  For the small sizes (a Python loop).

    The float32 fixed point lies within a few float32 roundings of
    it: a shortest path of ``h`` arcs is summed with ``h`` rounded
    additions, each off by at most 2^-24 = 6e-8 of the partial sum it
    rounds, and on the Kronecker graphs here a shortest path has a
    dozen or two arcs: a relative gap of at most 1e-6 at scale 10 is
    16 roundings at their worst and all one way, where most are
    smaller (the partial sums are) and they mostly cancel."""
    offsets = np.asarray(offsets)
    nv = len(offsets) - 1
    dst = np.repeat(np.arange(nv), np.diff(offsets))
    order = np.argsort(src, kind="stable")          # arcs by SOURCE
    out_off = np.zeros(nv + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=nv), out=out_off[1:])
    out_dst = dst[order].tolist()
    out_w = np.asarray(w, np.float64)[order].tolist()
    out_off = out_off.tolist()
    dist = [float("inf")] * nv
    dist[int(root)] = 0.0
    heap = [(0.0, int(root))]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for e in range(out_off[u], out_off[u + 1]):
            nd = d + out_w[e]
            v = out_dst[e]
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    return np.asarray(dist, dtype=np.float64)


def mismatched(got, want) -> int:
    """How many labels differ, BIT for bit (``+inf`` = unreached on
    both sides)."""
    got = np.ascontiguousarray(got, dtype=np.float32)
    want = np.ascontiguousarray(want, dtype=np.float32)
    if got.shape != want.shape:
        raise ValueError(f"answer of shape {got.shape}, reference of "
                         f"shape {want.shape}")
    return int(np.count_nonzero(got.view(np.uint32)
                                != want.view(np.uint32)))


def edges_violated(d, src, dst, w) -> int:
    """Graph500 kernel 3's validation rule over ALL stored edges, in
    the exact form the float32 fixed point guarantees: the edges
    ``u -> v`` with ``d[v] > fl32(d[u] + w)`` (a distance that one
    more relaxation would still lower; an unreached ``v`` beside a
    reached ``u`` is one)."""
    d = np.asarray(d, np.float32)
    cand = d[src]
    cand += np.asarray(w, np.float32)
    return int(np.count_nonzero(d[dst] > cand))


def roots_nonzero(d, root: int) -> int:
    """Kernel 3's other rule: the root's distance is 0."""
    return int(d[int(root)] != 0)
