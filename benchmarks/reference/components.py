"""Max-label propagation on a directed graph, to its fixed point:
the reference the components cell is compared with.

Plain NumPy; imports nothing of ``lux_tpu``.  Every vertex starts with
a label of its own (``label0``, a permutation of 0..nv-1: the ids the
system under test gave the vertices; data to this code), and

    label[v] <- max(label[v], max over arcs u -> v of label[u])

is repeated until nothing changes.  On a DIRECTED graph the fixed
point is, for every vertex, the largest starting label among the
vertices that reach it (itself included); on a symmetrized one the
largest of its component.  The fixed point of a monotone max is the
same whatever the schedule, so the comparison is exact.

The arcs come sorted by destination (``offsets`` [nv + 1], ``src``
[arcs]: a destination's sources are contiguous), and one sweep is one
gather and one ``np.maximum.reduceat``.
"""

from __future__ import annotations

import numpy as np


def check_permutation(perm, nv: int) -> np.ndarray:
    """``perm`` as int64 [nv], or ValueError unless it holds each of
    0..nv-1 once."""
    perm = np.asarray(perm, dtype=np.int64)
    if perm.shape != (nv,):
        raise ValueError(f"a permutation of {nv} has shape "
                         f"{perm.shape}")
    seen = np.zeros(nv, bool)
    ok = (perm >= 0) & (perm < nv)
    seen[perm[ok]] = True
    if not (ok.all() and seen.all()):
        raise ValueError("not a permutation: an id is missing or out "
                         "of range")
    return perm


def sweep(offsets, src, label) -> np.ndarray:
    """One synchronous iteration: every vertex takes the largest
    label among itself and its in-neighbours AS THEY WERE."""
    out = label.copy()
    has = np.flatnonzero(np.diff(offsets) > 0)
    if len(has):
        # reduceat over the non-empty destinations' first arcs: each
        # segment runs to the next one's start, the last to the end
        best = np.maximum.reduceat(label[src], offsets[has])
        out[has] = np.maximum(out[has], best)
    return out


def fixed_point(offsets, src, label0, before_last: bool = False):
    """Sweeps until nothing changes -> (labels, sweeps that changed
    something).  ``before_last``: also the state one sweep before the
    fixed point (the control's second fault; ``label0`` itself where
    that is the fixed point already)."""
    label = np.asarray(label0).copy()
    prev = label
    n = 0
    while True:
        new = sweep(offsets, src, label)
        if np.array_equal(new, label):
            return (label, n, prev) if before_last else (label, n)
        prev, label = label, new
        n += 1


def mismatched(got, want) -> int:
    """Vertices whose label differs."""
    return int(np.count_nonzero(np.asarray(got) != np.asarray(want)))
