"""Graph500 Kronecker edge generator (specification 2.x/3.0, "kernel 0
input"), plain NumPy.

Follows the specification's Octave reference: for every bit of the
vertex id, one draw picks the row half (``> A+B``) and a second picks
the column half with the probability renormalised by the row choice;
vertex labels are then permuted.  A/B/C = 0.57/0.19/0.19, D the rest.
One departure, stated here: the specification also shuffles the ORDER
of the edge list; every consumer here sorts the list, so that shuffle
is left out.

Everything comes from ``seed`` through NumPy's ``SeedSequence``; the
same (scale, edge_factor, seed) gives the same list on every machine.
This module imports nothing of the program under test.
"""

from __future__ import annotations

import numpy as np

A, B, C = 0.57, 0.19, 0.19


def kronecker_edges(scale: int, edge_factor: int, seed: int):
    """(src, dst) uint32 arrays of ``edge_factor * 2**scale`` directed
    edge tuples over ``2**scale`` vertices (self-loops and duplicates
    kept, as the specification keeps them)."""
    if not 1 <= scale <= 31:
        raise ValueError(f"scale {scale} out of range [1, 31]")
    n = 1 << scale
    m = int(edge_factor) * n
    rng = np.random.default_rng(
        [int(seed) % (1 << 63), int(scale), int(edge_factor)])
    ab = np.float32(A + B)
    c_norm = np.float32(C / (1.0 - (A + B)))
    a_norm = np.float32(A / (A + B))
    src = np.zeros(m, dtype=np.uint32)
    dst = np.zeros(m, dtype=np.uint32)
    for bit in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.uint32) << np.uint32(bit)
        dst |= jj.astype(np.uint32) << np.uint32(bit)
    perm = rng.permutation(n).astype(np.uint32)
    return perm[src], perm[dst]
