"""Graph500 kernel 3's edge weights, plain NumPy: one float32 uniform
in [0, 1) for every GENERATED edge tuple, drawn from ``seed`` (the
specification, v3, "kernel 0 input": the weights are part of the edge
list the generator hands over).

NumPy's float32 ``random`` takes 24 bits a draw, so every weight is a
multiple of 2^-24 (0 included: about two tuples in 2^25 get it).  The
graph is undirected: where the list is stored in both directions
(``both_directions``) the two arcs of a tuple carry the SAME weight.
Multi-edges and self-loops stay, each tuple with its own weight, as
``kronecker.py`` keeps them.

This module imports nothing of the program under test.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference import kronecker

# the stream of the weights among the draws made from a graph's seed
# (``kronecker.kronecker_edges`` draws from [seed, scale, edge_factor])
_STREAM = 0x77


def tuple_weights(tuples: int, seed: int) -> np.ndarray:
    """float32 [tuples], uniform in [0, 1): the weight of each
    generated edge tuple, in the generator's order."""
    rng = np.random.default_rng([int(seed) % (1 << 63), _STREAM])
    return rng.random(int(tuples), dtype=np.float32)


def both_directions(src, dst, w):
    """The undirected list as stored: every tuple (u, v, w) also as
    (v, u, w) -> (src, dst, w), the mirrored half behind the given
    one."""
    return (np.concatenate([src, dst]), np.concatenate([dst, src]),
            np.concatenate([w, w]))


def kernel3_arcs(scale: int, edge_factor: int, symmetrized: bool,
                 seed: int):
    """Kernel 3's input as stored -> (src, dst, w): the Kronecker
    tuples of ``seed`` (``kronecker.kronecker_edges``: the instance
    kernel 2 searches), each with its weight, in both directions where
    ``symmetrized``."""
    src, dst = kronecker.kronecker_edges(scale, edge_factor, seed)
    w = tuple_weights(len(src), seed)
    if symmetrized:
        return both_directions(src, dst, w)
    return src, dst, w


def by_destination(src, dst, w, nv: int):
    """The arcs sorted by destination, ties by source -> (offsets
    int64 [nv + 1], src int32 [ne], w float32 [ne]): the arcs INTO
    vertex ``v`` are ``offsets[v]:offsets[v + 1]``."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    if src.size and (int(src.max()) >= nv or int(dst.max()) >= nv):
        raise ValueError("edge endpoint out of range")
    key = dst.astype(np.uint64) << np.uint64(32)
    key |= src.astype(np.uint64)
    order = np.argsort(key, kind="stable")
    del key
    offsets = np.zeros(nv + 1, dtype=np.int64)
    np.cumsum(np.bincount(dst, minlength=nv), out=offsets[1:])
    return (offsets, src[order].astype(np.int32),
            np.asarray(w, np.float32)[order])
