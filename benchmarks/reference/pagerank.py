"""Plain PageRank reference with Lux's semantics, float64 NumPy.

Lux's update (reference pagerank/app.h, pagerank_gpu.cu) is
``rank = (1 - ALPHA) / nv + ALPHA * sum(rank[u] / outdeg[u] for u -> v)``
with ALPHA = 0.15 (the weight sits on the SUM: Lux's own convention),
seeded with ``1 / nv``; a vertex without out-edges sends nothing.
Returned are conventional ranks, not the degree-scaled state the
program iterates on.

``state_dtype`` is the control of "How correct is decided": the same
arithmetic with the per-vertex share that an edge reads stored in a
lower precision (bfloat16 for the program's float32) and the sum taken
in float32.
"""

from __future__ import annotations

import numpy as np

ALPHA = 0.15


def pagerank(offsets, neighbours, iterations: int, state_dtype=None):
    nv = len(offsets) - 1
    deg = np.diff(offsets)
    acc_dtype = np.float64 if state_dtype is None else np.float32
    rank = np.full(nv, 1.0 / nv, dtype=acc_dtype)
    for _ in range(int(iterations)):
        share = rank / np.maximum(deg, 1)
        if state_dtype is not None:
            share = share.astype(state_dtype).astype(acc_dtype)
        acc = np.bincount(neighbours, weights=np.repeat(share, deg),
                          minlength=nv)
        rank = ((1.0 - ALPHA) / nv + ALPHA * acc).astype(acc_dtype)
    return rank.astype(np.float64)


def compare_ranks(got, want):
    """The two numbers a PageRank answer is held to: the widest
    relative gap of one vertex's rank, and the summed absolute gap over
    the summed reference (steady from seed to seed).  Ranks are
    positive (every vertex holds at least ``(1 - ALPHA) / nv``)."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    gap = np.abs(got - want)
    return {"rank_max_rel_err": float(np.max(gap / want)),
            "rank_l1_rel_err": float(gap.sum() / want.sum())}
