"""Plain personalized-PageRank reference with Lux's semantics, float64
NumPy, one source at a time.

The program's update (``lux_tpu/apps/pagerank.py``, which keeps the
upstream's damping quirk, reference ``pagerank/app.h:24``) is

    rank = (1 - ALPHA) * reset + ALPHA * sum(rank[u] / outdeg[u]
                                             for u -> v)

with ALPHA = 0.15 ON THE SUM, ``reset`` the one-hot distribution of the
query's source, and ``rank = reset`` before the first iteration; a
vertex without out-edges sends nothing.  A query is answered after a
given number of iterations (the number its response reports), so the
reference runs exactly that many.  Returned are conventional ranks, not
the degree-scaled state the program iterates on (``to_ranks`` undoes
the scaling of an answer with the reference's own degrees).

``state_dtype`` is the control of "how correct is decided": the same
arithmetic with the per-vertex share that an edge reads stored in a
lower precision (bfloat16 for the program's float32) and the sum taken
in float32.
"""

from __future__ import annotations

import numpy as np

ALPHA = 0.15
# ranks under this are no mass a caller could tell from none: the
# widest relative gap is taken over the vertices at or above it (the
# device flushes subnormal shares to zero, the float64 reference keeps
# them; the program's own retirement tolerance is 1e-8)
MASS_FLOOR = 1e-12


def personalized_pagerank(offsets, neighbours, source: int,
                          iterations: int, state_dtype=None):
    nv = len(offsets) - 1
    if not 0 <= source < nv:
        raise ValueError(f"source {source} out of range [0, {nv})")
    deg = np.diff(offsets)
    acc_dtype = np.float64 if state_dtype is None else np.float32
    reset = np.zeros(nv, dtype=acc_dtype)
    reset[source] = 1.0
    rank = reset.copy()
    for _ in range(int(iterations)):
        share = rank / np.maximum(deg, 1)
        if state_dtype is not None:
            share = share.astype(state_dtype).astype(acc_dtype)
        acc = np.bincount(neighbours, weights=np.repeat(share, deg),
                          minlength=nv)
        rank = ((1.0 - ALPHA) * reset + ALPHA * acc).astype(acc_dtype)
    return rank.astype(np.float64)


def to_ranks(answer, offsets):
    """The program's degree-scaled answer -> conventional ranks."""
    deg = np.diff(offsets)
    return np.asarray(answer, dtype=np.float64) * np.maximum(deg, 1)


def compare_ranks(got, want, floor: float = MASS_FLOOR):
    """The two numbers a personalized answer is held to: the summed
    absolute gap over the summed reference, and the widest relative
    gap over the vertices to which the reference gives ``floor`` or
    more (the source among them: it keeps ``1 - ALPHA``)."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    gap = np.abs(got - want)
    held = want >= floor
    return {"ppr_l1_rel_err": float(gap.sum() / want.sum()),
            "ppr_max_rel_err": float(np.max(gap[held] / want[held]))}
