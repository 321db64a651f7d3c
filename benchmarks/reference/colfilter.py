"""The plain reference of collaborative filtering: the published SGD
sweep of upstream Lux's ``col_filter`` (``col_filter/app.h:24-28``,
``colfilter_gpu.cu:32-104``) in float64 NumPy.

    err_e  = w_e - <old[s], old[d]>            every stored edge s -> d
    acc[d] = sum_e err_e * old[s]
    new[d] = old[d] + GAMMA * (acc[d] - LAMBDA * old[d])

Both endpoints are read from the OLD state; LAMBDA regularizes once a
vertex.  Edges come sorted by destination (``ratings.by_destination``),
so the sum over a destination's edges is a segment sum
(``np.add.reduceat``), and the sweep runs in blocks of edges cut at
destination boundaries so that the ``[edges, K]`` temporaries fit the
host.  ``dot_dtype`` / ``msg_dtype`` are the CONTROL's knobs
(``benchmarks/control_colfilter.py``): the operands of the inner
product, and the messages, rounded to a lower precision first.
Nothing of ``lux_tpu`` is imported.
"""

from __future__ import annotations

import concurrent.futures
import os
import threading

import numpy as np

K = 20
LAMBDA = 0.001
GAMMA = 0.00000035
BLOCK_EDGES = 1 << 19
WORKERS = min(8, os.cpu_count() or 1)

_local = threading.local()


def initial_factors(nv: int, k: int = K) -> np.ndarray:
    return np.full((nv, k), np.sqrt(1.0 / k), dtype=np.float64)


def _blocks(offsets, block_edges=BLOCK_EDGES):
    """[(first destination, one past the last)]: consecutive
    destination ranges of about ``block_edges`` edges each."""
    nv = len(offsets) - 1
    out, lo = [], 0
    while lo < nv:
        hi = int(np.searchsorted(offsets, offsets[lo] + block_edges,
                                 side="right")) - 1
        hi = min(max(hi, lo + 1), nv)
        out.append((lo, hi))
        lo = hi
    return out


def _buffers(n: int, k: int):
    """This thread's two [n, k] and one [n] float64 work arrays, kept
    from block to block: fresh ones would be page-faulted in again
    every block, which is most of the time on a many-core host, and
    on the chip machine (a sandboxed kernel that gives freed mappings
    back late) a block's worth of fresh arrays per block grew the
    process by tens of GB until the machine's 40 GiB ended it (my chip
    runs, PR 37)."""
    b = getattr(_local, "buffers", None)
    if b is None or b[0].shape[0] < n or b[0].shape[1] != k:
        m = max(n, BLOCK_EDGES)
        b = _local.buffers = (np.empty((m, k)), np.empty((m, k)),
                              np.empty(m))
    return b[0][:n], b[1][:n], b[2][:n]


def _round(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(np.float64)


def _endpoints(state, offsets, src, lo, hi):
    """(old[s], old[d], edges per destination) of the edges into
    destinations [lo, hi), in this thread's buffers."""
    e0, e1 = int(offsets[lo]), int(offsets[hi])
    counts = np.diff(offsets[lo:hi + 1])
    s, d, dot = _buffers(e1 - e0, state.shape[1])
    # mode="clip" writes straight into ``out`` (the default, "raise",
    # fills a fresh array of the output's size first and copies);
    # every index is a vertex id, so nothing is ever clipped
    np.take(state, src[e0:e1], axis=0, out=s, mode="clip")
    np.take(state, np.repeat(np.arange(lo, hi), counts), axis=0, out=d,
            mode="clip")
    return s, d, dot, counts


def _block_acc(state, acc, offsets, src, rating, lo, hi, dot_dtype,
               msg_dtype):
    """acc rows [lo, hi) of one sweep, written in place."""
    e0, e1 = int(offsets[lo]), int(offsets[hi])
    if e1 == e0:
        return
    s, d, dot, counts = _endpoints(state, offsets, src, lo, hi)
    np.einsum("ek,ek->e", _round(s, dot_dtype), _round(d, dot_dtype),
              out=dot)
    np.subtract(rating[e0:e1], dot, out=dot)            # err
    np.multiply(dot[:, None], s, out=d)                 # messages
    has = np.flatnonzero(counts)
    acc[lo + has] = np.add.reduceat(_round(d, msg_dtype),
                                    (offsets[lo:hi] - e0)[has], axis=0)


def sweeps(offsets, src, rating, iterations: int, state=None,
           dot_dtype=None, msg_dtype=None, block_edges=BLOCK_EDGES,
           workers=WORKERS):
    """``iterations`` sweeps from ``state`` (default: the uniform
    sqrt(1/K)) -> float64 [nv, K].  The blocks of one sweep are
    independent (they read the old state, each writes its own rows of
    ``acc``) and run on a few threads."""
    nv = len(offsets) - 1
    state = initial_factors(nv) if state is None else np.array(
        state, dtype=np.float64)
    blocks = _blocks(offsets, block_edges)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        for _ in range(iterations):
            acc = np.zeros_like(state)
            list(pool.map(
                lambda b: _block_acc(state, acc, offsets, src, rating,
                                     *b, dot_dtype, msg_dtype), blocks))
            state = state + GAMMA * (acc - LAMBDA * state)
    return state


def _block_sq_err(state, offsets, src, rating, lo, hi) -> float:
    e0, e1 = int(offsets[lo]), int(offsets[hi])
    if e1 == e0:
        return 0.0
    s, d, dot, _counts = _endpoints(state, offsets, src, lo, hi)
    np.einsum("ek,ek->e", s, d, out=dot)
    np.subtract(rating[e0:e1], dot, out=dot)
    return float(dot @ dot)


def rmse(offsets, src, rating, state, workers=WORKERS) -> float:
    """Root-mean-square prediction error over all stored edges."""
    state = np.asarray(state, dtype=np.float64)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        total = sum(pool.map(
            lambda b: _block_sq_err(state, offsets, src, rating, *b),
            _blocks(offsets)))
    return float(np.sqrt(total / max(int(offsets[-1]), 1)))


def compare_factors(got, want, init, rmse_got, rmse_want,
                    rmse_init) -> dict:
    """The check's numbers.  The state barely moves (GAMMA = 3.5e-7),
    so a gap relative to the STATE would pass a bfloat16 contraction:
    the gaps that decide are taken on what was LEARNED."""
    got = np.asarray(got, dtype=np.float64)
    learned = want - init
    return {
        "factor_delta_l2_rel_err": float(
            np.linalg.norm((got - init) - learned)
            / np.linalg.norm(learned)),
        "factor_max_rel_err": float(
            np.max(np.abs(got - want) / np.abs(want))),
        "rmse_rel_gap": float(abs(rmse_got - rmse_want) / rmse_want),
        "rmse_not_falling": int(not rmse_got < rmse_init),
    }
