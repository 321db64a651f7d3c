"""Collaborative filtering: matrix-factorization SGD on a weighted
bipartite graph (pull model, fixed iterations).

Semantics match the reference (reference col_filter/colfilter_gpu.cu:
32-104, col_filter/app.h:24-28): vertex state is a K=20 latent-factor
vector, initialized to sqrt(1/K) (colfilter_gpu.cu:261-264).  Per
iteration, for each vertex d with in-edges (s -> d, rating w):

    err_e   = w - <old[s], old[d]>
    acc[d]  = sum_e err_e * old[s]
    new[d]  = old[d] + GAMMA * (acc[d] - LAMBDA * old[d])

Note LAMBDA regularizes once per vertex, not per edge — preserved.
This is a naturally TPU-friendly program: state is [vpad, K] (K=20
lanes), messages are rank-2, and the segment-sum feeds the VPU/MXU.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from lux_tpu.engine.delivery import sharding_demands
from lux_tpu.engine.program import PullProgram
from lux_tpu.engine.pull import PullEngine
from lux_tpu.graph import Graph, ShardedGraph

K = 20              # reference col_filter/app.h:28
LAMBDA = 0.001      # reference col_filter/app.h:26
GAMMA = 0.00000035  # reference col_filter/app.h:27


def make_program(k: int = K, lam: float = LAMBDA,
                 gamma: float = GAMMA) -> PullProgram:
    def edge_value(src_val, dst_val, weight):
        # err per edge, then the gradient contribution to the dst vertex
        err = weight - jnp.sum(src_val * dst_val, axis=-1)
        return err[..., None] * src_val

    def edge_value_from_dot(src_val, dot, weight):
        # dst dependence is only <src, dst>: lets the tiled engine get
        # the dot from MXU matmuls instead of a per-edge dst gather
        return (weight - dot)[..., None] * src_val

    def apply(old, red, ctx):
        return old + gamma * (red - lam * old)

    def init(sg: ShardedGraph):
        val = np.sqrt(1.0 / k).astype(np.float32)
        return np.full((sg.num_parts, sg.vpad, k), val, dtype=np.float32)

    return PullProgram(reduce="sum", edge_value=edge_value, apply=apply,
                       init=init, needs_dst=True,
                       edge_value_from_dot=edge_value_from_dot,
                       state_bytes=4 * k, name="colfilter")


def build_engine(g: Graph, num_parts: int = 1, mesh=None,
                 sg: ShardedGraph | None = None,
                 pair_threshold: int | None = None,
                 pair_min_fill: int | str | None = None,
                 pair_stream: bool | None = None,
                 starts=None, gather: str = "flat",
                 use_mxu: bool | str = "auto",
                 health: bool = False,
                 audit: str | None = None) -> PullEngine:
    """pair_threshold routes dense tile pairs through the blocked-
    SDDMM pair path (ops/pairs.pair_partial_dot, streamed past the
    memory budget — pair_partial_dot_streamed): one reshaped-row
    fetch per pair row instead of a per-edge [*, K] row gather — best
    after graph.pair_relabel, whose ``starts`` pass through here.

    pair_min_fill="auto" applies the K-AWARE occupancy cap: SDDMM
    rows cost more per row than scalar rows (~260 vs 150 ns at K=20,
    scalemodel.pair_row_ns), so under-filled rows ride the residual
    at a higher break-even fill (~22) than the scalar ~16
    (ops/pairs.resolve_min_fill)."""
    if g.weights is None:
        raise ValueError("collaborative filtering needs a weighted graph")
    vpad_align, tile_e = sharding_demands(gather, pair_threshold)
    if sg is None:
        sg = ShardedGraph.build(
            g, num_parts, starts=starts,
            pair_threshold=pair_threshold, vpad_align=vpad_align)
    return PullEngine(sg, make_program(), mesh=mesh,
                      pair_threshold=pair_threshold,
                      pair_min_fill=pair_min_fill,
                      pair_stream=pair_stream, tile_e=tile_e,
                      gather=gather, use_mxu=use_mxu,
                      health=health, audit=audit)


def run(g: Graph, num_iters: int, num_parts: int = 1, mesh=None):
    """Returns latent factors [nv, K] (host)."""
    eng = build_engine(g, num_parts, mesh)
    state = eng.init_state()
    state = eng.run(state, num_iters)
    return eng.unpad(state)


def reference_colfilter(g: Graph, num_iters: int,
                        k: int = K, init=None) -> np.ndarray:
    """NumPy oracle with identical semantics (float64).  ``init``
    [nv, k] replaces the uniform sqrt(1/k) start.

    The per-destination sum is a segment sum over the file's
    dst-sorted edges (``np.add.reduceat`` at each non-empty
    destination's first edge): the same float64 answer as
    ``np.add.at`` over ``[ne, k]`` up to the order of the additions,
    in seconds instead of minutes past a few 10^5 edges."""
    src, dst = g.edge_arrays()
    w = np.asarray(g.weights, dtype=np.float64)
    state = (np.full((g.nv, k), np.sqrt(1.0 / k), dtype=np.float64)
             if init is None else np.array(init, dtype=np.float64))
    indeg = g.in_degrees()
    has = np.flatnonzero(indeg)             # destinations with edges
    first = (np.cumsum(indeg) - indeg)[has]
    for _ in range(num_iters):
        err = w - np.einsum("ek,ek->e", state[src], state[dst])
        acc = np.zeros_like(state)
        if len(has):
            acc[has] = np.add.reduceat(err[:, None] * state[src],
                                       first, axis=0)
        state = state + GAMMA * (acc - LAMBDA * state)
    return state


def rmse(g: Graph, state: np.ndarray) -> float:
    """Root-mean-square rating prediction error over all edges."""
    src, dst = g.edge_arrays()
    pred = np.einsum("ek,ek->e", state[src], state[dst])
    err = np.asarray(g.weights, dtype=np.float64) - pred
    return float(np.sqrt(np.mean(err * err)))
