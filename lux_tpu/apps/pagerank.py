"""PageRank (pull model, fixed iteration count).

Semantics match the reference exactly (reference pagerank_gpu.cu:49-102,
pagerank/app.h:24, pull_init at pagerank_gpu.cu:255-259):

- ALPHA = 0.15 used as ``pr = (1-ALPHA)/nv + ALPHA * sum`` — i.e. the
  damping factor is 0.15, not the usual 0.85 (SURVEY.md §7 quirks;
  preserved for parity).
- State is *degree-normalized* rank: after each update the rank is
  divided by out-degree so the next gather needs no degree lookup
  (pagerank_gpu.cu:97-100); init seeds ``(1/nv)/deg`` (deg==0 -> 1/nv).
- Final output is therefore also degree-scaled; ``true_ranks``
  un-scales it for conventional PageRank values.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from lux_tpu.engine.delivery import sharding_demands
from lux_tpu.engine.program import PullProgram
from lux_tpu.engine.pull import PullEngine
from lux_tpu.graph import Graph, ShardedGraph, degree_relabel  # noqa: F401
# degree_relabel moved to graph.py; re-exported for existing callers

ALPHA = 0.15  # reference pagerank/app.h:24


def make_program(dtype=jnp.float32) -> PullProgram:
    def edge_value(src_val, dst_val, weight):
        return src_val

    def apply(old, red, ctx):
        pr = (1.0 - ALPHA) / ctx.nv + ALPHA * red
        deg = ctx.deg.astype(pr.dtype)
        return jnp.where(ctx.deg > 0, pr / jnp.maximum(deg, 1), pr)

    def seed(xp, deg, nv):
        # the ONE formula of the first state, on the host (xp = np)
        # and on the device (xp = jnp): divided in the state's dtype,
        # the arithmetic apply uses for every later iteration
        rank = xp.asarray(1.0 / nv, np.dtype(dtype))
        return xp.where(deg > 0, rank / xp.maximum(deg, 1).astype(
            rank.dtype), rank)

    def init(sg: ShardedGraph):
        return seed(np, np.asarray(sg.deg_padded), sg.nv)

    def init_device(ctx):
        return seed(jnp, ctx.deg, ctx.nv)

    return PullProgram(reduce="sum", edge_value=edge_value, apply=apply,
                       init=init, needs_dst=False,
                       state_bytes=np.dtype(dtype).itemsize,
                       name="pagerank", init_device=init_device)


def one_hot_resets(nv: int, sources) -> np.ndarray:
    """[nv, B] reset matrix with column q the one-hot distribution of
    ``sources[q]`` — the classic 'personalized to one vertex' case."""
    sources = [int(s) for s in sources]
    resets = np.zeros((nv, len(sources)), dtype=np.float32)
    for q, s in enumerate(sources):
        if not 0 <= s < nv:
            raise ValueError(f"source vertex {s} out of range [0, {nv})")
        resets[s, q] = 1.0
    return resets


def make_batched_program(resets, dtype=jnp.float32) -> PullProgram:
    """Personalized PageRank over a query batch: state ``[vpad, B]``
    degree-normalized ranks, one column per query, with per-query
    reset vectors ``resets [nv, B]`` (each column a distribution over
    vertices; the uniform column 1/nv recovers the classic program).
    Update per column: ``pr = (1-ALPHA) * reset_q + ALPHA * sum``
    (the reference's damping quirk, see module docstring), then the
    same degree normalization.

    The reset matrix rides ``PullProgram.extra_arrays`` — a jit
    ARGUMENT the engine ships like any graph array (``ctx.extra
    ['reset']``), so the no-closure convention holds and the serving
    front-end can swap retired columns' resets in place
    (PullEngine.update_program_arrays; the serving tier rewrites the
    column in the table where it lies on the device and hands the
    table back).  ONE state-table gather per
    dense iteration serves all B queries (audit gather-budget);
    ``state_bytes = 4B`` keeps the auto-exchange and ledger
    estimates honest at B > 1.

    ``deg_corr`` (round 21, live graphs) is a second extra array
    [nv, B] of per-column out-degree CORRECTIONS, zero by default (a
    float 0 add keeps the static case bitwise).  The live serving
    tier sets column q to the delta-append out-degree at q's
    admission epoch, so the engine normalizes by the EFFECTIVE
    degree of ``graph_at(epoch_q)`` while iterating the base edges;
    the correction step, a device program, adds the delta edges' rank
    mass at each boundary (serve._delta_mass — together one exact PPR
    iteration over the epoch's graph, which is how pull admissions
    advance with published epochs without waiting for a fold)."""
    resets = np.asarray(resets, dtype=np.dtype(dtype))
    if resets.ndim != 2:
        raise ValueError(f"resets must be [nv, B], got {resets.shape}")
    B = resets.shape[1]

    def edge_value(src_val, dst_val, weight):
        return src_val

    def apply(old, red, ctx):
        reset = ctx.extra["reset"]
        pr = (1.0 - ALPHA) * reset + ALPHA * red
        deg = ctx.deg.astype(pr.dtype)[:, None] \
            + ctx.extra["deg_corr"]
        return jnp.where(deg > 0, pr / jnp.maximum(deg, 1), pr)

    def init(sg: ShardedGraph):
        if resets.shape[0] != sg.nv:
            raise ValueError(f"resets rows {resets.shape[0]} != nv "
                             f"{sg.nv}")
        deg = np.asarray(sg.deg_padded)[..., None]
        r = sg.to_padded(resets)
        return np.where(deg > 0, r / np.maximum(deg, 1),
                        r).astype(np.dtype(dtype))

    def extra_arrays(sg: ShardedGraph):
        zeros = np.zeros(resets.shape, np.dtype(dtype))
        return {"reset": sg.to_padded(resets),
                "deg_corr": sg.to_padded(zeros)}

    return PullProgram(reduce="sum", edge_value=edge_value, apply=apply,
                       init=init, needs_dst=False,
                       state_bytes=np.dtype(dtype).itemsize * B,
                       name="ppr", extra_arrays=extra_arrays,
                       batch=B)


def build_engine(g: Graph, num_parts: int = 1, mesh=None,
                 dtype=jnp.float32, sg: ShardedGraph | None = None,
                 pair_threshold: int | None = None,
                 pair_min_fill: int | None = None,
                 starts=None, tile_e: int | None = None,
                 exchange: str = "auto",
                 gather: str = "flat",
                 owner_tile_e: int | None = None,
                 use_mxu: bool | str = "auto",
                 health: bool = False,
                 sources=None, resets=None,
                 audit: str | None = None) -> PullEngine:
    """starts: partition cut points (e.g. from graph.pair_relabel for
    balanced multi-part pair delivery).  tile_e default: 128 with pair
    delivery (residual edges are sparse; shorter chunks waste far
    fewer padded gather slots), else 512.  exchange='owner' switches
    to owner-side message generation (ops/owner.py) — the fast path
    once the state table outgrows ~64 MB.  health=True runs the
    device-side health watchdog loop variants (lux_tpu/health.py).
    audit='warn'|'error' statically audits every compiled program
    variant at build time (lux_tpu/audit.py).

    sources=[a, b, ...] builds the QUERY-BATCHED personalized engine
    with one-hot reset vectors (state [vpad, B] — one gather serves
    every query); resets [nv, B] passes arbitrary per-query reset
    distributions instead.  Batched engines reject pair_threshold
    (pair delivery reads scalar state)."""
    if sources is not None and resets is not None:
        raise ValueError("pass sources=[...] OR resets=[nv, B], "
                         "not both")
    if sources is not None:
        resets = one_hot_resets(g.nv, sources)
    vpad_align, default_tile_e = sharding_demands(gather,
                                                  pair_threshold)
    if sg is None:
        sg = ShardedGraph.build(
            g, num_parts, starts=starts,
            pair_threshold=pair_threshold, vpad_align=vpad_align)
    if tile_e is None:
        tile_e = default_tile_e
    program = (make_program(dtype) if resets is None
               else make_batched_program(resets, dtype))
    return PullEngine(sg, program, mesh=mesh,
                      pair_threshold=pair_threshold,
                      pair_min_fill=pair_min_fill, tile_e=tile_e,
                      exchange=exchange, gather=gather,
                      owner_tile_e=owner_tile_e, use_mxu=use_mxu,
                      health=health, audit=audit)




def run(g: Graph, num_iters: int, num_parts: int = 1, mesh=None):
    """Run PageRank; returns degree-normalized ranks [nv] (host)."""
    eng = build_engine(g, num_parts, mesh)
    state = eng.init_state()
    state = eng.run(state, num_iters)
    return eng.unpad(state)


def run_until(g: Graph, tol: float = 1e-9, max_iters: int = 10000,
              num_parts: int = 1, mesh=None):
    """Convergence-driven PageRank (a superset of the reference's
    fixed -ni runs): iterate until the max-abs change of the DEGREE-
    SCALED rank state (the iteration variable, see module docstring)
    is <= tol.  Conventional-rank changes can be up to out_degree
    times larger; pick tol accordingly.  Returns
    (ranks [nv], iterations)."""
    import jax

    eng = build_engine(g, num_parts, mesh)
    state, it, _res = eng.run_until(eng.init_state(), tol, max_iters)
    return eng.unpad(state), int(jax.device_get(it))


def true_ranks(norm_ranks: np.ndarray, out_degrees: np.ndarray):
    """Undo the degree scaling: conventional PageRank values."""
    deg = np.asarray(out_degrees)
    return np.where(deg > 0, norm_ranks * np.maximum(deg, 1), norm_ranks)


def reference_pagerank(g: Graph, num_iters: int) -> np.ndarray:
    """NumPy oracle with identical semantics (degree-normalized)."""
    src, dst = g.edge_arrays()
    deg = g.out_degrees.astype(np.float64)
    state = np.where(deg > 0, (1.0 / g.nv) / np.maximum(deg, 1), 1.0 / g.nv)
    for _ in range(num_iters):
        acc = np.zeros(g.nv, dtype=np.float64)
        np.add.at(acc, dst, state[src])
        pr = (1.0 - ALPHA) / g.nv + ALPHA * acc
        state = np.where(deg > 0, pr / np.maximum(deg, 1), pr)
    return state


def reference_pagerank_batched(g: Graph, resets,
                               num_iters: int) -> np.ndarray:
    """NumPy personalized-PageRank oracle -> ``[nv, B]``
    degree-normalized ranks, one column per reset vector.

    Column q is BITWISE-equal to running this oracle with the single
    column ``resets[:, q:q+1]``: the vectorized ``np.add.at``
    accumulates each column over the identical edge sequence, so the
    per-column float-summation order is the single-query order
    (tests/test_batched.py asserts it).  A uniform 1/nv column
    reproduces ``reference_pagerank`` exactly."""
    src, dst = g.edge_arrays()
    resets = np.asarray(resets, dtype=np.float64)
    deg = g.out_degrees.astype(np.float64)[:, None]
    state = np.where(deg > 0, resets / np.maximum(deg, 1), resets)
    for _ in range(num_iters):
        acc = np.zeros_like(state)
        np.add.at(acc, dst, state[src])
        pr = (1.0 - ALPHA) * resets + ALPHA * acc
        state = np.where(deg > 0, pr / np.maximum(deg, 1), pr)
    return state
