"""Decompose the pagerank iteration across RMAT scales (VERDICT r1 #2:
21 -> 23 lost 2.5x per-edge throughput; the gather sweep is flat, so
the regression is elsewhere).

For each scale: build the bench engine (relabel + pair) and time
  full   the fused engine step (bench configuration)
  nopair the same graph with pair_threshold=None (pure gather path)
  pair   a jit of ONLY the pair delivery+reduce (rows gather, chunk
         partials, class combine)
  resid  a jit of ONLY the residual gather+tiled reduce
plus the plan shape stats (coverage, R rows, inflation, chunks C).

Methodology per PERF_NOTES, through the trusted library recipe
(lux_tpu.timing.loop_bench — the PR-7/round-12 migration of the
profile scripts off the documented timing traps): K iterations inside
one jit, loop-DEPENDENT carry, scalar output, host-fetch fence; big
operands ride the carry as jit arguments and the reported number is
the median over repeats.

Usage: PYTHONPATH=/root/repo python scripts/profile_scale.py 21 22 23
"""

from __future__ import annotations

import sys
from statistics import median

import jax
import jax.numpy as jnp

from lux_tpu.apps import pagerank
from lux_tpu.convert import rmat_graph
from lux_tpu.graph import degree_relabel
from lux_tpu.timing import loop_bench

K = 5


def timed_scalar_loop(fn, state, *args):
    """K loop-dependent iterations of fn inside one jit
    (timing.loop_bench); returns median s/iter over 3 repeats."""

    def step(carry):
        s, rest = carry[0], carry[1:]
        out = fn(s, *rest)
        sv = jnp.sum(out.reshape(-1)[:8])
        return sv, (out * (1.0 - 1e-30 * sv), *rest)

    samples, _ = loop_bench(step, (state, *args), K, repeats=3)
    return median(samples)


def main(scales):
    for scale in scales:
        g = rmat_graph(scale=scale, edge_factor=16, seed=0)
        g2, _ = degree_relabel(g)
        eng = pagerank.build_engine(g2, num_parts=1, pair_threshold=16, exchange="gather")
        sp = eng.pairs
        lay = eng.tiles
        print(f"--- scale {scale}: ne={g.ne} "
              f"cov={sp.stats['coverage']:.3f} R={sp.R} Rp={sp.Rp} "
              f"infl={sp.stats['inflation']:.2f} "
              f"classes={len(sp.classes)} "
              f"resid_ne={int(eng.sg.ne_part[0])} C={lay.n_chunks}")

        # full step (the bench path)
        t_full = timed_scalar_loop(
            lambda s, *a: eng._step_core(s, *a), eng.init_state(),
            *eng.graph_args)

        # no-pair engine on the same relabeled graph
        eng0 = pagerank.build_engine(g2, num_parts=1, exchange="gather")
        t_nopair = timed_scalar_loop(
            lambda s, *a: eng0._step_core(s, *a), eng0.init_state(),
            *eng0.graph_args)

        # pair-only: delivery + reduce, state-shaped output
        from lux_tpu.ops.pairs import pair_partial
        gdict = dict(zip(eng._graph_keys, eng.graph_args))

        def pair_only(flat, rowbind, rel, tpos):
            red = pair_partial(sp, flat, rowbind, rel, None, tpos,
                               "sum", lambda v, w: v,
                               reduce_method=eng.reduce_method)
            return red[:eng.sg.vpad]

        t_pair = timed_scalar_loop(
            pair_only, eng.init_state().reshape(-1),
            gdict["pair_rowbind"][0], gdict["pair_rel"][0],
            gdict["pair_tile_pos"][0])

        # residual-only: per-edge gather + tiled reduce
        from lux_tpu.ops.tiled import tiled_segment_reduce

        def resid_only(flat, src_slot, cs, lc, rel):
            vals = jnp.take(flat, src_slot, axis=0)
            vals = jax.lax.optimization_barrier(vals)
            return tiled_segment_reduce(
                vals, lay, cs, lc, rel, eng.sg.vpad, "sum",
                method="pallas" if eng.reduce_method.startswith("pallas")
                else "xla")

        t_resid = timed_scalar_loop(
            resid_only, eng.init_state().reshape(-1),
            gdict["src_slot"][0], gdict["chunk_start"][0],
            gdict["last_chunk"][0], gdict["rel_dst"][0])

        print(f"    full={t_full * 1e3:8.1f} ms/iter "
              f"({g.ne / t_full / 1e9:.3f} GTEPS)")
        print(f"    nopair={t_nopair * 1e3:6.1f} ms/iter "
              f"({g.ne / t_nopair / 1e9:.3f} GTEPS)")
        print(f"    pair={t_pair * 1e3:8.1f} ms/iter  "
              f"resid={t_resid * 1e3:8.1f} ms/iter  "
              f"(sum {1e3 * (t_pair + t_resid):.1f})")


if __name__ == "__main__":
    main([int(s) for s in sys.argv[1:]] or [21, 23])
