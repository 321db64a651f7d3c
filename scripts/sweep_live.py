#!/usr/bin/env python
"""Incremental-vs-full revalidation sweep (round 20, live graphs).

The live-graph subsystem's third pillar (lux_tpu/livegraph.py
``LiveGraph.revalidate``) claims frontier-seeded incremental
re-convergence beats recomputing from scratch when the touched
fraction is small — the whole point of keeping converged state warm
under a mutation stream.  This sweep MEASURES that claim on CPU
(PERF_NOTES round 20; the on-device crossover is carried as debt
``live-mutation-on-device``, PERF.md section 7):

- per touched-fraction point f: append ``max(1, f * ne)`` random
  edges to a converged push engine's graph, then time
  (a) INCREMENTAL — ``LiveGraph.revalidate`` from the old fixed
      point (the delta-relax step + the engine's own compiled
      converge, delta blocks as jit arguments), vs
  (b) FULL — ``init_state + converge`` on an engine built over the
      augmented graph (what a rebuild-per-epoch serving tier would
      pay, compile excluded by warmup on both sides);
- each point PROVES equality first: the incremental fixed point must
  be bitwise-identical to the full recompute (the integer apps'
  proof obligation from the module docstring) before its timing may
  print — a fast wrong answer is not a speedup.

Timing fences with host fetches of the results (jax.device_get), the
round-3 discipline; medians of -reps timed runs with MAD spread.

Round 21 (the mutation algebra): ``-mode delete`` sweeps DELETION
fractions instead — per point it deletes ``max(1, f * ne)`` random
base edges and times the anti-monotone cone RE-SEED
(``LiveGraph.revalidate`` dispatching to ``_revalidate_anti``: host
re-seed of the forward-reachability cone from the deleted edges'
destinations, then the compiled converge) against the full recompute
it must bitwise-equal, reporting the measured cone fraction and
whether the cone cap forced the full-recompute fallback.  The
on-device deletion path is carried as debt
``live-deletion-on-device`` (PERF.md section 7).

Usage:
    PYTHONPATH=. python scripts/sweep_live.py [-scale N] [-ef E]
        [-np P] [-kind sssp|components] [-mode append|delete]
        [-fracs f1,f2,...] [-reps R]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _median_mad(xs):
    xs = sorted(xs)
    med = xs[len(xs) // 2]
    mad = sorted(abs(x - med) for x in xs)[len(xs) // 2]
    return med, mad


def sweep_point(g, eng, lab0, act0, frac, *, kind, num_parts, reps,
                seed):
    """One touched-fraction point.  Returns a result dict (timings in
    ms) after proving incremental == full bitwise."""
    import jax

    from lux_tpu import timing

    from lux_tpu.graph import Graph  # noqa: F401 (doc pointer)
    from lux_tpu.livegraph import LiveGraph
    from lux_tpu.apps import components, sssp

    m = max(1, int(frac * g.ne))
    rng = np.random.default_rng(seed)
    src = rng.integers(g.nv, size=m)
    dst = rng.integers(g.nv, size=m)
    live = LiveGraph(g, capacity=m)
    live.append_edges(src, dst)

    # warm both sides so neither bills XLA compilation to the timings
    inc_lab, inc_act, _ = live.revalidate(eng, lab0, act0)
    g_new = live.graph_at(live.epoch)
    app = sssp if kind == "sssp" else components
    build = (lambda gg: app.build_engine(gg, 0, num_parts=num_parts)) \
        if kind == "sssp" else \
        (lambda gg: app.build_engine(gg, num_parts=num_parts))
    eng_full = build(g_new)
    flab, fact = eng_full.init_state()
    flab, fact, _ = eng_full.converge(flab, fact)

    # the proof obligation first: bitwise-equal fixed points
    inc_h = eng.sg.from_padded(np.asarray(jax.device_get(inc_lab)))
    full_h = eng_full.sg.from_padded(np.asarray(jax.device_get(flab)))
    if not np.array_equal(inc_h, full_h):
        raise AssertionError(
            f"frac={frac}: incremental fixed point differs from full "
            f"recompute — a fast wrong answer is not a speedup")

    # fence with the O(1)-byte checksum, NEVER a full-state fetch:
    # on the owed on-device run a device_get of the whole label
    # table bills its device->host transfer to BOTH sides and drowns
    # the millisecond incremental timings (CLAUDE.md fencing rule)
    timing.fence(inc_lab)           # warm the fence jit outside
    t_inc = []
    for _ in range(reps):
        t0 = time.perf_counter()
        il, ia, _ = live.revalidate(eng, lab0, act0)
        timing.fence(il)
        t_inc.append((time.perf_counter() - t0) * 1e3)
    t_full = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fl, fa = eng_full.init_state()
        fl, fa, _ = eng_full.converge(fl, fa)
        timing.fence(fl)
        t_full.append((time.perf_counter() - t0) * 1e3)
    inc_med, inc_mad = _median_mad(t_inc)
    full_med, full_mad = _median_mad(t_full)
    return {"frac": frac, "edges": m, "inc_ms": inc_med,
            "inc_mad": inc_mad, "full_ms": full_med,
            "full_mad": full_mad,
            "speedup": full_med / inc_med if inc_med > 0 else
            float("inf")}


def _forward_cone(g_new, seeds):
    """Forward reachability from ``seeds`` over ``g_new`` — the same
    rule ``_revalidate_anti`` re-seeds by, recomputed here so the
    sweep can REPORT the cone it measured."""
    reach = np.zeros(g_new.nv, bool)
    frontier = np.unique(np.asarray(seeds))
    reach[frontier] = True
    s_a, d_a = g_new.edge_arrays()
    while frontier.size:
        nxt = np.unique(d_a[np.isin(s_a, frontier)])
        nxt = nxt[~reach[nxt]]
        reach[nxt] = True
        frontier = nxt
    return int(reach.sum())


def sweep_delete_point(g, eng, lab0, act0, frac, *, kind, num_parts,
                       reps, seed):
    """One DELETION-fraction point (round 21).  Times the
    anti-monotone re-seed (place the old converged state onto an
    engine over ``graph_at(target)``, then ``revalidate``) against
    ``init_state + converge`` on the same engine, after proving the
    two fixed points bitwise-equal."""
    import jax

    from lux_tpu import timing
    from lux_tpu.livegraph import LiveGraph
    from lux_tpu.apps import components, sssp

    m = max(1, int(frac * g.ne))
    rng = np.random.default_rng(seed)
    idx = rng.choice(g.ne, size=min(m, g.ne), replace=False)
    esrc, edst = g.edge_arrays()
    live = LiveGraph(g, capacity=len(idx))
    live.delete_edges(esrc[idx], edst[idx])
    g_new = live.graph_at(live.epoch)
    app = sssp if kind == "sssp" else components
    eng_t = (app.build_engine(g_new, 0, num_parts=num_parts)
             if kind == "sssp"
             else app.build_engine(g_new, num_parts=num_parts))
    old_h = eng.sg.from_padded(np.asarray(jax.device_get(lab0)))
    zeros = np.zeros(g.nv, bool)

    def reseed():
        lab, act = eng_t.place(eng_t.sg.to_padded(old_h),
                               eng_t.sg.to_padded(zeros))
        return live.revalidate(eng_t, lab, act)

    # warm both sides (compile excluded), then the proof obligation
    rlab, _ract, _ = reseed()
    flab, fact = eng_t.init_state()
    flab, fact, _ = eng_t.converge(flab, fact)
    r_h = eng_t.sg.from_padded(np.asarray(jax.device_get(rlab)))
    f_h = eng_t.sg.from_padded(np.asarray(jax.device_get(flab)))
    if not np.array_equal(r_h, f_h):
        raise AssertionError(
            f"frac={frac}: re-seeded fixed point differs from full "
            f"recompute — a fast wrong repair is not a speedup")
    cone = _forward_cone(g_new, edst[idx])
    fell_back = live.reseed_fallbacks > 0

    timing.fence(rlab)
    t_rs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        rl, _ra, _ = reseed()
        timing.fence(rl)
        t_rs.append((time.perf_counter() - t0) * 1e3)
    t_full = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fl, fa = eng_t.init_state()
        fl, fa, _ = eng_t.converge(fl, fa)
        timing.fence(fl)
        t_full.append((time.perf_counter() - t0) * 1e3)
    rs_med, rs_mad = _median_mad(t_rs)
    full_med, full_mad = _median_mad(t_full)
    return {"frac": frac, "edges": len(idx),
            "cone_frac": cone / g.nv, "fallback": fell_back,
            "reseed_ms": rs_med, "reseed_mad": rs_mad,
            "full_ms": full_med, "full_mad": full_mad,
            "speedup": full_med / rs_med if rs_med > 0 else
            float("inf")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="incremental-vs-full revalidation sweep "
                    "(lux_tpu/livegraph.py round 20)")
    ap.add_argument("-scale", type=int, default=14)
    ap.add_argument("-ef", type=int, default=16)
    ap.add_argument("-np", type=int, default=2, dest="num_parts")
    ap.add_argument("-kind", default="sssp",
                    choices=["sssp", "components"])
    ap.add_argument("-mode", default="append",
                    choices=["append", "delete"])
    ap.add_argument("-fracs", default=None,
                    help="touched fractions (default depends on "
                         "-mode: deletions cone out fast on "
                         "scale-free graphs, so the delete sweep "
                         "defaults to smaller points)")
    ap.add_argument("-reps", type=int, default=5)
    ap.add_argument("-seed", type=int, default=7)
    args = ap.parse_args(argv)
    if args.fracs is None:
        args.fracs = ("0.0005,0.002,0.01,0.05,0.2"
                      if args.mode == "append"
                      else "0.00001,0.0001,0.001,0.01")

    from lux_tpu import convert
    from lux_tpu.graph import Graph
    from lux_tpu.apps import components, sssp

    fracs = [float(f) for f in args.fracs.split(",") if f.strip()]
    src, dst, nv = convert.rmat_edges(args.scale, args.ef,
                                      seed=args.seed)
    g = Graph.from_edges(src.astype(np.int64), dst.astype(np.int64),
                         nv)
    app = sssp if args.kind == "sssp" else components
    eng = (app.build_engine(g, 0, num_parts=args.num_parts)
           if args.kind == "sssp"
           else app.build_engine(g, num_parts=args.num_parts))
    lab0, act0 = eng.init_state()
    lab0, act0, _ = eng.converge(lab0, act0)

    print(f"# sweep_live kind={args.kind} mode={args.mode} "
          f"rmat{args.scale} ef{args.ef} nv={g.nv} ne={g.ne} "
          f"np={args.num_parts} reps={args.reps}")
    if args.mode == "append":
        print(f"{'frac':>8} {'edges':>8} {'incr_ms':>10} "
              f"{'full_ms':>10} {'speedup':>8}")
        for i, f in enumerate(fracs):
            r = sweep_point(g, eng, lab0, act0, f, kind=args.kind,
                            num_parts=args.num_parts,
                            reps=args.reps,
                            seed=args.seed + 100 + i)
            print(f"{r['frac']:>8g} {r['edges']:>8d} "
                  f"{r['inc_ms']:>7.1f}±{r['inc_mad']:<4.1f} "
                  f"{r['full_ms']:>7.1f}±{r['full_mad']:<4.1f} "
                  f"{r['speedup']:>7.2f}x")
        return 0
    print(f"{'frac':>8} {'edges':>7} {'cone':>7} {'fb':>3} "
          f"{'reseed_ms':>11} {'full_ms':>10} {'speedup':>8}")
    for i, f in enumerate(fracs):
        r = sweep_delete_point(g, eng, lab0, act0, f,
                               kind=args.kind,
                               num_parts=args.num_parts,
                               reps=args.reps,
                               seed=args.seed + 200 + i)
        print(f"{r['frac']:>8g} {r['edges']:>7d} "
              f"{r['cone_frac']:>6.1%} "
              f"{'Y' if r['fallback'] else 'n':>3} "
              f"{r['reseed_ms']:>8.1f}±{r['reseed_mad']:<4.1f} "
              f"{r['full_ms']:>7.1f}±{r['full_mad']:<4.1f} "
              f"{r['speedup']:>7.2f}x")
    return 0


if __name__ == "__main__":
    sys.exit(main())
