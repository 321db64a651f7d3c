"""VERDICT r1 #8 / r4 #1-2: prove the >=0.5B-edge path on one chip,
for BOTH engine families.

Generates RMAT{scale} with the native C++ generator, builds a
multi-part ShardedGraph within host RAM, runs the app on the real TPU,
and prints one JSON line per stage plus the final GTEPS (driver
methodology: pull apps time a loop-dependent fused run, push apps time
whole while_loop converges; host-fetch fence either way).

Usage (key=value args, any order):
  PYTHONPATH=/root/repo \
      python scripts/bench_bigscale.py [scale=25] [np=4] [pair=0] \
          [ni=3] [tile_e=0] [exchange=gather] [owner_e=0] \
          [app=pagerank|cc|sssp|sssp-w] [sparse=1] [repeats=1] \
          [preset=rmat27pair]

preset=rmat27pair expands to the scale-27 pair record configuration
(round-5 pointer #4): pagerank scale=27 np=8 pair=16 min_fill=16
exchange=owner owner_e=128 ni=1 repeats=3 — pair(16)+owner+min_fill
on the 2.1B-edge flagship graph.  The geometry stays inside the
proven RMAT26 pair+owner shapes (min_fill thins the residual toward
well-packed E=128 chunks; the packed uint32 owner encoding holds the
arrays), ni=1 keeps each execution under the ~55 s duration wall
(PERF_NOTES round 5), and the relabel needs ~60-80 GB host peak.
Explicit key=value args override preset fields.

pair > 0 additionally runs graph.pair_relabel + pair-lane delivery
(slower host prep; measures the fast path at scale).  tile_e=0 uses
the engine default (512; 128 for the pair residual); bigger values
halve the [P, C, 128] partials temporary but grow per-tile chunk
padding — measured NET WORSE at RMAT26 (PERF_NOTES).

Push apps: cc symmetrizes (and caches) the graph and converges
max-propagation; sssp converges hop frontiers from vertex 0; sssp-w
attaches uniform 1..5 int weights (the bench convention) and converges
weighted frontiers.  sparse=0 drops the src-sorted frontier view
(halves edge memory; every iteration dense) — the big-scale fit lever
priced by ShardedGraph.memory_report(push_sparse=...).
"""

from __future__ import annotations

import json
import resource
import sys
import time


def log(stage, t0, **kw):
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    print(json.dumps(dict(stage=stage, secs=round(time.time() - t0, 1),
                          peak_host_gb=round(peak, 1), **kw)),
          flush=True)
    return time.time()


DEFAULTS = dict(scale=25, np=4, pair=0, ni=3, tile_e=0,
                exchange="gather", owner_e=0, app="pagerank",
                sparse=1, repeats=1, min_fill=0, seg=0, preset="")

# the scale-27 pair record configuration (round-5 pointer #4); see
# the module docstring
PRESETS = dict(rmat27pair=dict(
    app="pagerank", scale=27, np=8, pair=16, min_fill=16,
    exchange="owner", owner_e=128, ni=1, repeats=3))


def parse_args(argv):
    cfg = dict(DEFAULTS)
    explicit = {}
    pos = 0
    for a in argv:
        if "=" in a:
            k, v = a.split("=", 1)
            if k not in cfg:
                raise SystemExit(f"unknown arg {k!r} (known: "
                                 f"{', '.join(cfg)})")
        else:   # legacy positional order
            if pos >= len(DEFAULTS):
                raise SystemExit(f"too many positional args at {a!r}")
            k, v = list(DEFAULTS)[pos], a
            pos += 1
        explicit[k] = v if k in ("exchange", "app", "preset") else int(v)
    preset = explicit.pop("preset", "")
    if preset:
        if preset not in PRESETS:
            raise SystemExit(f"unknown preset {preset!r} (known: "
                             f"{', '.join(PRESETS)})")
        cfg.update(PRESETS[preset])
    cfg.update(explicit)        # explicit args override the preset
    return cfg


def main():
    cfg = parse_args(sys.argv[1:])
    scale, np_parts, pair = cfg["scale"], cfg["np"], cfg["pair"]
    app, exchange = cfg["app"], cfg["exchange"]

    import os

    import numpy as np

    from lux_tpu.format import write_lux
    from lux_tpu.graph import Graph, pair_relabel

    t = time.time()
    cache = f"/tmp/rmat{scale}_ef16_s0.lux"
    if os.path.exists(cache):
        g = Graph.from_file(cache, use_native=True)
        t = log("load_cached", t, nv=g.nv, ne=g.ne)
    else:
        from lux_tpu.convert import rmat_graph
        g = rmat_graph(scale=scale, edge_factor=16, seed=0)
        t = log("generate", t, nv=g.nv, ne=g.ne)
        write_lux(cache, g.row_ptrs, g.col_idx, degrees=g.out_degrees)
        t = log("cache_write", t)

    if app == "cc":
        # CC needs the symmetrized edge set (bench.py convention);
        # cache it — the 2x-edge from_edges sort is minutes at scale 25
        sym = f"/tmp/rmat{scale}_ef16_s0_sym.lux"
        if os.path.exists(sym):
            g = Graph.from_file(sym, use_native=True)
            t = log("load_sym_cached", t, ne=g.ne)
        else:
            from lux_tpu.apps.components import symmetrize
            s, d = symmetrize(*g.edge_arrays())
            g = Graph.from_edges(s, d, g.nv)
            # temp + rename: a crash mid-write must never leave a
            # truncated cache that a later run would load as the graph
            write_lux(sym + ".tmp", g.row_ptrs, g.col_idx,
                      degrees=g.out_degrees)
            os.replace(sym + ".tmp", sym)
            t = log("symmetrize", t, ne=g.ne)
    elif app == "sssp-w":
        rng = np.random.default_rng(1)
        g.weights = rng.integers(1, 6, size=g.ne).astype(np.int32)
        t = log("weights", t)

    starts = None
    if pair:
        # pair_relabel is deterministic: cache the relabeled graph +
        # cut points so repeat runs (phase probes, exchange A/Bs) skip
        # the ~20-min billion-edge relabel.  RELAB_VER must be bumped
        # whenever pair_relabel's PARTITIONING changes, or a stale
        # cache silently benchmarks the old cuts; the .starts.npy is
        # written LAST and gates the load, so a crash mid-write never
        # serves a partial cache.
        RELAB_VER = "v5p"   # v5p: cache gained .perm.npy (round 5)
        sym_tag = "_sym" if app == "cc" else ""
        rcache = (f"/tmp/rmat{scale}_ef16_s0{sym_tag}_relab_np{np_parts}"
                  f"_p{pair}{RELAB_VER}")
        if os.path.exists(rcache + ".starts.npy"):
            if g.weights is not None:
                # weights are attached PRE-relabel in this script only
                # for sssp-w; the unweighted cache cannot serve them
                raise SystemExit("pair cache + weighted: rebuild the "
                                 "cache with weights in the .lux file")
            g = Graph.from_file(rcache + ".lux", use_native=True)
            starts = np.load(rcache + ".starts.npy")
            perm = np.load(rcache + ".perm.npy")
            t = log("load_relabel_cache", t)
        else:
            g, perm, starts = pair_relabel(g, np_parts,
                                           pair_threshold=pair,
                                           verbose=True)
            t = log("pair_relabel", t)
            if g.weights is None:
                write_lux(rcache + ".lux", g.row_ptrs, g.col_idx,
                          degrees=g.out_degrees)
                np.save(rcache + ".perm.npy", perm)
                # written LAST: gates the whole cache load
                np.save(rcache + ".starts.npy", starts)
                t = log("relabel_cache_write", t)
        # start from the top-degree hub = relabeled vertex 0 (original
        # vertex 0 IS isolated at rmat25+ seed 0 — the reached-fraction
        # assert below caught exactly that; a hub start guarantees a
        # meaningful frontier cascade at every scale)
        start_vertex = 0
    else:
        # no relabel: the max-out-degree vertex, for the same reason
        start_vertex = int(np.argmax(g.out_degrees))

    kw = dict(num_parts=np_parts, pair_threshold=pair or None,
              pair_min_fill=cfg["min_fill"] or None,
              starts=starts, exchange=exchange)
    if cfg["owner_e"]:
        kw["owner_tile_e"] = cfg["owner_e"]
    if app == "pagerank":
        from lux_tpu.apps import pagerank
        if cfg["tile_e"]:
            kw["tile_e"] = cfg["tile_e"]
        eng = pagerank.build_engine(g, **kw)
    elif app == "cc":
        from lux_tpu.apps import components
        eng = components.build_engine(g, enable_sparse=bool(cfg["sparse"]),
                                      **kw)
    elif app in ("sssp", "sssp-w"):
        from lux_tpu.apps import sssp as sssp_app
        eng = sssp_app.build_engine(g, start_vertex=start_vertex,
                                    weighted=app == "sssp-w",
                                    enable_sparse=bool(cfg["sparse"]),
                                    **kw)
    else:
        raise SystemExit(f"unknown app {app!r}")

    rep = eng.sg.memory_report(
        exchange=eng.exchange,   # the RESOLVED value ('auto' -> real)
        owner_slots_per_part=(
            eng.owner.stats["slots"] // len(eng.sg.part_ids())
            if eng.owner is not None else None),
        owner_packed=(eng.owner.packed if eng.owner is not None
                      else None),
        push_sparse=app != "pagerank" and bool(cfg["sparse"]))
    t = log("build_engine", t,
            vpad=eng.sg.vpad, epad=eng.sg.epad,
            device_gb=round(rep["total_bytes"] / 1e9, 2),
            pair_cov=(round(eng.pairs.stats["coverage"], 3)
                      if eng.pairs is not None else None),
            pair_inflation=(round(eng.pairs.stats["inflation"], 2)
                            if eng.pairs is not None else None),
            owner_stats=(eng.owner.stats if eng.owner is not None
                         else None))

    if app == "pagerank":
        from lux_tpu.timing import timed_fused_run
        ni = cfg["ni"]
        state, elapsed = timed_fused_run(eng, ni, repeats=cfg["repeats"])
        out = eng.unpad(state)
        assert np.isfinite(out).all(), "non-finite result"
        iters = ni
    elif cfg["seg"]:
        # SEGMENTED converge: cap each while_loop execution at seg
        # iterations with host round-trips between segments — bounds
        # single-execution duration under the TPU-worker crash
        # envelope (PERF_NOTES round 5: a ~2x-longer all-dense CC
        # converge died where the same-shape sssp converge ran).
        # Timing includes the segment round-trips (honest; recorded).
        from lux_tpu.timing import fence, fetch
        label, active = eng.init_state()
        _l, _a, _it = eng.converge(label, active, 1)   # compile
        fence(_l)
        label, active = eng.init_state()
        fence((label, active))
        t0 = time.perf_counter()
        iters = 0
        while True:
            label, active, it = eng.converge(label, active,
                                             cfg["seg"])
            it = int(fetch(it))
            iters += it
            if it < cfg["seg"]:
                break
        elapsed = [time.perf_counter() - t0]
        out = eng.unpad(label)
        if app == "cc":
            assert out.min() >= 0, "CC label underflow"
        else:
            from lux_tpu.apps import sssp as _s
            reached = int((~_s.unreachable(out)).sum())
            assert reached > g.nv // 100, "vacuous sssp run"
    else:
        from lux_tpu.timing import timed_converge
        # timed_converge returns labels already unpadded to [nv]
        out, iters, elapsed = timed_converge(eng, repeats=cfg["repeats"])
        if app == "cc":
            assert out.min() >= 0, "CC label underflow"
        else:
            from lux_tpu.apps import sssp as _s
            reached = int((~_s.unreachable(out)).sum())
            assert reached > g.nv // 100, (
                f"sssp reached only {reached} vertices — vacuous run "
                f"(isolated start?); GTEPS would be meaningless")
    from statistics import median

    from lux_tpu.resilience import screen_outliers
    raw = [g.ne * iters / e / 1e9 for e in elapsed]
    # outlier-screened like bench.py (>3x collapsed samples discarded,
    # never medianed; no rerun here — scripts run one batch)
    samples, discarded, attempts = screen_outliers(raw, None,
                                                   factor=3.0)
    gteps = median(samples)
    log("run", t, iters=int(iters), elapsed=[round(e, 2) for e in elapsed],
        gteps=round(gteps, 4))
    print(json.dumps({
        "metric": f"{app}_rmat{scale}_np{np_parts}_gteps_per_chip",
        "value": round(gteps, 4), "unit": "GTEPS",
        "vs_baseline": round(gteps, 4),
        "samples": [round(s, 4) for s in samples],
        "attempts": attempts,
        "discarded": [round(d, 4) for d in discarded],
        "np": np_parts,
        "scale": scale, "ne": g.ne, "pair_threshold": pair or None,
        "min_fill": cfg["min_fill"] or None,
        "exchange": exchange, "sparse": bool(cfg["sparse"]),
        "start": (start_vertex if app in ("sssp", "sssp-w") else None),
        "seg": cfg["seg"] or None,
        "telemetry": {"runs": [
            {"repeat": i, "iters": int(iters), "seconds": e}
            for i, e in enumerate(elapsed)], "counters": None},
        # session-calibration fingerprint (lux_tpu/observe.py):
        # check_bench rejects lines from degraded/uncalibrated
        # sessions, so an off-canon session is labeled at the source
        "calibration": _calibration(),
        "iters": int(iters)}))


def _calibration():
    from lux_tpu import observe
    try:
        return observe.fingerprint_digest()
    except Exception as e:  # noqa: BLE001 — labeling must not kill the run
        print(f"# calibration probe failed ({type(e).__name__}: {e})",
              file=sys.stderr)
        return None


if __name__ == "__main__":
    main()
