#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that lux_tpu still starts on the chip.

Drives the system's main path ONCE, in ONE process, through the entry
points a user calls (``lux_tpu.cli.main`` and ``lux_tpu.serve.main``),
at the repo's own bench shapes (bench.py DEFAULT_SHAPE; RMAT23 x16 =
8.4M vertices / 134M edges), and checks every answer by the repo's own
means (``-check`` fixed-point audits on the device, the serving tier's
NumPy oracles).  Graphs come from the native R-MAT generator under
``--seed`` and are written once as ``.lux`` files.

    python3 chip_smoke.py                 # on a machine with a TPU
    python3 chip_smoke.py --rehearsal     # tiny sizes, any backend

Contract (the driver runs this after every PR):

- fails, non-zero, BEFORE any leg unless ``jax.devices()[0].platform``
  is ``"tpu"`` — there is no CPU fallback.  ``--rehearsal`` is the one
  way to run it elsewhere: tiny sizes, prints ``REHEARSAL, NOT A CHIP
  RUN`` and can never print the pass line;
- one process: every leg runs in-process (a chip belongs to one
  process); the only child is the ``make`` of the native tools, and a
  failed native build fails the smoke instead of switching generators;
- a leg passes only on exit code 0 AND its check line; ANY failed
  phase makes the exit code non-zero;
- the last stdout line of a full passing run is
  ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

The per-leg seconds and bytes printed here are observations for
CHANGES.md, not metrics: nothing from this script goes into a record
under a metric's name.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import re
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# (scale, edge factor, weighted) per graph role — bench.py's shapes
# (DEFAULT_SHAPE: pagerank/cc/sssp at 21 and 20-21, the -mp configs at
# 23, colfilter 16 x 128 weighted) and, under --rehearsal, stand-ins
# small enough for the CPU backend
SHAPES = {
    "full": {"mid": (21, 16, False), "big": (23, 16, False),
             "cf": (16, 128, True), "serve": (20, 16)},
    "rehearsal": {"mid": (10, 16, False), "big": (12, 16, False),
                  "cf": (8, 16, True), "serve": (9, 8)},
}

# leg -> (graph role, app, flags).  -retries stays at its default 0:
# the supervisor's retry would turn a crash on the chip into a slower
# pass.  Pull legs carry -verbose (the memory advisor); push legs do
# not, because -verbose there compiles and runs a second, counter-
# recording converge just to replay it.
CLI_LEGS = {
    "a": ("mid", "pagerank", ["-pair", "16", "-min-fill", "24",
                              "-ni", "20", "-check", "-verbose"]),
    "b": ("big", "pagerank", ["-np", "4", "-exchange", "owner",
                              "-pair", "16", "-min-fill", "24",
                              "-ni", "10", "-check", "-verbose"]),
    "c": ("big", "sssp", ["-np", "4", "-exchange", "owner",
                          "-start", "0", "-check"]),
    "d": ("mid", "components", ["-pair", "16", "-min-fill", "24",
                                "-check"]),
    "e": ("cf", "colfilter", ["-pair", "16", "-ni", "10", "-check",
                              "-verbose"]),
}
MESH_LEGS = ("b", "c")          # leg g: these again with -mesh 4
ALL_LEGS = ("a", "b", "c", "d", "e", "f", "g")


class _Tee(io.TextIOBase):
    """stdout that also keeps what was written (a leg's own lines are
    its evidence; the run still shows them as they happen)."""

    def __init__(self, real):
        self.real, self.kept = real, io.StringIO()

    def write(self, s):
        self.real.write(s)
        self.kept.write(s)
        return len(s)

    def flush(self):
        self.real.flush()


def _captured(fn, argv):
    """Run an entry point in-process; returns (exit code, its stdout,
    wall seconds).  A crash is a failed leg, not the end of the run —
    the remaining legs still report."""
    tee = _Tee(sys.stdout)
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(tee):
            rc = fn(argv)
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    except Exception:  # noqa: BLE001 — boundary: report, keep going
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    return rc, tee.kept.getvalue(), time.perf_counter() - t0


def _peak_bytes():
    """Largest ``peak_bytes_in_use`` over the visible devices — the
    PROCESS watermark so far (the runtime never resets it), or None
    where the backend keeps no memory stats (CPU)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _find(pattern, text, cast=float):
    m = re.search(pattern, text, re.M)
    return cast(m.group(1)) if m else None


def make_graph(out_dir, shape, seed):
    """Generate one R-MAT graph with the NATIVE generator and write
    it as a .lux file (once: legs that share a shape share the file).
    Returns (path, nv, ne)."""
    import numpy as np

    from lux_tpu.convert import rmat_graph
    from lux_tpu.format import write_lux

    scale, ef, weighted = shape
    path = os.path.join(
        out_dir, f"rmat{scale}x{ef}{'w' if weighted else ''}.lux")
    t0 = time.perf_counter()
    g = rmat_graph(scale, ef, seed=seed)      # raises without native
    weights = None
    if weighted:
        # bench.build_graph's ratings: integers 1..5
        weights = np.random.default_rng(seed + 1).integers(
            1, 6, size=g.ne).astype(np.int32)
    write_lux(path, g.row_ptrs, g.col_idx, weights=weights,
              degrees=g.out_degrees)
    print(f"graph {os.path.basename(path)}: nv={g.nv} ne={g.ne} "
          f"({time.perf_counter() - t0:.1f} s generate+write)",
          flush=True)
    return path, g.nv, g.ne


def run_cli_leg(name, graph, app, flags, expect_reduce, mesh=0):
    """One CLI leg -> its result record."""
    from lux_tpu import cli

    path, nv, ne = graph
    argv = [app, "-file", path] + flags
    if mesh:
        argv += ["-mesh", str(mesh)]
    print(f"\n=== leg {name}: lux_tpu.cli {' '.join(argv)}", flush=True)
    rc, out, wall = _captured(cli.main, argv)
    rec = {
        "leg": name, "nv": nv, "ne": ne, "rc": rc,
        "reduce": _find(r"^engine: reduce=(\S+)", out, str),
        "devices": _find(r"^engine: .* devices=(\d+)", out, int),
        "iters": (_find(r"\((\d+) iterations", out, int)
                  or _find(r"-ni (\d+)", " ".join(argv), int)),
        "load_layout_s": _find(r"load\+layout ([\d.]+) s", out),
        "build_s": _find(r"engine build ([\d.]+) s", out),
        "warm_s": _find(r"compile\+warm ([\d.]+) s", out),
        "run_s": _find(r"^ELAPSED TIME = ([\d.]+) s", out),
        "wall_s": round(wall, 2),
        "peak_bytes": _peak_bytes(),
        "check": _find(r"^(\[(?:PASS|FAIL)\].*)$", out, str),
    }
    why = []
    if rc != 0:
        why.append(f"exit code {rc}")
    if not (rec["check"] or "").startswith("[PASS]"):
        why.append(f"no [PASS] check line (got {rec['check']!r})")
    if rec["reduce"] != expect_reduce:
        why.append(f"reduce={rec['reduce']!r}, expected "
                   f"{expect_reduce!r}")
    if app in ("sssp", "components") and (rec["iters"] or 0) < 2:
        why.append(f"converged in {rec['iters']} iteration(s): the "
                   f"run exercised nothing (an isolated start vertex "
                   f"under this --seed?)")
    if app == "colfilter":
        rmse = _find(r"^RMSE = (\S+)", out)
        rec["rmse"] = rmse
        if rmse is None or rmse != rmse or rmse in (float("inf"),
                                                   float("-inf")):
            why.append(f"RMSE not finite ({rmse!r})")
    if mesh:
        m = re.search(r"^placement: graph (\d+) bytes, per device "
                      r"\[([\d, ]+)\]; state (\d+) bytes, per device "
                      r"\[([\d, ]+)\]", out, re.M)
        if not m:
            why.append("no placement line")
        else:
            for what, total, per in (("graph", m.group(1), m.group(2)),
                                     ("state", m.group(3), m.group(4))):
                per = [int(x) for x in per.split(",")]
                rec[f"{what}_bytes_per_device"] = per
                # one quarter each — not all of it on device 0, not
                # a full copy everywhere
                if len(per) != mesh or any(
                        b * mesh != int(total) for b in per):
                    why.append(f"{what} arrays not sharded 1/{mesh} "
                               f"per device: {per} of {total}")
        if rec["devices"] != mesh:
            why.append(f"engine ran on {rec['devices']} device(s), "
                       f"not {mesh}")
    rec["ok"], rec["why"] = not why, "; ".join(why)
    return rec


def run_serve_leg(shape, expect_queries=24):
    """Leg f: mixed sssp/components/pagerank queries through the
    continuous-batching server, every answer oracle-checked."""
    from lux_tpu import serve

    scale, ef = shape
    argv = ["-scale", str(scale), "-ef", str(ef), "-batch", "8",
            "-np", "1", "-queries", str(expect_queries)]
    print(f"\n=== leg f: lux_tpu.serve {' '.join(argv)}", flush=True)
    rc, out, wall = _captured(serve.main, argv)
    served = re.search(r"^# served (\d+)/(\d+) queries .* in "
                       r"([\d.]+)s", out, re.M)
    rec = {"leg": "f", "nv": 1 << scale, "ne": (1 << scale) * ef,
           "rc": rc, "wall_s": round(wall, 2),
           "iters": None, "peak_bytes": _peak_bytes(),
           "run_s": float(served.group(3)) if served else None,
           "check": _find(r"^(# all answers match.*)$", out, str)}
    why = []
    if rc != 0:
        why.append(f"exit code {rc}")
    if not served or served.group(1) != served.group(2) \
            or int(served.group(1)) != expect_queries:
        why.append("queue did not drain")
    if rec["check"] is None:
        why.append("no oracle line")
    rec["ok"], rec["why"] = not why, "; ".join(why)
    return rec


def library_check(graph, rehearsal):
    """What says the Pallas kernel ran — not the XLA formulation and
    not interpret mode: build leg a's engine through the library,
    require ``reduce_method == "pallas"`` and a ``tpu_custom_call`` in
    the COMPILED step, run that step, and let memwatch read the real
    ``memory_stats()`` watermark against the byte ledger.  Runs BEFORE
    the CLI legs: ``peak_bytes_in_use`` is the process's watermark
    and is never reset, so only here does it belong to this engine.
    The verdict is printed, not gated."""
    import jax

    from lux_tpu import memwatch
    from lux_tpu.apps import pagerank
    from lux_tpu.graph import Graph, ShardedGraph, pair_relabel

    path, nv, ne = graph
    print(f"\n=== library check: leg a's engine on {path}", flush=True)
    t0 = time.perf_counter()
    g2, _perm, starts = pair_relabel(Graph.from_file(path), 1,
                                     pair_threshold=16)
    sg = ShardedGraph.build(g2, 1, starts=starts, pair_threshold=16)
    eng = pagerank.build_engine(g2, 1, sg=sg, pair_threshold=16,
                                pair_min_fill=24)
    jitted, args = eng.audit_variant("step")
    hlo = jitted.lower(*args()).compile().as_text()
    kernels = hlo.count("tpu_custom_call")
    state = eng.step(eng.init_state())
    jax.block_until_ready(state)
    print(f"library check: reduce={eng.reduce_method} "
          f"tpu_custom_call x{kernels} in the compiled step "
          f"({time.perf_counter() - t0:.1f} s)")
    why = []
    if rehearsal:
        # no Mosaic off the chip: rehearse the kernel's CODE through
        # the interpreter against the XLA formulation instead
        import jax.numpy as jnp
        import numpy as np

        from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
        from lux_tpu.ops.tiled import chunk_partials
        rng = np.random.default_rng(0)
        vals = jnp.asarray(rng.random((8, 128), np.float32))
        rel = jnp.asarray(rng.integers(-1, 128, (8, 128)), jnp.int8)
        got = chunk_partials_pallas(vals, rel, W=128, kind="sum",
                                    interpret=True)
        want = chunk_partials(vals, rel.astype(jnp.int32), 128, "sum")
        if not np.allclose(got, want, rtol=1e-5, atol=1e-6):
            why.append("pallas-interpret chunk partials != XLA")
        print("library check: pallas kernel rehearsed in interpret "
              "mode (REHEARSAL)")
    else:
        if eng.reduce_method != "pallas":
            why.append(f"reduce_method={eng.reduce_method!r}")
        if not kernels:
            why.append("no tpu_custom_call in the compiled step")
    trail = memwatch.MemoryTrail()
    trail.sample("chip_smoke")
    v = memwatch.bench_digest(eng, trail=trail)
    print(f"library check: memwatch grade={v.get('grade')} "
          f"peak={v.get('peak_bytes')} ledger={v.get('ledger_bytes')} "
          f"ratio={v.get('ratio')} tol={v.get('tol')} "
          f"errors={v.get('errors')} (observation, gates nothing)")
    return {"leg": "lib", "ok": not why,
            "why": "; ".join(why) or (
                f"reduce={eng.reduce_method}, tpu_custom_call "
                f"x{kernels} in the compiled step"),
            "reduce": eng.reduce_method, "tpu_custom_calls": kernels,
            "memwatch": v}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever backend is here; "
                         "never prints the pass line")
    ap.add_argument("--seed", type=int, default=0,
                    help="R-MAT generator seed (default 0)")
    ap.add_argument("--legs", default=",".join(ALL_LEGS),
                    help="comma list out of a..g (default: all).  A "
                         "partial run never prints the pass line")
    args = ap.parse_args(argv)
    legs = [x for x in args.legs.split(",") if x]
    if any(x not in ALL_LEGS for x in legs):
        ap.error(f"--legs takes a comma list out of {ALL_LEGS}")
    partial = set(legs) != set(ALL_LEGS)

    t_start = time.perf_counter()
    import jax

    from lux_tpu import native, observe, runtime

    cache_dir = runtime.use_compile_cache()
    dev = jax.devices()[0]
    ndev = len(jax.devices())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": ndev}
    from importlib import metadata
    vers = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            vers[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            vers[pkg] = "absent"
    print(f"device: platform={dev.platform} device_kind="
          f"{dev.device_kind} count={ndev}; " + " ".join(
              f"{k}={v}" for k, v in vers.items()))
    print(f"compile cache: {cache_dir}")
    if args.rehearsal:
        print("REHEARSAL, NOT A CHIP RUN")
    elif dev.platform != "tpu":
        print(f"error: no TPU (platform={dev.platform!r}); the smoke "
              f"measures the chip or nothing.  --rehearsal runs tiny "
              f"sizes here and cannot pass.", file=sys.stderr)
        return 2
    expect_reduce = "pallas" if dev.platform == "tpu" else "xla"

    cache = {"requests": 0, "hits": 0, "writes": 0}
    _EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
               "requests",
               "/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "writes"}

    def on_event(event, **_kw):
        if event in _EVENTS:
            cache[_EVENTS[event]] += 1

    jax.monitoring.register_event_listener(on_event)

    if not native.ensure_built(quiet=False):
        print("error: the native tools did not build (make -C "
              "lux_tpu/native); refusing to generate graphs with a "
              "different generator", file=sys.stderr)
        return 1

    shapes = SHAPES["rehearsal" if args.rehearsal else "full"]
    out_dir = os.path.join(HERE, ".chip_smoke")     # the .lux files
    os.makedirs(out_dir, exist_ok=True)
    results = []

    def phase(leg, fn):
        """Run one phase; a crash is that phase's failure record, not
        the end of the run — the remaining phases still report."""
        try:
            results.extend(fn())
        except Exception as e:  # noqa: BLE001 — boundary: report on
            traceback.print_exc()
            results.append({"leg": leg, "ok": False,
                            "why": f"{type(e).__name__}: {e}"})
        gc.collect()                # drop the phase's device arrays

    # the session probe FIRST: the chip's first program is a small
    # gather and its first Mosaic compile the lane-shuffle kernel, so
    # a broken toolchain fails in seconds, before the big builds.  Its
    # ns/elem and grade are printed and gate nothing (the canon they
    # are graded against predates this installation).
    def probe():
        fp = observe.calibrate()
        print(f"probe: gather {fp.probe['gather_small_ns']:.3f} "
              f"ns/elem, pair-dot row "
              f"{fp.probe['pair_dot_row_ns']:.1f} ns, page row "
              f"{fp.probe['page_gather_row_ns']:.1f} ns; deviation "
              f"{fp.deviation:.3f}x canon, grade={fp.grade} (smoke "
              f"observation, not a metric; gates nothing)")
        return [{"leg": "probe", "ok": True,
                 "why": f"gather {fp.probe['gather_small_ns']:.3f} "
                        f"ns/elem, grade={fp.grade} (observation, "
                        f"not a metric)",
                 "probe": fp.probe, "grade": fp.grade,
                 "deviation": fp.deviation}]

    print("\n=== session probe (observe.calibrate)", flush=True)
    phase("probe", probe)

    graphs = {}

    def graph(role):
        if role not in graphs:
            graphs[role] = make_graph(out_dir, shapes[role], args.seed)
        return graphs[role]

    def cli_leg(name, sub=None, mesh=0):
        role, app, flags = CLI_LEGS[sub or name]
        return [run_cli_leg(name, graph(role), app, flags,
                            expect_reduce, mesh=mesh)]

    if "a" in legs:
        phase("lib", lambda: [library_check(graph("mid"),
                                            args.rehearsal)])
    for name in legs:
        if name in CLI_LEGS:
            phase(name, lambda: cli_leg(name))
        elif name == "f":
            phase("f", lambda: [run_serve_leg(shapes["serve"])])
        elif ndev >= 4:
            for sub in MESH_LEGS:
                phase(f"g/{sub}-mesh4", lambda: cli_leg(
                    f"g/{sub}-mesh4", sub, mesh=4))
        else:
            results.append({"leg": "g", "ok": None, "why":
                            f"mesh4: not run ({ndev} device visible)"})

    total = time.perf_counter() - t_start
    print("\n=== summary")
    for r in results:
        tag = {True: "PASS", False: "FAIL", None: "NOT RUN"}[r["ok"]]
        if "nv" not in r:
            print(f"{r['leg']:<10} {tag:<7} {r['why']}")
            continue

        def s(key):
            return "-" if r.get(key) is None else f"{r[key]:.1f}"

        peak = r.get("peak_bytes")
        print(f"{r['leg']:<10} {tag:<7} nv={r['nv']} ne={r['ne']} "
              f"iters={r.get('iters')} s[load+layout/build/"
              f"compile+warm/run/wall]={s('load_layout_s')}/"
              f"{s('build_s')}/{s('warm_s')}/{s('run_s')}/"
              f"{s('wall_s')} peak_bytes="
              f"{'n/a' if peak is None else peak} "
              f"| {r.get('check')} {r['why']}")
    print(f"device: {dev.device_kind} x{ndev}; compile cache: "
          f"{cache['requests']} request(s), {cache['hits']} hit(s), "
          f"{cache['writes']} new entr(ies) in {cache_dir}")
    print(f"total {total:.1f} s")

    failed = [r["leg"] for r in results if r["ok"] is False]
    summary = {"device": device, "versions": vers,
               "rehearsal": args.rehearsal, "legs": legs,
               "seed": args.seed, "total_s": round(total, 1),
               "cache": cache, "failed": failed, "results": results}
    report_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(report_dir, exist_ok=True)
    with open(os.path.join(report_dir, "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)

    if failed:
        print(f"FAILED: {', '.join(failed)}")
        return 1
    if args.rehearsal:
        print("REHEARSAL, NOT A CHIP RUN — selected legs passed at "
              "tiny sizes; this proves nothing about the chip")
        return 0
    if partial:
        print(f"PARTIAL RUN (--legs {args.legs}): selected legs "
              f"passed; no pass line without the full set")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
