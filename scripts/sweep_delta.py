"""Bucket-width sweep of weighted SSSP on a benchmark cell's own graph
(``ssspw.kron21.delta``: Graph500 kernel 3's Kronecker graph, float32;
``ssspw.road.delta``: the GAP suite's road network, int32): SECONDS a
search to the converged answer, with the relax iterations, the
relax-free advances and the dense / sparse split beside them (of the
dense trips, those whose front fit the queue and ran dense for its
out-edges), the edges relaxed and the vertices a relax trip held.

Every iteration of the push engine is fixed-shape (dense = all edges;
sparse = the ladder's rungs), so a narrower bucket cannot shrink an
iteration: it trades re-relaxed edges (fewer) against loop trips
(more), and it moves iterations from the dense to the sparse branch.
Where that trade lands is read here in seconds, never in
``ne x iterations`` (which rewards wasted iterations).

    python3 scripts/sweep_delta.py [--workload ssspw.kron21.delta]
        [--widths none,0.01,auto,0.1,0.25,1.0] [--out FILE.json]
        [--config FILE.json]

The graph, the roots and the engine's options are the cell's own
(its configuration and traffic files by ``BENCHMARK.json``; the cache
entry of its runner, generated on the first use), loaded and laid out
as the cell's runner does; ``--config`` puts another configuration
file of the same runner in the cell's place (another size of the
graph).  Every width's answers are compared with the first width's,
bit for bit.  One JSON object a width on standard output, then a
table.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _width(text: str):
    return None if text == "none" else text if text == "auto" \
        else float(text)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="scripts/sweep_delta.py")
    ap.add_argument("--workload", default="ssspw.kron21.delta")
    ap.add_argument("--widths", default="none,0.01,auto,0.1,0.25,1.0")
    ap.add_argument("--out", default=None)
    ap.add_argument("--config", default=None)
    args = ap.parse_args(argv)

    import importlib

    import jax
    import numpy as np

    from benchmarks import harness
    from lux_tpu import runtime, telemetry
    from lux_tpu.apps import sssp

    runtime.use_compile_cache()
    t0 = time.perf_counter()
    _cell, config, traffic = harness.cell_of(harness.load_benchmark(),
                                             args.workload)
    if args.config:
        config = harness.load_json(args.config)
    runner = importlib.import_module(
        "benchmarks.runners." + config["runner"])
    options = {k: v for k, v in config["engine"].items()
               if k != "delta"}
    run = types.SimpleNamespace(config=config, graph={}, seed=0,
                                traffic=traffic)
    paths = runner.cached_graph(run)
    roots = runner.fixed_roots(run, paths)
    g_run, perm, sg = runner.load_and_layout(run, paths)
    st = types.SimpleNamespace(perm=perm, sg=sg, nv=run.graph["nv"],
                               rank=runner.rank_of(perm))
    # a runner whose labels are not float32 hands the search's start
    # state over in the program's type (batch_sssp_road.starts_in)
    starts_in = getattr(runner, "starts_in", lambda eng, _config: eng)
    print(f"# graph ready nv={g_run.nv} ne={g_run.ne} "
          f"({time.perf_counter() - t0:.0f} s); roots "
          f"{[int(r) for r in roots]}; platform "
          f"{jax.devices()[0].platform}", flush=True)

    def mark():
        return [r for r in telemetry.spans()
                if r["name"] == "push.converge"][-1]["counts"]

    rows, first = [], None
    for text in args.widths.split(","):
        t1 = time.perf_counter()
        st.eng = starts_in(sssp.build_engine(
            g_run, start_vertex=0, num_parts=1, weighted=True,
            delta=_width(text), sg=sg, **options), config)
        # compile and warm: one relax iteration (a road search is
        # thousands, tens of seconds)
        runner.search(None, st, roots[0], max_iters=1)
        build_s = time.perf_counter() - t1
        seconds, sums, answers = [], {}, []
        for root in roots:
            s, _iters, answer = runner.search(None, st, root)
            seconds.append(s)
            for k, v in mark().items():
                sums[k] = sums.get(k, 0) + int(v)
            answers.append(answer)
        if first is None:
            first = answers
        differ = sum(int(np.count_nonzero(
            a.view(np.uint32) != b.view(np.uint32)))
            for a, b in zip(answers, first))
        n = len(roots)
        row = {"delta": text,
               "resolved": None if st.eng.delta is None
               else float(st.eng.delta),
               "median_s": statistics.median(seconds),
               "total_s": sum(seconds),
               "iters": sums["iters"] / n,
               "advances": sums["advances"] / n,
               "dense_iters": (sums["iters"] - sums["sparse_iters"]) / n,
               "edge_dense_iters": sums["edge_dense_iters"] / n,
               "sparse_iters": sums["sparse_iters"] / n,
               "low_rung_iters": sums["low_rung_iters"] / n,
               "front_edges": sums["front_edges"] / n,
               "budget_edges": sums["budget_edges"] / n,
               "front_vertices": sums.get("front_vertices", 0) / n,
               "relaxed_edge_ratio": (
                   sums["front_edges"] / sums["graph_edges"]
                   if sums["graph_edges"] else None),
               "differ_from_first": differ,
               "build_s": build_s,
               "seconds": seconds}
        rows.append(row)
        print(json.dumps(row), flush=True)
        st.eng = None

    print("| delta | resolved | median s a search | sum of the "
          f"{len(roots)} | relax iterations | advances | dense (for "
          "the front's out-edges) | sparse (low rung) | edges relaxed "
          "/ stored | front edges a search | front vertices a relax "
          "trip | differ |")
    print("|---|---|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        ratio = "-" if r["relaxed_edge_ratio"] is None \
            else f"{r['relaxed_edge_ratio']:.3f}"
        res = "-" if r["resolved"] is None else f"{r['resolved']:.5g}"
        print(f"| {r['delta']} | {res} | {r['median_s']:.4f} | "
              f"{r['total_s']:.3f} | {r['iters']:.1f} | "
              f"{r['advances']:.1f} | {r['dense_iters']:.1f} "
              f"({r['edge_dense_iters']:.1f}) | "
              f"{r['sparse_iters']:.1f} ({r['low_rung_iters']:.1f}) | "
              f"{ratio} | {r['front_edges']:.0f} | "
              f"{r['front_vertices'] / max(r['iters'], 1):.1f} | "
              f"{r['differ_from_first']} |")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"platform": jax.devices()[0].platform,
                       "workload": args.workload,
                       "graph": run.graph, "rows": rows}, f, indent=1)
    return 1 if any(r["differ_from_first"] for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
