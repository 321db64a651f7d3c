"""Runner ``batch_sssp_road``: the GAP Benchmark Suite's SSSP on its
road graph.  Shortest paths with int32 weights and int32 distances
from a fixed set of roots, one after another on one push engine and
one executable under the configuration's bucket width, each ``[nv]``
answer fetched to the host, in WHOLE passes over the roots
(``batch_sssp``'s ``search`` and ``window``, imported, not copied).

The graph is ``roadnet_cache``'s, loaded with the configuration's
``weight_type``; the program takes its distance type from the weights
(``apps.sssp.distance_dtype``), and a program that has no such rule
(one that sums integer weights in float32) is refused before anything
is generated.

The check is exact (``reference/dijkstra.py``): EVERY search of the
window against the O(E) certificate of exactness, and the searches of
``check_searches`` roots, vertex for vertex, against Dijkstra.
"""

from __future__ import annotations

import resource
import types

import numpy as np

from benchmarks import graphs, roadnet_cache
from benchmarks.harness import BenchmarkError
from benchmarks.reference import dijkstra as ref
from benchmarks.runners import common
from benchmarks.runners.batch_sssp import (      # noqa: F401
    _engine_id, fixed_roots, rank_of, search, window)


def cached_graph(run):
    c = run.config
    run.graph_paths = roadnet_cache.ensure(
        c["vertices"], c["arcs"], c["graph_seed"],
        {**c["shape"], "extent_km": c["extent_km"]})
    return run.graph_paths


def load_and_layout(run, paths):
    """``common.load_and_layout`` with the weights' type from the
    configuration (``lux_tpu/cli.py sssp -weighted -weight-type``)."""
    from lux_tpu.graph import Graph, ShardedGraph, pair_relabel

    c = run.config
    num_parts = int(c["num_parts"])
    pair = c.get("engine", {}).get("pair_threshold")
    g = Graph.from_file(paths["lux"], weighted=True,
                        weight_dtype=np.dtype(c["weight_type"]))
    perm = starts = None
    g_run = g
    if pair is not None:
        g_run, perm, starts = pair_relabel(g, num_parts,
                                           pair_threshold=pair)
    sg = ShardedGraph.build(g_run, num_parts, starts=starts,
                            pair_threshold=pair)
    run.graph = {"nv": int(g.nv), "stored_edges": int(g.ne),
                 "generated_edges": int(paths["generated_edges"])}
    return g_run, perm, sg


class _StartsIn:
    """The engine, given ``batch_sssp.search``'s start state (float32,
    ``+inf`` unreached) in the program's own label type: ``place``
    alone is wrapped, ``converge`` and ``unpad`` are the engine's."""

    def __init__(self, eng, dtype, unreached):
        self._eng, self._dtype, self._unreached = eng, dtype, unreached

    def place(self, label, active):
        # never through float32: the sentinel is no float32 value
        out = np.full(label.shape, self._unreached, self._dtype)
        reached = ~np.isinf(label)
        out[reached] = label[reached]
        return self._eng.place(out, active)

    def __getattr__(self, name):
        return getattr(self._eng, name)


def starts_in(eng, config):
    """``eng`` taking ``batch_sssp.search``'s start state in the
    configuration's distance type (``scripts/sweep_delta.py`` too)."""
    return _StartsIn(eng, np.dtype(config["distance_type"]),
                     int(config["unreached"]))


def prepare(run):
    st = types.SimpleNamespace()
    c = run.config
    app = common.app_module(run)
    want = np.dtype(c["distance_type"])
    if not hasattr(app, "distance_dtype"):
        raise BenchmarkError(
            f"the program has no weighted distances of type {want}: "
            f"apps.{c['app']} has no distance_dtype (it sums "
            f"{c['weight_type']} weights in float32)")
    paths = cached_graph(run)
    st.roots = common.seeded_order(run, 2, fixed_roots(run, paths))
    with run.span("load_layout"):
        g_run, st.perm, st.sg = load_and_layout(run, paths)
    st.nv = run.graph["nv"]
    st.rank = rank_of(st.perm)
    with run.span("engine_build"):
        eng = app.build_engine(
            g_run, start_vertex=_engine_id(st, st.roots[0]),
            num_parts=int(c["num_parts"]), mesh=common.mesh_of(run),
            weighted=bool(c["weighted"]), sg=st.sg,
            **c.get("engine", {}))
    got = np.asarray(eng.program.identity)
    st.unreached = int(c["unreached"])
    if got.dtype != want or int(got) != st.unreached:
        raise BenchmarkError(
            f"the program's distances are {got.dtype} with {got!r} "
            f"for unreached; the configuration states {want} with "
            f"{st.unreached}")
    if eng.delta is None or not 0 < eng.delta < st.unreached:
        # the cell is the bucket schedule's
        raise BenchmarkError(
            f"engine.delta {c['engine'].get('delta')!r} resolved to "
            f"{eng.delta!r}, no finite bucket width")
    print(f"bucket width {eng.delta!r} (engine.delta "
          f"{c['engine'].get('delta')!r}), distances {got.dtype}",
          flush=True)
    st.eng = starts_in(eng, c)
    with run.span("compile_warm"):
        # max_iters is an argument of the one executable: a search of
        # one relax iteration compiles and warms what a whole one runs
        search(run, st, st.roots[0], max_iters=1)
    del g_run
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    print(f"host peak after set-up {peak:.1f} GB (ru_maxrss)", flush=True)
    return st


def verify(run, st):
    """Graph500's edge count of every search (by the reference's own
    degrees), the certificate of exactness over EVERY search, and the
    searches of ``check_searches`` roots (drawn from the seed) against
    Dijkstra, all vertices."""
    paths = run.graph_paths
    offsets, src, w = roadnet_cache.load_reference(paths)
    deg = np.diff(offsets)
    dst = np.repeat(np.arange(st.nv, dtype=np.int32), deg)
    halves = 2 if run.config["symmetrized"] else 1
    roots = sorted({root for root, _i, _a in st.searches})
    rng = np.random.default_rng([run.seed % (1 << 63), 3])
    picked = {roots[i] for i in common.sample_indices(
        rng, len(roots), int(run.traffic["check_searches"]))}
    # searches of one root are, as a rule, bitwise alike: each
    # distinct answer is counted and compared once
    seen = {}           # root -> [(answer, traversed, wrong, broken)]

    def judged(root, answer):
        for a, *numbers in seen.setdefault(root, []):
            if np.array_equal(a, answer):
                return numbers
        if answer.dtype != np.dtype(run.config["distance_type"]):
            raise BenchmarkError(f"an answer of type {answer.dtype}")
        got = common.to_generator_ids(answer, st.perm)
        traversed = int(deg[got != st.unreached].sum()) // halves
        broken = ref.certificate(got, src, dst, w, root,
                                 st.unreached)["violations"]
        wrong = 0
        if root in picked:
            want = graphs.cached_array(
                paths, f"ref_dijkstra_{root}",
                lambda: ref.dijkstra(offsets, src, w, root,
                                     st.unreached))
            wrong = ref.mismatched(got, want)
            far = got[got != st.unreached].max()
            print(f"root {root}: largest distance {int(far)} "
                  f"({int(far) / 2 ** 24:.3f} x 2^24), "
                  f"{int(np.count_nonzero(got == st.unreached))} "
                  f"unreached", flush=True)
        seen[root].append((answer, traversed, wrong, broken))
        return traversed, wrong, broken

    traversed = mismatched = violated = checked = 0
    for root, _iters, answer in st.searches:
        t, wrong, broken = judged(root, answer)
        traversed += t
        mismatched += wrong
        violated += broken
        checked += root in picked
        run.failed += bool(wrong or broken)
    run.metrics["gteps_per_chip"] = (
        traversed / st.elapsed / run.chips / 1e9)
    run.attempted = len(st.searches)
    print(f"checked {checked} of {len(st.searches)} searches against "
          f"Dijkstra, all against the certificate", flush=True)
    g = run.config["guarantees"]
    run.check("road_mismatched_dists", mismatched,
              g["road_mismatched_dists"])
    run.check("road_certificate_violations", violated,
              g["road_certificate_violations"])
