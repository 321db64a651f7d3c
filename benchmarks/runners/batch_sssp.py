"""Runner ``batch_sssp``: Graph500 kernel 3.  Weighted single-source
shortest paths (float32 weights uniform in [0, 1), float32 distances)
from a fixed set of roots, one after another on one push engine and
one executable under the configuration's bucket width, a new
``(label, active)`` per root, each ``[nv]`` answer fetched to the
host.  The window is made of WHOLE passes over the roots: it ends with
the pass that passes the window's seconds, and the rate is over the
measured time, so every seed does the same multiset of searches.

The check is exact (``reference/sssp.py``): sampled searches against
the float32 fixed point bit for bit, and kernel 3's edge rule over
all stored edges.
"""

from __future__ import annotations

import contextlib
import resource
import types

import jax
import numpy as np

from benchmarks import graphs, kron_weighted_cache
from benchmarks.harness import clock
from benchmarks.reference import sssp as ref
from benchmarks.runners import common


def cached_graph(run):
    c = run.config
    run.graph_paths = kron_weighted_cache.ensure(
        c["scale"], c["edge_factor"], c["symmetrized"], c["graph_seed"])
    return run.graph_paths


def fixed_roots(run, paths):
    """The traffic's ``roots`` vertices of non-zero degree (Graph500
    samples its search keys so; kernel 3 searches from kernel 2's):
    the same set for every seed (``common.fixed_vertices``)."""
    src = np.load(paths["ref_src"], mmap_mode="r")
    nv = len(np.load(paths["ref_offsets"], mmap_mode="r")) - 1
    by_source = np.concatenate(
        [[0], np.cumsum(np.bincount(src, minlength=nv))])
    return common.fixed_vertices(run, by_source, 2,
                                 int(run.traffic["roots"]))


def load_and_layout(run, paths):
    """``common.load_and_layout`` for a file of float32 weights: the
    ``.lux`` does not say what its weights are, so the loader is told
    (``lux_tpu/cli.py sssp -weighted -weight-type float32``)."""
    from lux_tpu.graph import Graph, ShardedGraph, pair_relabel

    c = run.config
    num_parts = int(c["num_parts"])
    pair = c.get("engine", {}).get("pair_threshold")
    g = Graph.from_file(paths["lux"], weighted=True,
                        weight_dtype=np.float32)
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


def rank_of(perm):
    """``rank[generator id] = engine id`` (``perm[new] = old``)."""
    if perm is None:
        return None
    rank = np.empty(len(perm), np.int64)
    rank[perm] = np.arange(len(perm))
    return rank


def prepare(run):
    st = types.SimpleNamespace()
    c = run.config
    paths = cached_graph(run)
    st.roots = common.seeded_order(run, 2, fixed_roots(run, paths))
    with run.span("load_layout"):
        g_run, st.perm, st.sg = load_and_layout(run, paths)
    st.nv = run.graph["nv"]
    st.rank = rank_of(st.perm)
    with run.span("engine_build"):
        st.eng = common.app_module(run).build_engine(
            g_run, start_vertex=_engine_id(st, st.roots[0]),
            num_parts=int(c["num_parts"]), mesh=common.mesh_of(run),
            weighted=bool(c["weighted"]), sg=st.sg,
            **c.get("engine", {}))
    if st.eng.delta is None or not np.isfinite(st.eng.delta):
        # the cell is the bucket schedule's
        raise RuntimeError(f"engine.delta {c['engine'].get('delta')!r} "
                           f"resolved to {st.eng.delta!r}, no finite "
                           f"bucket width")
    print(f"bucket width {float(st.eng.delta)!r} "
          f"(engine.delta {c['engine'].get('delta')!r})", flush=True)
    with run.span("compile_warm"):
        search(run, st, st.roots[0])
    del g_run
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    print(f"host peak after set-up {peak:.1f} GB (ru_maxrss)", flush=True)
    return st


def _engine_id(st, v) -> int:
    return int(v) if st.rank is None else int(st.rank[int(v)])


def search(run, st, root, max_iters=None):
    """One search through the timed path -> (search seconds, relax
    iterations, distances float32 [nv] in the engine's vertex order).
    ``run`` None: no benchmark spans (``scripts/sweep_delta.py``)."""
    span = run.span if run is not None else \
        (lambda name: contextlib.nullcontext())
    with span("place"):
        r = _engine_id(st, root)
        label = np.full(st.nv, np.inf, dtype=np.float32)
        active = np.zeros(st.nv, dtype=bool)
        label[r] = 0
        active[r] = True
        label, active = st.eng.place(st.sg.to_padded(label),
                                     st.sg.to_padded(active))
        jax.block_until_ready((label, active))
    with span("search"):
        t0 = clock()
        label, active, it = st.eng.converge(label, active, max_iters)
        iters = int(jax.device_get(it))
        search_s = clock() - t0
    with span("fetch"):
        answer = st.eng.unpad(label)
    return search_s, iters, answer


def window(run, st):
    st.searches, loop_s, loop_iters, traced_iters = [], 0.0, 0, 0
    seconds = []                 # of the first pass's searches
    t0 = run.begin_window()
    passes = 0
    while clock() - t0 < run.seconds:        # whole passes
        for root in st.roots:
            tracing = run.trace_tick()       # between searches
            s, iters, answer = search(run, st, root)
            st.searches.append((int(root), iters, answer))
            if not passes:
                seconds.append(s)
            loop_s += s
            loop_iters += iters
            if tracing:
                traced_iters += iters
        passes += 1
    st.elapsed = clock() - t0
    run.counters.update(loop_seconds=loop_s, loop_iters=loop_iters,
                        traced_iters=traced_iters)
    print(f"window: {passes} passes over {len(st.roots)} roots, "
          f"{loop_iters} relax iterations in {st.elapsed:.3f} s "
          f"({loop_s:.3f} s inside the searches)", flush=True)
    print("first pass, root: relax iterations, seconds: " + "; ".join(
        f"{root}: {iters}, {s:.3f}" for (root, iters, _a), s in zip(
            st.searches, seconds)), flush=True)


def verify(run, st):
    """Graph500's edge count of every search (by the reference's own
    degrees), and a seeded sample of the searches, the one with most
    relax iterations among them, against the float32 fixed point bit
    for bit and against kernel 3's edge rule over all stored edges."""
    paths = run.graph_paths
    offsets, src, w = kron_weighted_cache.load_reference(paths)
    deg = np.bincount(src, minlength=st.nv)
    halves = 2 if run.config["symmetrized"] else 1
    # searches of one root are, as a rule, bitwise alike: each
    # distinct answer is counted and compared once
    seen = {}                   # root -> [(answer, traversed)]

    def traversed_by(root, answer):
        for a, t in seen.setdefault(root, []):
            if np.array_equal(a, answer):
                return t
        reached = common.to_generator_ids(np.isfinite(answer), st.perm)
        t = int(deg[reached].sum()) // halves
        seen[root].append((answer, t))
        return t

    traversed = sum(traversed_by(root, answer)
                    for root, _iters, answer in st.searches)
    run.metrics["gteps_per_chip"] = (
        traversed / st.elapsed / run.chips / 1e9)
    run.attempted = len(st.searches)
    rng = np.random.default_rng([run.seed % (1 << 63), 3])
    most = int(np.argmax([iters for _r, iters, _a in st.searches]))
    picked = common.sample_indices(
        rng, len(st.searches), int(run.traffic["check_searches"]),
        always=[most])
    dst = np.repeat(np.arange(st.nv, dtype=np.int32), np.diff(offsets))
    mismatched = violated = 0
    for i in picked:
        root, _iters, answer = st.searches[i]
        got = common.to_generator_ids(answer, st.perm)

        def fixed_point(root=root):
            label, sweeps = ref.fixed_point_f32(offsets, src, w, root)
            print(f"reference: root {root} fixed point after {sweeps} "
                  f"sweeps", flush=True)
            return label

        want = graphs.cached_array(paths, f"ref_sssp_f32_{root}",
                                   fixed_point)
        bad = ref.mismatched(got, want)
        broken = (ref.edges_violated(got, src, dst, w)
                  + ref.roots_nonzero(got, root))
        run.failed += bool(bad or broken)
        mismatched += bad
        violated += broken
    print(f"checked {len(picked)} of {len(st.searches)} searches",
          flush=True)
    g = run.config["guarantees"]
    run.check("sssp_mismatched_dists", mismatched,
              g["sssp_mismatched_dists"])
    run.check("sssp_edges_violated", violated, g["sssp_edges_violated"])
