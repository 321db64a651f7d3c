"""Runner ``batch_push``: breadth-first searches from a seeded list of
roots on one push engine and one executable, a new ``(label, active)``
per root, each answer fetched to the host, until the window ends."""

from __future__ import annotations

import types

import jax
import numpy as np

from benchmarks import graphs
from benchmarks.harness import clock
from benchmarks.reference import bfs as ref
from benchmarks.runners import common


def _roots(run, offsets):
    """The traffic's ``roots`` vertices of non-zero degree (Graph500
    kernel 2 samples its search keys so): the same set for every
    seed, searched in an order drawn from the seed."""
    fixed = common.fixed_vertices(run, offsets, 2,
                                  int(run.traffic["roots"]))
    return common.seeded_order(run, 2, fixed)


def prepare(run):
    st = types.SimpleNamespace()
    c = run.config
    paths = common.cached_graph(run)
    offsets = np.load(paths["ref_offsets"])
    st.roots = _roots(run, offsets)
    del offsets
    with run.span("load_layout"):
        g_run, st.perm, st.sg = common.load_and_layout(run, paths)
    st.nv = run.graph["nv"]
    st.rank = None
    if st.perm is not None:
        st.rank = np.empty(st.nv, np.int64)
        st.rank[st.perm] = np.arange(st.nv)
    app = common.app_module(run)
    st.inf = app.HOP_INF
    with run.span("engine_build"):
        st.eng = app.build_engine(
            g_run, start_vertex=_engine_id(st, st.roots[0]),
            num_parts=int(c["num_parts"]), mesh=common.mesh_of(run),
            weighted=False, sg=st.sg, **c.get("engine", {}))
    with run.span("compile_warm"):
        _search(run, st, st.roots[0])
    del g_run
    return st


def _engine_id(st, v) -> int:
    return int(v) if st.rank is None else int(st.rank[int(v)])


def _search(run, st, root):
    """One search through the timed path -> (search seconds,
    iterations, hop labels [nv] in the engine's vertex order)."""
    with run.span("place"):
        r = _engine_id(st, root)
        label = np.full(st.nv, st.inf, dtype=np.int32)
        active = np.zeros(st.nv, dtype=bool)
        label[r] = 0
        active[r] = True
        label, active = st.eng.place(st.sg.to_padded(label),
                                     st.sg.to_padded(active))
        jax.block_until_ready((label, active))
    with run.span("search"):
        t0 = clock()
        label, active, it = st.eng.converge(label, active)
        iters = int(jax.device_get(it))
        search_s = clock() - t0
    with run.span("fetch"):
        answer = st.eng.unpad(label)
    return search_s, iters, answer


def window(run, st):
    st.searches, loop_s, loop_iters, traced_iters = [], 0.0, 0, 0
    t0 = run.begin_window()
    k = 0
    while clock() - t0 < run.seconds:
        tracing = run.trace_tick()
        root = st.roots[k % len(st.roots)]
        s, iters, answer = _search(run, st, root)
        st.searches.append((int(root), answer))
        loop_s += s
        loop_iters += iters
        if tracing:
            traced_iters += iters
        k += 1
    elapsed = clock() - t0
    run.counters.update(loop_seconds=loop_s, loop_iters=loop_iters,
                        traced_iters=traced_iters)
    st.elapsed = elapsed
    print(f"window: {k} searches, {loop_iters} iterations in "
          f"{elapsed:.3f} s ({loop_s:.3f} s inside the searches)",
          flush=True)


def verify(run, st):
    """Graph500's edge count of every search (by the reference's own
    degrees), and a seeded sample of the searches against the
    reference, level for level."""
    offsets, neighbours = graphs.load_reference(run.graph_paths)
    deg = np.diff(offsets)
    halves = 2 if run.config["symmetrized"] else 1
    traversed = 0
    for _root, answer in st.searches:
        reached = ref.hops_to_levels(answer, st.nv) >= 0
        traversed += int(deg[common.to_generator_ids(
            reached, st.perm)].sum()) // halves
    run.metrics["gteps_per_chip"] = (
        traversed / st.elapsed / run.chips / 1e9)
    run.attempted = len(st.searches)
    rng = np.random.default_rng([run.seed % (1 << 63), 3])
    picked = common.sample_indices(rng, len(st.searches),
                                   int(run.traffic["check_searches"]))
    mismatched = 0
    for i in picked:
        root, answer = st.searches[i]
        got = common.to_generator_ids(
            ref.hops_to_levels(answer, st.nv), st.perm)
        want = graphs.cached_array(
            run.graph_paths, f"ref_bfs_{root}",
            lambda: ref.bfs_levels(offsets, neighbours, root))
        bad = int(np.count_nonzero(got != want))
        if bad:
            run.failed += 1
        mismatched += bad
    print(f"checked {len(picked)} of {len(st.searches)} searches",
          flush=True)
    run.check("bfs_mismatched_levels", mismatched,
              run.config["guarantees"]["bfs_mismatched_levels"])
