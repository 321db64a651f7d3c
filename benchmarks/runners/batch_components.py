"""Runner ``batch_components``: back-to-back solves of connected
components (max-label propagation, the unbatched push engine) to
convergence, each from a fresh ``init_state`` (every vertex active
with its own id), each ``[nv]`` answer fetched to the host, until the
solve that passes the window's seconds ends.  The graph is the
generated web crawl (``webgraph_cache``), directed as stored; the
check is the exact fixed point (``reference/components.py``) of every
solve."""

from __future__ import annotations

import hashlib
import resource
import types

import jax
import numpy as np

from benchmarks import graphs, webgraph_cache
from benchmarks.harness import clock
from benchmarks.reference import components as ref
from benchmarks.reference import webgraph
from benchmarks.runners import common


def cached_crawl(run):
    c = run.config
    run.graph_paths = webgraph_cache.ensure(
        c["vertices"], c["arcs"], c["graph_seed"],
        {k: c[k] for k in webgraph.PARAMETERS})
    return run.graph_paths


def prepare(run):
    st = types.SimpleNamespace()
    c = run.config
    paths = cached_crawl(run)
    with run.span("load_layout"):
        g_run, st.perm, sg = common.load_and_layout(run, paths)
    with run.span("engine_build"):
        st.eng = common.app_module(run).build_engine(
            g_run, int(c["num_parts"]), common.mesh_of(run), sg=sg,
            **c.get("engine", {}))
    with run.span("compile_warm"):
        _solve(run, st)
    del g_run
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    print(f"host peak after set-up {peak:.1f} GB (ru_maxrss)", flush=True)
    return st


def _solve(run, st):
    """One solve through the timed path -> (loop seconds, iterations,
    labels [nv] in the engine's vertex order)."""
    with run.span("init_state"):
        state = st.eng.init_state()
        jax.block_until_ready(state)
    with run.span("solve"):
        t0 = clock()
        label, _active, it = st.eng.converge(*state)
        iters = int(jax.device_get(it))
        loop_s = clock() - t0
    with run.span("fetch"):
        answer = st.eng.unpad(label)
    return loop_s, iters, answer


def window(run, st):
    st.answers, loop_s, loop_iters, traced_iters = [], 0.0, 0, 0
    t0 = run.begin_window()
    while clock() - t0 < run.seconds:
        tracing = run.trace_tick()       # between solves: whole solves
        s, iters, answer = _solve(run, st)
        st.answers.append(answer)
        loop_s += s
        loop_iters += iters
        if tracing:
            traced_iters += iters
    elapsed = clock() - t0
    n = len(st.answers)
    run.counters.update(loop_seconds=loop_s, loop_iters=loop_iters,
                        traced_iters=traced_iters)
    # Graph500's convention: input edges over time.  The iterations
    # are the algorithm's own and are NOT multiplied in
    run.metrics["gteps_per_chip"] = (
        run.graph["stored_edges"] * n / elapsed / run.chips / 1e9)
    print(f"window: {n} solves, {loop_iters} iterations in "
          f"{elapsed:.3f} s ({loop_s:.3f} s inside the loops)",
          flush=True)


def verify(run, st):
    """Every solve of the window against the reference's fixed point,
    vertex for vertex (bitwise-equal solves inherit the verdict)."""
    paths = run.graph_paths
    nv = run.graph["nv"]
    # the ids the engine's relabel gave the generator's vertices:
    # perm[new] = old, so rank[old] = new is where each starts
    perm = ref.check_permutation(
        np.arange(nv) if st.perm is None else st.perm, nv)
    rank = np.empty(nv, np.int64)
    rank[perm] = np.arange(nv)
    tag = hashlib.sha256(perm.tobytes()).hexdigest()[:16]

    def fixed_point():
        offsets, src = webgraph_cache.load_reference(paths)
        label, sweeps = ref.fixed_point(offsets, src, rank)
        print(f"reference: fixed point after {sweeps} sweeps",
              flush=True)
        return label

    want = graphs.cached_array(paths, f"ref_components_{tag}",
                               fixed_point)
    run.attempted = len(st.answers)
    worst, seen = 0, []          # seen: (answer, its mismatches)
    for answer in st.answers:
        bad = next((b for a, b in seen if np.array_equal(answer, a)),
                   None)
        if bad is None:          # not bitwise a solve already compared
            got = common.to_generator_ids(answer, st.perm)
            bad = ref.mismatched(got, want)
            seen.append((answer, bad))
        worst = max(worst, bad)
        run.failed += bool(bad)
    run.check("cc_mismatched_labels", worst,
              run.config["guarantees"]["cc_mismatched_labels"])
