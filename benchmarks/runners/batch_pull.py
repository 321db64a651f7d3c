"""Runner ``batch_pull``: back-to-back fixed-iteration solves on one
pull engine (PageRank), each from a fresh ``init_state``, each answer
fetched to the host, until the window ends."""

from __future__ import annotations

import types

import jax
import numpy as np

from benchmarks import graphs
from benchmarks.harness import clock
from benchmarks.reference import pagerank as ref
from benchmarks.runners import common


def prepare(run):
    st = types.SimpleNamespace()
    c = run.config
    paths = common.cached_graph(run)
    with run.span("load_layout"):
        g_run, st.perm, sg = common.load_and_layout(run, paths)
    with run.span("engine_build"):
        st.eng = common.app_module(run).build_engine(
            g_run, int(c["num_parts"]), common.mesh_of(run), sg=sg,
            **c.get("engine", {}))
    st.iters = int(run.traffic.get("iterations", c["iterations"]))
    with run.span("compile_warm"):
        _solve(run, st)
    del g_run
    return st


def _solve(run, st):
    """One solve through the timed path -> (loop seconds, answer [nv]
    in the engine's vertex order)."""
    with run.span("init_state"):
        state = st.eng.init_state()
        jax.block_until_ready(state)
    with run.span("solve"):
        t0 = clock()
        state = st.eng.run(state, st.iters)
        jax.block_until_ready(state)
        loop_s = clock() - t0
    with run.span("fetch"):
        answer = st.eng.unpad(state)
    return loop_s, answer


def window(run, st):
    st.answers, loop_s, traced_iters = [], 0.0, 0
    t0 = run.begin_window()
    while clock() - t0 < run.seconds:
        tracing = run.trace_tick()
        s, answer = _solve(run, st)
        loop_s += s
        st.answers.append(answer)
        if tracing:
            traced_iters += st.iters
    elapsed = clock() - t0
    n = len(st.answers)
    edges = run.graph["generated_edges"]
    run.counters.update(loop_seconds=loop_s, loop_iters=n * st.iters,
                        traced_iters=traced_iters)
    run.metrics["gteps_per_chip"] = (
        edges * st.iters * n / elapsed / run.chips / 1e9)
    print(f"window: {n} solves of {st.iters} iterations in "
          f"{elapsed:.3f} s ({loop_s:.3f} s inside the loops)", flush=True)


def verify(run, st):
    """Every solve of the window against the float64 reference."""
    offsets, neighbours = graphs.load_reference(run.graph_paths)
    want = graphs.cached_array(
        run.graph_paths, f"ref_pagerank_{st.iters}it",
        lambda: ref.pagerank(offsets, neighbours, st.iters))
    deg = np.maximum(np.diff(offsets), 1)
    limits = run.config["guarantees"]
    run.attempted = len(st.answers)
    worst, seen = {}, []         # seen: (answer, its verdict)
    for answer in st.answers:
        bad = next((b for a, b in seen if np.array_equal(answer, a)),
                   None)
        if bad is None:          # not bitwise a solve already compared
            got = common.to_generator_ids(answer, st.perm) * deg
            nums = ref.compare_ranks(got, want)
            bad = any(not nums[k] <= limits[k] for k in nums)
            for k, v in nums.items():
                if k not in worst or not v <= worst[k]:
                    worst[k] = v
            seen.append((answer, bad))
        run.failed += bool(bad)
    for k, v in worst.items():
        run.check(k, v, limits[k])
