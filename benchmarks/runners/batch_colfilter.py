"""Runner ``batch_colfilter``: back-to-back fixed-iteration solves of
collaborative filtering on one pull engine, each from a fresh
``init_state``, each ``[nv, K]`` answer fetched to the host, until the
window ends: ``batch_pull``'s timed path on another graph and another
check.  The graph is the generated rating matrix (``ratings_cache``),
the check the float64 sweep (``reference/colfilter.py``) on what was
LEARNED."""

from __future__ import annotations

import resource
import types

import numpy as np

from benchmarks import graphs, ratings_cache
from benchmarks.reference import colfilter as ref
from benchmarks.runners import batch_pull, common


def cached_ratings(run):
    c = run.config
    run.graph_paths = ratings_cache.ensure(
        c["users"], c["items"], c["ratings"], c["graph_seed"],
        c["user_skew"], c["item_skew"], c["rating_marginal"])
    return run.graph_paths


def prepare(run):
    st = types.SimpleNamespace()
    c = run.config
    paths = cached_ratings(run)
    with run.span("load_layout"):
        g_run, st.perm, sg = common.load_and_layout(run, paths)
    with run.span("engine_build"):
        st.eng = common.app_module(run).build_engine(
            g_run, int(c["num_parts"]), common.mesh_of(run), sg=sg,
            **c.get("engine", {}))
    st.iters = int(run.traffic.get("iterations", c["iterations"]))
    with run.span("compile_warm"):
        batch_pull._solve(run, st)
    del g_run
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    print(f"host peak after set-up {peak:.1f} GB (ru_maxrss)", flush=True)
    return st


# the timed path is batch_pull's, line for line: a fresh init_state,
# run(state, iterations), the answer fetched, spans init_state / solve
# / fetch, the counters ms_per_iter reads, gteps_per_chip over ALL the
# window's time (its ``generated_edges`` are this cell's stored edges:
# both directions of every rating are generated and stored)
window = batch_pull.window


def verify(run, st):
    """Every solve of the window against the float64 reference."""
    paths = run.graph_paths
    offsets, src, rating = ratings_cache.load_reference(paths)
    want = graphs.cached_array(
        paths, f"ref_colfilter_{st.iters}it",
        lambda: ref.sweeps(offsets, src, rating, st.iters))
    init = ref.initial_factors(len(offsets) - 1)
    rmse_init, rmse_want = graphs.cached_array(
        paths, f"ref_colfilter_rmse_{st.iters}it",
        lambda: [ref.rmse(offsets, src, rating, init),
                 ref.rmse(offsets, src, rating, want)])
    limits = {k: v for k, v in run.config["guarantees"].items()
              if not k.startswith("_")}
    run.attempted = len(st.answers)
    worst, seen = {}, []         # seen: (answer, its verdict)
    for answer in st.answers:
        bad = next((b for a, b in seen if np.array_equal(answer, a)),
                   None)
        if bad is None:          # not bitwise a solve already compared
            got = common.to_generator_ids(answer, st.perm)
            nums = ref.compare_factors(
                got, want, init, ref.rmse(offsets, src, rating, got),
                rmse_want, rmse_init)
            bad = any(not nums[k] <= limits[k] for k in nums)
            for k, v in nums.items():
                if k not in worst or not v <= worst[k]:
                    worst[k] = v
            seen.append((answer, bad))
        run.failed += bool(bad)
    for k, v in worst.items():
        run.check(k, v, limits[k])
