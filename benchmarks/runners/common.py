"""What the batch runners share: graph from the cache, the program's
load / relabel / layout, and the engine from the configuration's
``engine`` options."""

from __future__ import annotations

import importlib

import numpy as np

from benchmarks import graphs


def cached_graph(run):
    """Generate or find the configuration's graph.  The graph is the
    deployment's data set: it comes from the configuration's
    ``graph_seed``, not from ``--seed``, so that every seed does the
    same work (``--seed`` draws and orders the traffic) and every run
    after a checkout's first finds graph and programs in the caches."""
    c = run.config
    paths = graphs.ensure(c["scale"], c["edge_factor"],
                          c["symmetrized"], c["graph_seed"])
    run.graph_paths = paths
    return paths


def load_and_layout(run, paths):
    """-> (graph as the engine runs it, perm or None, sharded layout).
    With ``engine.pair_threshold`` set the graph is degree-relabelled
    first, as ``lux_tpu.cli`` does under ``-pair``; ``perm[new] = old``
    maps answers back to the generator's vertex ids."""
    from lux_tpu.graph import Graph, ShardedGraph, pair_relabel

    c = run.config
    opts = c.get("engine", {})
    num_parts = int(c["num_parts"])
    pair = opts.get("pair_threshold")
    g = Graph.from_file(paths["lux"], weighted=None)
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


def mesh_of(run):
    from lux_tpu.parallel.mesh import make_mesh
    n = int(run.config.get("mesh", 1))
    return make_mesh(n) if n > 1 else None


def app_module(run):
    return importlib.import_module("lux_tpu.apps." + run.config["app"])


def to_generator_ids(answer, perm):
    """An answer indexed by the engine's vertex ids -> indexed by the
    generator's (``perm[new] = old``)."""
    if perm is None:
        return np.asarray(answer)
    out = np.empty_like(answer)
    out[perm] = answer
    return out


def seeded_order(run, stream: int, fixed):
    """``fixed`` (the same for every seed) in an order drawn from
    ``--seed``: every seed sends the same set, in another order."""
    rng = np.random.default_rng([run.seed % (1 << 63), stream])
    return np.asarray(fixed)[rng.permutation(len(fixed))]


def fixed_vertices(run, offsets, stream: int, n: int):
    """``n`` vertices of non-zero out-degree, drawn once from the
    configuration's ``graph_seed``: the same for every ``--seed``."""
    rng = np.random.default_rng([int(run.config["graph_seed"]), stream])
    candidates = np.flatnonzero(np.diff(offsets) > 0)
    return rng.choice(candidates, size=n, replace=False)


def sample_indices(rng, n: int, k: int, always=()):
    """``k`` of ``range(n)`` drawn from the seed, ``always`` among
    them."""
    chosen = list(dict.fromkeys(int(i) for i in always))[:k]
    rest = [i for i in rng.permutation(n) if i not in chosen]
    return sorted(chosen + [int(i) for i in rest[:max(k - len(chosen), 0)]])
