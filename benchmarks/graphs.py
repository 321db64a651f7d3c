"""The graph cache: one generated Kronecker graph per (scale, edge
factor, symmetrized, seed) under ``benchmarks/.cache/graphs/``.

Each entry holds the ``.lux`` file the PROGRAM loads, written through
the program's own converter (``convert.edges_to_csc`` +
``format.write_lux``: the in-process form of its converter tool), and
the REFERENCE's adjacency (``ref_offsets.npy``, ``ref_neighbours.npy``),
built from the same edge list by the benchmark's own code.  Only the
first run of a seed in a checkout pays generation.
"""

from __future__ import annotations

import os
import shutil

import numpy as np

from benchmarks.reference import adjacency, kronecker

HERE = os.path.dirname(os.path.abspath(__file__))
GRAPHS = os.path.join(HERE, ".cache", "graphs")


def entry_dir(scale: int, edge_factor: int, symmetrized: bool,
              seed: int) -> str:
    kind = "sym" if symmetrized else "dir"
    return os.path.join(GRAPHS,
                        f"kron{scale}x{edge_factor}-{kind}-seed{seed}")


def ensure(scale: int, edge_factor: int, symmetrized: bool, seed: int):
    """Paths of the cached entry, generating it first where missing:
    {"lux", "ref_offsets", "ref_neighbours", "generated_edges"}."""
    d = entry_dir(scale, edge_factor, symmetrized, seed)
    paths = {"lux": os.path.join(d, "graph.lux"),
             "ref_offsets": os.path.join(d, "ref_offsets.npy"),
             "ref_neighbours": os.path.join(d, "ref_neighbours.npy"),
             "generated_edges": int(edge_factor) << int(scale)}
    if os.path.exists(os.path.join(d, "DONE")):
        return paths
    from lux_tpu.convert import edges_to_csc
    from lux_tpu.format import write_lux

    nv = 1 << scale
    src, dst = kronecker.kronecker_edges(scale, edge_factor, seed)
    if symmetrized:
        src, dst = (np.concatenate([src, dst]),
                    np.concatenate([dst, src]))
    tmp = d + ".partial"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    offsets, neighbours = adjacency.by_source(src, dst, nv)
    np.save(os.path.join(tmp, "ref_offsets.npy"), offsets)
    np.save(os.path.join(tmp, "ref_neighbours.npy"), neighbours)
    del offsets, neighbours
    row_ptrs, col_idx, _w, degrees = edges_to_csc(src, dst, nv)
    write_lux(os.path.join(tmp, "graph.lux"), row_ptrs, col_idx,
              degrees=degrees)
    with open(os.path.join(tmp, "DONE"), "w") as f:
        f.write("ok\n")
    shutil.rmtree(d, ignore_errors=True)
    os.rename(tmp, d)
    return paths


def load_reference(paths):
    """(offsets, neighbours) of the reference's adjacency."""
    return (np.load(paths["ref_offsets"]),
            np.load(paths["ref_neighbours"]))


def cached_array(paths, name: str, compute):
    """A reference answer kept beside the graph it belongs to
    (``<entry>/<name>.npy``): computed by the benchmark's own reference
    on the first run of a seed, read back on the others, so that a
    run's check costs seconds, not the reference's full time."""
    path = os.path.join(os.path.dirname(paths["lux"]), name + ".npy")
    if os.path.exists(path):
        return np.load(path)
    value = np.asarray(compute())
    tmp = path + ".partial.npy"
    np.save(tmp, value)
    os.replace(tmp, path)
    return value
