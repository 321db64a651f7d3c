"""The weighted Kronecker cache: one generated graph WITH Graph500
kernel 3's edge weights per (scale, edge factor, symmetrized, seed)
under ``benchmarks/.cache/graphs/kron...-w32``, beside the unweighted
entries of ``graphs.py`` and in their form.

The edge tuples are ``graphs.py``'s (``reference/kronecker.py`` from
the same seed: the instance kernel 2 searches), each with one float32
weight uniform in [0, 1) (``reference/edge_weights.py``
``kernel3_arcs``); stored in both directions, the two arcs of a tuple
carry the same weight.

Each entry holds the weighted ``graph.lux`` the PROGRAM loads, written
through the program's own converter (``convert.edges_to_csc`` with
weights + ``format.write_lux(weights=)``; the file does not say that
its weights are float32: the loader is told), and the REFERENCE's
arrays (``ref_offsets.npy``, ``ref_src.npy``, ``ref_w.npy``: the arcs
sorted by destination), built from the same tuples by the benchmark's
own code.  ``graphs.cached_array`` keeps the reference's answers
beside them.
"""

from __future__ import annotations

import os
import shutil

import numpy as np

from benchmarks import graphs
from benchmarks.reference import edge_weights


def entry_dir(scale: int, edge_factor: int, symmetrized: bool,
              seed: int) -> str:
    return graphs.entry_dir(scale, edge_factor, symmetrized,
                            seed) + "-w32"


def ensure(scale: int, edge_factor: int, symmetrized: bool, seed: int):
    """Paths of the cached entry, generating it first where missing:
    {"lux", "ref_offsets", "ref_src", "ref_w", "generated_edges"}."""
    d = entry_dir(scale, edge_factor, symmetrized, seed)
    paths = {"lux": os.path.join(d, "graph.lux"),
             "ref_offsets": os.path.join(d, "ref_offsets.npy"),
             "ref_src": os.path.join(d, "ref_src.npy"),
             "ref_w": os.path.join(d, "ref_w.npy"),
             "generated_edges": int(edge_factor) << int(scale)}
    if os.path.exists(os.path.join(d, "DONE")):
        return paths
    from lux_tpu.convert import edges_to_csc
    from lux_tpu.format import write_lux

    nv = 1 << scale
    src, dst, w = edge_weights.kernel3_arcs(scale, edge_factor,
                                            symmetrized, seed)
    tmp = d + ".partial"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    offsets, by_src, by_w = edge_weights.by_destination(src, dst, w, nv)
    np.save(os.path.join(tmp, "ref_offsets.npy"), offsets)
    np.save(os.path.join(tmp, "ref_src.npy"), by_src)
    np.save(os.path.join(tmp, "ref_w.npy"), by_w)
    del offsets, by_src, by_w
    row_ptrs, col_idx, w_sorted, degrees = edges_to_csc(src, dst, nv, w)
    del src, dst, w
    write_lux(os.path.join(tmp, "graph.lux"), row_ptrs, col_idx,
              weights=w_sorted, degrees=degrees)
    with open(os.path.join(tmp, "DONE"), "w") as f:
        f.write("ok\n")
    shutil.rmtree(d, ignore_errors=True)
    os.rename(tmp, d)
    return paths


def load_reference(paths):
    """(offsets, src, w) of the reference's arcs, sorted by
    destination."""
    return (np.load(paths["ref_offsets"]), np.load(paths["ref_src"]),
            np.load(paths["ref_w"]))
