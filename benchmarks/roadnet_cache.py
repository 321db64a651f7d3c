"""The road-network cache: one generated network per (vertices, arcs,
seed, shape) under ``benchmarks/.cache/graphs/roadnet-...``, beside
the Kronecker, rating-matrix and web-crawl entries, in
``kron_weighted_cache.py``'s form and file names.

Each entry holds the weighted ``graph.lux`` the PROGRAM loads, written
through the program's own converter (``convert.edges_to_csc`` with
weights + ``format.write_lux(weights=)``; the weights are int32, which
is what the loader takes a ``.lux``'s weights for unless told
otherwise), and the REFERENCE's arrays (``ref_offsets.npy``,
``ref_src.npy``, ``ref_w.npy``: the arcs sorted by destination, int32
weights), built from the same segments by the benchmark's own code
(``reference/roadnet.py``).  ``graphs.cached_array`` keeps the
reference's answers beside them.  What the generator made is printed
and kept as ``shape.json``.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil

import numpy as np

from benchmarks import graphs
from benchmarks.reference import roadnet


def entry_dir(vertices: int, arcs: int, seed: int, shape: dict) -> str:
    """The entry's directory; the generator's parameters are part of
    its name (a digest), so a changed model never finds an old
    network."""
    digest = hashlib.sha256(json.dumps(
        {**roadnet.SHAPE, **shape}, sort_keys=True).encode()
    ).hexdigest()[:8]
    return os.path.join(
        graphs.GRAPHS,
        f"roadnet-{vertices}x{arcs}-seed{seed}-{digest}-i32")


def ensure(vertices: int, arcs: int, seed: int, shape: dict):
    """Paths of the cached entry, generating it first where missing:
    {"lux", "ref_offsets", "ref_src", "ref_w", "shape",
    "generated_edges"} (the undirected segments; each is stored both
    ways)."""
    d = entry_dir(vertices, arcs, seed, shape)
    paths = {"lux": os.path.join(d, "graph.lux"),
             "ref_offsets": os.path.join(d, "ref_offsets.npy"),
             "ref_src": os.path.join(d, "ref_src.npy"),
             "ref_w": os.path.join(d, "ref_w.npy"),
             "shape": os.path.join(d, "shape.json"),
             "generated_edges": int(arcs) // 2}
    if os.path.exists(os.path.join(d, "DONE")):
        return paths
    from lux_tpu.convert import edges_to_csc
    from lux_tpu.format import write_lux

    u, v, w, info = roadnet.road_edges(vertices, arcs, seed, **shape)
    src, dst, w = roadnet.both_directions(u, v, w)
    del u, v
    # a directory of this process's own: two processes that miss the
    # entry at once (test workers) each make it, and one rename wins
    tmp = f"{d}.partial.{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    offsets, by_src, by_w = roadnet.by_destination(src, dst, w, vertices)
    info = roadnet.describe(offsets, info)
    print("roadnet: " + json.dumps(info), flush=True)
    with open(os.path.join(tmp, "shape.json"), "w") as f:
        json.dump(info, f, indent=1)
    np.save(os.path.join(tmp, "ref_offsets.npy"), offsets)
    np.save(os.path.join(tmp, "ref_src.npy"), by_src)
    np.save(os.path.join(tmp, "ref_w.npy"), by_w)
    del offsets, by_src, by_w
    # the same bits as uint32: the converter takes them without a copy
    row_ptrs, col_idx, w_sorted, degrees = edges_to_csc(
        src.view(np.uint32), dst.view(np.uint32), vertices, w)
    del src, dst, w
    write_lux(os.path.join(tmp, "graph.lux"), row_ptrs, col_idx,
              weights=w_sorted, degrees=degrees)
    with open(os.path.join(tmp, "DONE"), "w") as f:
        f.write("ok\n")
    done = os.path.join(d, "DONE")
    if not os.path.exists(done):
        shutil.rmtree(d, ignore_errors=True)      # a broken entry
    try:
        os.rename(tmp, d)
    except OSError:
        if not os.path.exists(done):
            raise
        shutil.rmtree(tmp, ignore_errors=True)    # the other one won
    return paths


def load_reference(paths):
    """(offsets, src, w) of the reference's arcs, sorted by
    destination; ``w`` int32."""
    return (np.load(paths["ref_offsets"]), np.load(paths["ref_src"]),
            np.load(paths["ref_w"]))
