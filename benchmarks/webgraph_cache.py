"""The web-crawl cache: one generated crawl per (vertices, arcs, seed)
under ``benchmarks/.cache/graphs/webgraph-...``, beside the Kronecker
and rating-matrix entries and in their form.

Each entry holds the ``graph.lux`` the PROGRAM loads, written through
the program's own converter (``convert.edges_to_csc`` +
``format.write_lux``), and the REFERENCE's arrays (``ref_offsets.npy``,
``ref_src.npy``: the arcs sorted by destination), built from the same
pairs by the benchmark's own code.  ``graphs.cached_array`` keeps the
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
from benchmarks.reference import webgraph as gen

# the largest components are worked out where that is cheap
COMPONENTS_UP_TO = 200_000


def entry_dir(vertices: int, arcs: int, seed: int, shape: dict) -> str:
    """The entry's directory; the generator's parameters are part of
    its name (a digest), so a changed model never finds an old
    crawl."""
    digest = hashlib.sha256(json.dumps(
        shape, sort_keys=True).encode()).hexdigest()[:8]
    return os.path.join(
        graphs.GRAPHS,
        f"webgraph-{vertices}x{arcs}-seed{seed}-{digest}")


def ensure(vertices: int, arcs: int, seed: int, shape: dict):
    """Paths of the cached entry, generating it first where missing:
    {"lux", "ref_offsets", "ref_src", "shape", "generated_edges"}
    (every generated arc is stored, as it is)."""
    d = entry_dir(vertices, arcs, seed, shape)
    paths = {"lux": os.path.join(d, "graph.lux"),
             "ref_offsets": os.path.join(d, "ref_offsets.npy"),
             "ref_src": os.path.join(d, "ref_src.npy"),
             "shape": os.path.join(d, "shape.json"),
             "generated_edges": int(arcs)}
    if os.path.exists(os.path.join(d, "DONE")):
        return paths
    from lux_tpu.convert import edges_to_csc
    from lux_tpu.format import write_lux

    src, dst = gen.web_arcs(vertices, arcs, seed, **shape)
    tmp = d + ".partial"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    info = gen.describe(src, dst, vertices,
                        components=vertices <= COMPONENTS_UP_TO)
    print("webgraph: " + json.dumps(info), flush=True)
    with open(os.path.join(tmp, "shape.json"), "w") as f:
        json.dump(info, f, indent=1)
    offsets, by_src = gen.by_destination(src, dst, vertices)
    np.save(os.path.join(tmp, "ref_offsets.npy"), offsets)
    np.save(os.path.join(tmp, "ref_src.npy"), by_src)
    del offsets, by_src
    # the same bits as uint32: the converter takes them without a copy
    row_ptrs, col_idx, _w, degrees = edges_to_csc(
        src.view(np.uint32), dst.view(np.uint32), vertices)
    del src, dst
    write_lux(os.path.join(tmp, "graph.lux"), row_ptrs, col_idx,
              degrees=degrees)
    with open(os.path.join(tmp, "DONE"), "w") as f:
        f.write("ok\n")
    shutil.rmtree(d, ignore_errors=True)
    os.rename(tmp, d)
    return paths


def load_reference(paths):
    """(offsets, src) of the reference's arcs, sorted by
    destination."""
    return np.load(paths["ref_offsets"]), np.load(paths["ref_src"])
