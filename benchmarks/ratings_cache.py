"""The rating-matrix cache: one generated matrix per (users, items,
ratings, seed) under ``benchmarks/.cache/graphs/netflix-...``, beside
the Kronecker entries of ``graphs.py`` and in their form.

Each entry holds the weighted ``graph.lux`` the PROGRAM loads, written
through the program's own converter (``convert.edges_to_csc`` with
weights + ``format.write_lux(weights=)``), and the REFERENCE's arrays
(``ref_offsets.npy``, ``ref_src.npy``, ``ref_rating.npy``: the stored
edges sorted by destination), built from the same pairs by the
benchmark's own code.  ``graphs.cached_array`` keeps the reference's
answers beside them.
"""

from __future__ import annotations

import os
import shutil

import numpy as np

from benchmarks import graphs
from benchmarks.reference import ratings as gen


def entry_dir(users: int, items: int, ratings: int, seed: int) -> str:
    return os.path.join(
        graphs.GRAPHS, f"netflix-{users}x{items}x{ratings}-seed{seed}")


def ensure(users: int, items: int, ratings: int, seed: int,
           user_skew: float, item_skew: float, marginal):
    """Paths of the cached entry, generating it first where missing:
    {"lux", "ref_offsets", "ref_src", "ref_rating", "generated_edges"}
    (both directions of every rating are generated AND stored)."""
    d = entry_dir(users, items, ratings, seed)
    paths = {"lux": os.path.join(d, "graph.lux"),
             "ref_offsets": os.path.join(d, "ref_offsets.npy"),
             "ref_src": os.path.join(d, "ref_src.npy"),
             "ref_rating": os.path.join(d, "ref_rating.npy"),
             "generated_edges": 2 * int(ratings)}
    if os.path.exists(os.path.join(d, "DONE")):
        return paths
    from lux_tpu.convert import edges_to_csc
    from lux_tpu.format import write_lux

    user, item, rating = gen.rating_pairs(
        users, items, ratings, seed, user_skew, item_skew, marginal)
    tmp = d + ".partial"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    offsets, src, rat = gen.by_destination(user, item, rating, users,
                                           items)
    np.save(os.path.join(tmp, "ref_offsets.npy"), offsets)
    np.save(os.path.join(tmp, "ref_src.npy"), src)
    np.save(os.path.join(tmp, "ref_rating.npy"), rat)
    del offsets, src, rat
    src, dst, weight = gen.both_directions(user, item, rating, users)
    del user, item, rating
    row_ptrs, col_idx, w_sorted, degrees = edges_to_csc(
        src, dst, users + items, weight)
    del src, dst, weight
    write_lux(os.path.join(tmp, "graph.lux"), row_ptrs, col_idx,
              weights=w_sorted, degrees=degrees)
    with open(os.path.join(tmp, "DONE"), "w") as f:
        f.write("ok\n")
    shutil.rmtree(d, ignore_errors=True)
    os.rename(tmp, d)
    return paths


def load_reference(paths):
    """(offsets, src, rating) of the reference's edges, sorted by
    destination."""
    return (np.load(paths["ref_offsets"]), np.load(paths["ref_src"]),
            np.load(paths["ref_rating"]))
