"""Edge-list <-> .lux conversion and synthetic graph generators.

Python implementations of the reference's offline converter tool
(reference tools/converter.cc:72-130: read `src dst` text pairs, sort by
destination, emit binary CSC + trailing out-degrees).  A native C++ CLI
with the same behavior lives in lux_tpu/native/ for billion-edge inputs;
this module is the in-process path and the test oracle.

Also provides an R-MAT generator (Chakrabarti et al., SDM'04 — the
standard recursive-matrix power-law generator; the reference's RMAT27
benchmark graph is such a graph, README.md:86) so benchmarks run without
downloading datasets.
"""

from __future__ import annotations

import numpy as np

from lux_tpu import format as luxfmt


def edges_to_csc(src, dst, nv: int, weights=None):
    """Sort edges by destination and build CSC end-offset arrays.

    Returns (row_ptrs[u8 nv], col_idx[u4 ne] = sources, sorted_weights,
    out_degrees[u4 nv]).  Same output semantics as the reference
    converter (converter.cc:98-124); the canonical order is (dst, src)
    so the Python and native converters produce byte-identical files.
    """
    src = np.asarray(src, dtype=np.uint32)
    dst = np.asarray(dst, dtype=np.uint32)
    if src.size and (int(src.max()) >= nv or int(dst.max()) >= nv):
        raise ValueError("edge endpoint out of range")
    # one packed-u64 FUSED radix sort instead of lexsort's two stable
    # passes (then instead of argsort + gathers: measured 2.1x at one
    # thread, parallel on pod hosts — PERF_NOTES round 4); identical
    # (dst, src) order.  The key carries src in its low 32 bits, so
    # the sorted col_idx falls out as a truncating cast and weights
    # ride as a sort payload — no post-sort gathers at all.
    from lux_tpu import native
    # compose the key in ONE uint64 buffer (three transient u64 copies
    # would cost ~50 GB extra peak at RMAT27 scale)
    key = dst.astype(np.uint64)
    key <<= np.uint64(32)
    np.bitwise_or(key, src, out=key)
    w_sorted = None
    if weights is not None:
        w_sorted = np.ascontiguousarray(weights)
        if np.shares_memory(w_sorted, weights):   # sort_kv permutes
            w_sorted = w_sorted.copy()            # IN PLACE
    native.sort_kv(key, () if w_sorted is None else (w_sorted,))
    col_idx = key.astype(np.uint32)  # truncation keeps the low half
    del key
    counts = np.bincount(dst, minlength=nv).astype(np.uint64)
    row_ptrs = np.cumsum(counts, dtype=np.uint64)
    out_degrees = np.bincount(src, minlength=nv).astype(np.uint32)
    return row_ptrs, col_idx, w_sorted, out_degrees


def convert_edge_list(text_path: str, lux_path: str, nv: int,
                      weighted: bool = False, weight_dtype=np.int32):
    """Convert a text edge list (`src dst [weight]` per line) to .lux."""
    ncols = 3 if weighted else 2
    data = np.loadtxt(text_path, dtype=np.float64, ndmin=2)
    if data.size == 0:
        data = data.reshape(0, ncols)
    if data.shape[1] != ncols:
        raise ValueError(
            f"{text_path}: expected {ncols} columns "
            f"({'src dst weight' if weighted else 'src dst'}), "
            f"got {data.shape[1]}")
    src = data[:, 0].astype(np.uint32)
    dst = data[:, 1].astype(np.uint32)
    w = data[:, 2].astype(weight_dtype) if weighted else None
    row_ptrs, col_idx, w_sorted, deg = edges_to_csc(src, dst, nv, w)
    luxfmt.write_lux(lux_path, row_ptrs, col_idx, w_sorted, deg)
    return row_ptrs, col_idx, w_sorted, deg


def rmat_edges(scale: int, edge_factor: int = 16, seed: int = 0,
               a: float = 0.57, b: float = 0.19, c: float = 0.19):
    """Generate an R-MAT edge list: nv = 2**scale, ne = nv * edge_factor.

    Vectorized: draws all `scale` quadrant choices for all edges at once.
    Produces a skewed power-law degree distribution comparable to the
    reference's RMAT27 benchmark graph.
    """
    nv = 1 << scale
    ne = nv * edge_factor
    rng = np.random.default_rng(seed)
    src = np.zeros(ne, dtype=np.uint64)
    dst = np.zeros(ne, dtype=np.uint64)
    if not 0.0 < a + b + c <= 1.0:
        raise ValueError("quadrant probabilities must satisfy 0 < a+b+c <= 1")
    # Per bit level: pick quadrant with probs (a, b, c, 1-a-b-c).
    for _ in range(scale):
        r = rng.random(ne)
        src_bit = (r >= a + b).astype(np.uint64)          # quadrants c,d
        dst_bit = ((r >= a) & (r < a + b) | (r >= a + b + c)).astype(np.uint64)
        src = (src << np.uint64(1)) | src_bit
        dst = (dst << np.uint64(1)) | dst_bit
    # Permute vertex ids so the skew is not correlated with id order.
    perm = rng.permutation(nv).astype(np.uint32)
    return perm[src.astype(np.uint32)], perm[dst.astype(np.uint32)], nv


def rmat_graph(scale: int, edge_factor: int = 16, seed: int = 0,
               use_native: bool = True):
    """Build an R-MAT Graph with the native C++ generate+sort+CSC
    path (~10x faster host setup at benchmark scales).  The NumPy
    generator (``use_native=False``: rmat_edges + edges_to_csc) draws
    from a different RNG stream — same distribution, a DIFFERENT
    graph for the same seed — so it is never substituted silently:
    a missing native library raises (native._load_lib) instead of
    handing a benchmark another instance."""
    from lux_tpu.graph import Graph

    if use_native:
        from lux_tpu import native
        row_ptrs, col_idx, degrees = native.rmat_csc(
            scale, edge_factor, seed)
        nv = 1 << scale
        return Graph(nv=nv, ne=int(col_idx.shape[0]),
                     row_ptrs=row_ptrs, col_idx=col_idx,
                     weights=None, out_degrees=degrees)
    src, dst, nv = rmat_edges(scale, edge_factor, seed)
    return Graph.from_edges(src, dst, nv)


def netflix_like_edges(n_users: int = 480_000, n_items: int = 17_700,
                       n_ratings: int = 100_000_000, seed: int = 0,
                       user_skew: float = 0.6, item_skew: float = 0.9):
    """Synthesize a NetFlix-shaped weighted bipartite rating set — the
    reference's fifth benchmark workload (reference README.md:88,
    col_filter/colfilter_gpu.cu:32-104): ~480K users x ~17.7K items,
    ~100M integer ratings 1..5, with power-law skew on BOTH sides
    (the most-rated item draws ~0.2-0.5% of all ratings, like the
    real dataset's top titles).

    Returns (src, dst, weights, nv): DIRECTED edges in BOTH
    directions (user->item and item->user, each rating twice — both
    endpoint states must receive gradient updates, exactly how the
    reference feeds its SGD), so ne = 2 * n_ratings after dedup.
    Vertex ids: users [0, n_users), items [n_users, n_users+n_items).
    (user, item) pairs are deduplicated like the real dataset's unique
    ratings; expect a few percent under 2*n_ratings."""
    rng = np.random.default_rng(seed)
    # Zipf-ish endpoint distributions via inverse-CDF sampling on
    # rank^(-skew) weights (exact rank popularity, no rejection).
    def sample(n, skew, count):
        w = (np.arange(1, n + 1, dtype=np.float64)) ** -skew
        cdf = np.cumsum(w)
        cdf /= cdf[-1]
        return np.searchsorted(cdf, rng.random(count)).astype(np.uint32)

    users = sample(n_users, user_skew, n_ratings)
    items = sample(n_items, item_skew, n_ratings)
    # dedup (user, item) pairs: one fused u64 key sort + boundary pass
    from lux_tpu import native
    key = users.astype(np.uint64)
    key *= np.uint64(n_items)
    key += items
    native.sort_kv(key, ())
    keep = np.ones(len(key), bool)
    keep[1:] = key[1:] != key[:-1]
    key = key[keep]
    users = (key // np.uint64(n_items)).astype(np.uint32)
    items = (key % np.uint64(n_items)).astype(np.uint32) + n_users
    # integer ratings 1..5, roughly the public dataset's marginal
    w = rng.choice(np.arange(1, 6, dtype=np.int32), size=len(users),
                   p=[0.05, 0.10, 0.23, 0.34, 0.28])
    src = np.concatenate([users, items])
    dst = np.concatenate([items, users])
    weights = np.concatenate([w, w])
    return src, dst, weights, n_users + n_items


def community_edges(scale: int, edge_factor: int = 16,
                    community_scale: int = 8, p_in: float = 0.98,
                    seed: int = 0, scrambled: bool = True,
                    weighted: bool = False):
    """Planted-partition (stochastic-block-model family) edge list:
    2^scale vertices in communities of 2^community_scale, each vertex
    drawing ``edge_factor`` out-edges, fraction ``p_in`` inside its
    own community — the LOCALITY-RICH synthetic counterpart of the
    R-MAT presets (real social/web graphs cluster like this; R-MAT
    famously does not, which is exactly the round-15 paged-gather
    finding).  The default ``p_in`` = 0.98 is web-graph-like
    intra-domain locality (most links stay within a host/domain);
    note the paged economics are SHARP in it — uniform cross edges
    pay one delivery row each, so achievable page fill is about
    128 / (p_in + 128 * (1 - p_in)) under perfect clustering: ~36 at
    0.98, only ~10 at 0.9.  ``scrambled`` (default) applies a seeded
    random relabel, so the locality EXISTS but is not handed to the
    layout for free — recovering it is the reorder pass's job
    (lux_tpu/reorder.py); scrambled=False keeps communities
    contiguous (the oracle best order, for break-even pins).

    Returns (src, dst, weights|None, nv) uint32 edge arrays.
    """
    if not 0.0 <= p_in <= 1.0:
        raise ValueError(f"p_in must be in [0, 1], got {p_in}")
    if community_scale > scale:
        raise ValueError(f"community_scale {community_scale} > "
                         f"scale {scale}")
    rng = np.random.default_rng(seed)
    nv = 1 << scale
    csize = 1 << community_scale
    ne = nv * edge_factor
    src = np.repeat(np.arange(nv, dtype=np.int64), edge_factor)
    comm = src // csize
    inside = rng.random(ne) < p_in
    dst = np.where(
        inside,
        comm * csize + rng.integers(0, csize, size=ne),
        rng.integers(0, nv, size=ne))
    if scrambled:
        shuf = rng.permutation(nv)
        src = shuf[src]
        dst = shuf[dst]
    w = (rng.integers(1, 6, size=ne).astype(np.int32)
         if weighted else None)
    return src.astype(np.uint32), dst.astype(np.uint32), w, nv


def community_graph(scale: int, edge_factor: int = 16,
                    community_scale: int = 8, p_in: float = 0.98,
                    seed: int = 0, scrambled: bool = True,
                    weighted: bool = False):
    """community_edges assembled into a Graph (dst-sorted CSC)."""
    from lux_tpu.graph import Graph

    src, dst, w, nv = community_edges(
        scale, edge_factor, community_scale, p_in, seed,
        scrambled=scrambled, weighted=weighted)
    return Graph.from_edges(src, dst, nv, weights=w)


def uniform_random_edges(nv: int, ne: int, seed: int = 0, weighted=False):
    """Erdos-Renyi-ish random edge list (test-sized graphs)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nv, size=ne, dtype=np.uint32)
    dst = rng.integers(0, nv, size=ne, dtype=np.uint32)
    if weighted:
        w = rng.integers(1, 6, size=ne, dtype=np.int32)
        return src, dst, w
    return src, dst
