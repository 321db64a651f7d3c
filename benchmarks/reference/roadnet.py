"""A seeded road-like network to a DIMACS road instance's counts, plain
NumPy: what ``USA-road-d.*`` (9th DIMACS Implementation Challenge, the
GAP Benchmark Suite's graph "Road") is as a SHAPE, since the files are
not in the repository and there is no network.

What a road file is, and what this makes:

- a near-planar network of bounded degree: intersections joined by
  road segments, most of them chains of degree-2 points, many dead
  ends; average degree 2.4, no hub;
- ONE connected component, deep: hundreds to thousands of hops across
  (the source: 6,304 at 23.9 M vertices, 1.29 sqrt(n));
- every segment stored both ways with one integer length; lengths
  follow the geometry (short blocks in towns, long arcs between them)
  with a heavy right tail;
- vertex ids are local: a file is written region by region.

The construction.  Intersections ("sites") stand on an ``R x C``
lattice.  Roads are lattice edges: first a spanning tree (every site
off the first row and column keeps its north or its west edge, a fair
coin: the "binary tree" maze, connected by construction and loop-free),
then further lattice edges drawn uniformly until the edge count is the
instance's.  The remaining vertices are chain points, which subdivide
drawn edges into chains of degree-2 vertices, evenly along the
segment, and dead ends: one stub each off a drawn site, a fraction of
the local spacing long.  So the graph has exactly ``nv`` vertices and
``arcs // 2`` edges, max degree 5, one component, and no shortcut: a
path across is at least the lattice distance, times the chains.

Lengths are planar: rows and columns lie at cumulative spacings,
scaled to the instance's ground extent in decimetres; the spacing of
a BAND of rows (columns) is log-normal about the mean (towns: narrow
bands; open country: wide ones), a few bands are DESERT (a drawn
factor wider), and each line varies a little about its band.  Sites
are jittered off their crossing.  An arc's weight is the rounded
Euclidean length of its segment, at least 1.  The mean arc follows
from extent over lattice size; the tail from the bands (the widest
desert lines are 76-91 means at the DIMACS instances' sizes).

Ids: sites in tiles of ``tile x tile`` lattice cells, tile by tile
(a county at a time), row-major inside; a chain point follows the
site that owns its edge, a dead end its site.

This module imports nothing of the program under test.
"""

from __future__ import annotations

import numpy as np

# the defaults are the configuration's (configs/dimacs-road-sssp.json
# ``shape`` states them again; the cache keys its entry by them), but
# for the ground, which is the instance's (``extent_km`` there)
SHAPE = {
    "site_share": 0.75,         # lattice sites / vertices
    "stub_share": 0.6,          # of the other vertices: dead ends
    "chain_mean": 2.5,          # chain points a subdivided edge
    "extent_km": [1000.0, 1150.0],   # ground extent: width, height
    "band": 16,                 # lattice lines a spacing band
    "band_sigma": 0.8,          # log-normal sigma of a band's spacing
    "line_sigma": 0.25,         # and of a line about its band
    "desert_share": 0.03,       # bands that are desert
    "desert_factor": [12.0, 40.0],   # their spacing, times the band's
    "jitter": 0.25,             # a site off its crossing, in spacings
    "tile": 32,                 # id order: lattice cells a tile side
}


def _spacings(rng, n: int, shape) -> np.ndarray:
    """``n`` positive gaps between consecutive lattice lines."""
    nb = -(-n // int(shape["band"]))
    band = rng.lognormal(0.0, float(shape["band_sigma"]), nb)
    lo, hi = shape["desert_factor"]
    desert = rng.random(nb) < float(shape["desert_share"])
    band = np.where(desert, band * rng.uniform(lo, hi, nb), band)
    line = rng.lognormal(0.0, float(shape["line_sigma"]), n)
    return np.repeat(band, int(shape["band"]))[:n] * line


def _lines(rng, n: int, extent_dm: float, shape):
    """Positions of ``n`` lattice lines over ``extent_dm`` and each
    line's own spacing (the mean of its two gaps)."""
    gaps = _spacings(rng, n - 1, shape)
    gaps *= extent_dm / gaps.sum()
    pos = np.concatenate([[0.0], np.cumsum(gaps)])
    own = np.empty(n)
    own[1:-1] = 0.5 * (gaps[1:] + gaps[:-1])
    own[0], own[-1] = gaps[0], gaps[-1]
    return pos, own


def lattice_of(nv: int, shape) -> tuple:
    """(rows, columns) of the site lattice: ``site_share`` of the
    vertices, in the extent's aspect."""
    sites = float(shape["site_share"]) * nv
    width, height = shape["extent_km"]
    cols = max(2, int(round(np.sqrt(sites * width / height))))
    rows = max(2, int(sites // cols))
    return rows, cols


def road_edges(nv: int, arcs: int, seed: int, **shape):
    """-> (u int32 [m], v int32 [m], w int32 [m], info): the ``m =
    arcs // 2`` undirected segments of a road-like network on exactly
    ``nv`` vertices, each to be stored both ways with its weight
    (``both_directions``), and what was made (``describe`` adds the
    degrees)."""
    shape = {**SHAPE, **shape}
    rng = np.random.default_rng([int(seed), 47])
    m = int(arcs) // 2
    R, C = lattice_of(nv, shape)
    S = R * C
    stubs = min(S, int(float(shape["stub_share"]) * (nv - S)))
    chain = nv - S - stubs              # chain points
    site_edges = m - (nv - S)           # lattice edges kept
    lattice_edges = 2 * S - R - C
    if not S - 1 <= site_edges <= lattice_edges:
        raise ValueError(
            f"{nv} vertices / {arcs} arcs do not fit a {R} x {C} "
            f"lattice: {site_edges} edges among {S} sites")

    # every lattice edge is the NORTH (k = 0) or the WEST (k = 1) edge
    # of exactly one site: edge id = 2 * site + k
    r, c = np.divmod(np.arange(S, dtype=np.int64), C)
    has_n, has_w = r > 0, c > 0
    coin = rng.random(S) < 0.5
    tree_n = has_n & (~has_w | coin)
    tree_w = has_w & (~has_n | ~coin)
    keep = np.zeros(2 * S, dtype=bool)
    keep[0::2], keep[1::2] = tree_n, tree_w
    exists = np.zeros(2 * S, dtype=bool)
    exists[0::2], exists[1::2] = has_n, has_w
    spare = np.flatnonzero(exists & ~keep)
    extra = site_edges - (S - 1)
    keep[rng.choice(spare, size=extra, replace=False)] = True
    edge = np.flatnonzero(keep)                 # ascending: by owner
    owner, k = edge >> 1, edge & 1
    other = np.where(k == 0, owner - C, owner - 1)

    # ground positions
    width, height = (1e4 * float(x) for x in shape["extent_km"])
    x_at, x_own = _lines(rng, C, width, shape)
    y_at, y_own = _lines(rng, R, height, shape)
    j = float(shape["jitter"])
    x = x_at[c] + rng.uniform(-j, j, S) * x_own[c]
    y = y_at[r] + rng.uniform(-j, j, S) * y_own[r]

    # chain points: ``chain`` of them over drawn edges, each drawn
    # edge at least one
    n_e = len(edge)
    points = np.zeros(n_e, dtype=np.int64)
    if chain:
        drawn = max(1, min(chain, int(chain / float(shape["chain_mean"]))))
        at = rng.integers(0, n_e, drawn)
        points += np.bincount(at, minlength=n_e)
        points += np.bincount(rng.choice(at, size=chain - drawn),
                              minlength=n_e)
    # a segment of p points is p + 1 equal pieces from ``other`` to
    # ``owner``; the pieces' vertices in order: other, its points, owner
    length = np.hypot(x[owner] - x[other], y[owner] - y[other])
    piece_w = np.maximum(1, np.rint(length / (points + 1))
                         ).astype(np.int32)

    # dead ends: a stub off a site, in a drawn direction
    stub_at = rng.choice(S, size=stubs, replace=False)
    reach = rng.uniform(0.2, 0.7, stubs) * np.minimum(
        x_own[c[stub_at]], y_own[r[stub_at]])
    stub_w = np.maximum(1, np.rint(reach)).astype(np.int32)

    # ids.  A site's key is its place in the tile order; after it come
    # the points of its north edge, of its west edge, then its stub
    T = int(shape["tile"])
    tiles_across = -(-C // T)
    site_key = (((r // T) * tiles_across + c // T) * (T * T)
                + (r % T) * T + c % T)
    first = np.cumsum(points) - points          # a segment's first point
    seg_of = np.repeat(np.arange(n_e), points)  # [chain]
    rank_in = np.arange(chain) - first[seg_of]
    on_north = np.zeros(S, dtype=np.int64)
    on_north[owner[k == 0]] = points[k == 0]
    on_edges = on_north.copy()
    on_edges[owner[k == 1]] += points[k == 1]
    before = np.where(k == 1, on_north[owner], 0)
    per_site = 2 + int(on_edges.max(initial=0))
    key = np.concatenate([
        site_key * per_site,
        site_key[owner[seg_of]] * per_site + 1 + before[seg_of]
        + rank_in,
        site_key[stub_at] * per_site + 1 + on_edges[stub_at]])
    new_id = np.empty(nv, dtype=np.int64)
    new_id[np.argsort(key, kind="stable")] = np.arange(nv)
    site_id, point_id, stub_id = (new_id[:S], new_id[S:S + chain],
                                  new_id[S + chain:])

    # the pieces: segment e runs other -> p_1 .. p_k -> owner
    n_pieces = points + 1
    starts = np.cumsum(n_pieces) - n_pieces
    seg = np.repeat(np.arange(n_e), n_pieces)       # [m - stubs]
    pos = np.arange(int(n_pieces.sum())) - starts[seg]
    # (one id more, so that a first or last piece, which takes its
    # site's id, still indexes something)
    points_then = np.append(point_id, 0)
    at = np.minimum(first[seg] + pos, chain)
    tail = np.where(pos == 0, site_id[other[seg]], points_then[at - 1])
    head = np.where(pos == points[seg], site_id[owner[seg]],
                    points_then[at])
    tail = np.concatenate([tail, site_id[stub_at]])
    head = np.concatenate([head, stub_id])
    w = np.concatenate([piece_w[seg], stub_w])
    assert len(w) == m
    info = {"vertices": int(nv), "edges": int(m), "arcs": 2 * int(m),
            "lattice": [int(R), int(C)], "sites": int(S),
            "chain_points": int(chain), "dead_end_stubs": int(stubs),
            "site_edges": int(site_edges),
            "weight_mean": float(w.mean()), "weight_max": int(w.max()),
            "weight_min": int(w.min()),
            "weights_over_100_means": int(
                np.count_nonzero(w > 100 * w.mean())),
            "weights_over_30_means": int(
                np.count_nonzero(w > 30 * w.mean()))}
    return (tail.astype(np.int32), head.astype(np.int32), w, info)


def both_directions(u, v, w):
    """The stored arcs: every segment both ways, one weight."""
    return (np.concatenate([u, v]), np.concatenate([v, u]),
            np.concatenate([w, w]))


def by_destination(src, dst, w, nv: int):
    """The reference's own form of the stored arcs, sorted by
    destination -> (offsets int64 [nv + 1], src int32, w int32)."""
    order = np.argsort(dst, kind="stable")
    offsets = np.zeros(nv + 1, dtype=np.int64)
    np.cumsum(np.bincount(dst, minlength=nv), out=offsets[1:])
    return offsets, np.asarray(src)[order], np.asarray(w)[order]


def describe(offsets, info: dict) -> dict:
    """``info`` with the degree histogram (shares of degree 0..8+)."""
    deg = np.diff(offsets)
    hist = np.bincount(np.minimum(deg, 8), minlength=9) / len(deg)
    return {**info, "degree_max": int(deg.max()),
            "degree_mean": float(deg.mean()),
            "degree_share": [round(float(h), 4) for h in hist]}


def hop_levels(offsets, src, root: int) -> np.ndarray:
    """Hop distance from ``root`` over the arcs (the graph is stored
    both ways, so in-arcs are out-arcs) -> int64 [nv], -1 unreached."""
    nv = len(offsets) - 1
    level = np.full(nv, -1, dtype=np.int64)
    level[int(root)] = 0
    front = np.asarray([int(root)])
    depth = 0
    while len(front):
        depth += 1
        lo, hi = offsets[front], offsets[front + 1]
        n = hi - lo
        idx = np.repeat(lo - (np.cumsum(n) - n), n) + np.arange(n.sum())
        nbr = np.unique(src[idx])
        front = nbr[level[nbr] < 0]
        level[front] = depth
    return level
