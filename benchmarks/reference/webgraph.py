"""A web crawl's SHAPE from a seed: the directed graph a crawler of a
set of hosts leaves behind, at a given number of pages and arcs.

Plain NumPy; imports nothing of ``lux_tpu``.  The crawl the
configuration names (Indochina, 7,414,866 pages / 194,109,311 arcs) is
public but cannot be fetched here, so the data set is generated to its
shape, as ``ratings.py`` does for the NetFlix matrix.  The model, every
parameter of which the configuration states under ``assumed``:

- page ids are in URL order, so a HOST's pages are a contiguous id
  range; host sizes are heavy-tailed (lognormal);
- inside a host the pages form a directory TREE laid out in URL order
  (preorder; a directory's files stay together in runs): a page links
  to its parent and to its next sibling, a parent to its children.  A
  share of the directories are LISTINGS shown page after page, their
  children a SERIES: the parent links the first alone, each links the
  next and the one before, and has no other links of its own.  This
  is what gives a crawl its depth;
- a page's further links go to NEARBY ids of its host (the gap a
  power law up to the host's size: Boldi and Vigna's locality), and
  a share of them is COPIED by the pages right behind it (their
  similarity; Kumar et al.'s copying model);
- a tenth of the arcs leave the host, aimed at other hosts by
  popularity ~ rank^-alpha: mostly at the host's first page, the rest
  at any page of it; a share of the hosts nobody links to, and the
  pages of another share link to no other host (the two sides of
  Broder et al.'s bow-tie);
- a share of the pages are documents without links (the crawl's
  dangling nodes).

There is no self-loop and no duplicate arc, and the number of arcs is
the asked one to the arc: every block of hosts generates a little more
than its share, then drops links (never the tree's) down to it.  The
generator works in blocks of whole hosts, so its own peak is a few
hundred MB beside the two result arrays.  Nothing here knows the
engine: ``describe`` prints what came out, and the number of
iterations a solve takes on it is whatever it is.
"""

from __future__ import annotations

import numpy as np

# the model's parameters, as a configuration names them
PARAMETERS = ("host_size_median", "host_size_sigma", "tree_depth_mean",
              "tree_depth_max", "directory_run", "listing_share",
              "leaf_share",
              "gap_alpha", "out_degree_sigma", "out_degree_max",
              "copy_share", "inter_host_share", "host_popularity_alpha",
              "deep_link_share", "unlinked_host_share",
              "closed_host_share")
# pages a block holds (whole hosts; a larger host is a block alone):
# part of the data set's definition, since every block has its own
# random stream
BLOCK_PAGES = 400_000
# how much more than its share a block generates before it trims
OVERSHOOT = 0.03
TOP_UP_ROUNDS = 30


def host_starts(vertices: int, seed: int, size_median: float,
                size_sigma: float) -> np.ndarray:
    """First page id of every host, ascending, and ``vertices`` last:
    lognormal sizes (at least one page) drawn until the pages are
    used up; the last host takes what is left."""
    rng = np.random.default_rng([int(seed), 0])
    sizes = []
    left = int(vertices)
    while left > 0:
        n = max(16, int(left / (size_median
                                * np.exp(size_sigma ** 2 / 2))) + 16)
        s = np.maximum(1, rng.lognormal(np.log(size_median), size_sigma,
                                        n)).astype(np.int64)
        cut = int(np.searchsorted(np.cumsum(s), left, side="left")) + 1
        s = s[:cut]
        if s.sum() > left:
            s[-1] -= s.sum() - left
        sizes.append(s)
        left -= int(s.sum())
    starts = np.concatenate([[0], np.cumsum(np.concatenate(sizes))])
    assert starts[-1] == vertices and np.all(np.diff(starts) > 0)
    return starts


def _blocks(starts: np.ndarray):
    """Runs of whole hosts of about BLOCK_PAGES pages -> (first host,
    one past the last) pairs."""
    out, h, nh = [], 0, len(starts) - 1
    while h < nh:
        stop = int(np.searchsorted(starts, starts[h] + BLOCK_PAGES,
                                   side="left"))
        stop = min(max(stop, h + 1), nh)
        out.append((h, stop))
        h = stop
    return out


def _tree(depth_drawn: np.ndarray, first: np.ndarray, depth_max: int):
    """Preorder depths and parents of the hosts' directory trees.
    ``depth_drawn`` [n] are the wished depths (>= 1), ``first`` marks a
    host's first page (its root, depth 0).  A page lies at most one
    level below the page before it: depth[i] = min over j <= i of
    wished[j] + (i - j), a running minimum.  Its parent is the last
    page before it one level up (-1 for a root)."""
    n = len(depth_drawn)
    idx = np.arange(n, dtype=np.int64)
    wished = np.where(first, 0, depth_drawn).astype(np.int64)
    depth = idx + np.minimum.accumulate(wished - idx)
    parent = np.full(n, -1, np.int64)
    for k in range(depth_max):
        last = np.maximum.accumulate(np.where(depth == k, idx, -1))
        below = depth == k + 1
        parent[below] = last[below]
    return depth, parent


def _siblings(parent: np.ndarray):
    """Children of one parent in id order -> (page, its next sibling)
    pairs, and every page's rank among its siblings (0 for the first
    child and for a root)."""
    kids = np.flatnonzero(parent >= 0)
    order = kids[np.argsort(parent[kids], kind="stable")]
    by_parent = parent[order]
    same = by_parent[:-1] == by_parent[1:]
    rank = np.zeros(len(parent), np.int64)
    rank[order] = np.arange(len(order)) - np.searchsorted(
        by_parent, by_parent, side="left")
    return order[:-1][same], order[1:][same], rank


def _near(rng, src, lo, size, alpha):
    """A nearby page of the same host for every ``src``: the gap a
    power law ~ gap^-alpha on [1, size) (alpha 1: log-uniform),
    either way, turned round at the host's ends (a host of one page
    gives the page itself: dropped later as a self-loop)."""
    span = np.maximum(size[src] - 1, 1).astype(np.float64)
    u = rng.random(len(src))
    if alpha == 1.0:
        gap = np.exp(u * np.log(span + 1.0))
    else:
        e = 1.0 - alpha
        gap = (u * ((span + 1.0) ** e - 1.0) + 1.0) ** (1.0 / e)
    gap = np.clip(gap.astype(np.int64), 1, span.astype(np.int64))
    gap = np.where(rng.random(len(src)) < 0.5, gap, -gap)
    dst = src + gap
    out = (dst < lo[src]) | (dst >= lo[src] + size[src])
    dst = np.where(out, src - gap, dst)
    return np.clip(dst, lo[src], lo[src] + size[src] - 1)


def _block_arcs(seed, b, starts, h0, h1, vertices, want, popular_cum,
                popular_host, host_open, p):
    """The block's arcs as sorted keys ``src * vertices + dst``,
    exactly ``want`` of them."""
    rng = np.random.default_rng([int(seed), 1, int(b)])
    base = int(starts[h0])
    n = int(starts[h1]) - base
    sizes = np.diff(starts[h0:h1 + 1])
    lo = np.repeat(starts[h0:h1] - base, sizes)      # local host start
    size = np.repeat(sizes, sizes)
    idx = np.arange(n, dtype=np.int64)
    first = idx == lo

    drawn = 1 + np.minimum(rng.poisson(p["tree_depth_mean"] - 1.0, n),
                           p["tree_depth_max"] - 1)
    # URL order keeps a directory's files together: a page wishes the
    # depth of the page before it, except where a new run starts
    # (runs of ``directory_run`` pages on average)
    fresh = rng.random(n) * p["directory_run"] < 1.0
    fresh[0] = True
    drawn = drawn[np.maximum.accumulate(np.where(fresh, idx, 0))]
    _depth, parent = _tree(drawn, first, p["tree_depth_max"])
    links = rng.random(n) >= p["leaf_share"]
    links |= first                       # a host's first page has links
    child = np.flatnonzero(parent >= 0)
    sib_a, sib_b, rank = _siblings(parent)
    # a LISTING shows its entries page after page, a SERIES: the
    # parent links its first child alone, each child the next and the
    # one before, and only the first links back; a series page has no
    # other links of its own (but its own children's), and a link
    # aimed at one lands on the listing's front page.  No parent links
    # more children than a page has links
    listing = rng.random(n) < p["listing_share"]
    series = np.zeros(n, bool)
    series[child] = listing[parent[child]]
    shown = np.where(series[child], 1, p["out_degree_max"] // 2)
    down = child[rank[child] < shown]
    up = child[~series[child] | (rank[child] == 0)]
    back = series[sib_b]
    t_src = np.concatenate([up, parent[down], sib_a, sib_b[back]])
    t_dst = np.concatenate([parent[up], down, sib_b, sib_a[back]])
    keep = links[t_src]
    tree = np.unique((t_src[keep] + base) * vertices
                     + (t_dst[keep] + base))

    n_popular = len(popular_host)
    pages = np.flatnonzero(links & ~series)
    front = np.where(series, parent, idx)    # where a link to it lands
    # pages of a CLOSED host link to no other host
    opens = np.repeat(host_open[h0:h1], sizes)[pages]
    sigma = p["out_degree_sigma"]
    # links to other hosts hardly ever collide, nearby ones often do:
    # the block's share of them is drawn once, not in every round
    to_others = [int(round(want * p["inter_host_share"]
                           * (1.0 + OVERSHOOT)))]

    def more(count):
        """About ``count`` further link keys: locality links of the
        pages' own, copies of some of them by the pages behind, and
        links to other hosts, in the model's proportions."""
        n_out = min(int(round(count * p["inter_host_share"])),
                    to_others[0]) if opens.any() and n_popular else 0
        to_others[0] -= n_out
        n_copy = int(round((count - n_out) * p["copy_share"]))
        n_own = max(count - n_out - n_copy, 0)
        mean = max(n_own / max(len(pages), 1), 0.05)
        k = rng.lognormal(np.log(mean) - sigma ** 2 / 2, sigma,
                          len(pages)) + rng.random(len(pages))
        k = np.minimum(k.astype(np.int64),
                       np.minimum(p["out_degree_max"],
                                  size[pages] - 1))
        src = np.repeat(pages, k)
        dst = front[_near(rng, src, lo, size, float(p["gap_alpha"]))]
        # copies: a page right behind takes the link over
        pick = rng.integers(0, max(len(src), 1), n_copy) \
            if len(src) else np.zeros(0, np.int64)
        c_src = src[pick] + rng.geometric(0.5, len(pick))
        ok = c_src < lo[src[pick]] + size[src[pick]]
        c_src, c_dst = c_src[ok], dst[pick][ok]
        ok = links[c_src] & ~series[c_src]
        c_src, c_dst = c_src[ok], c_dst[ok]
        # to other hosts, from pages in proportion to their links
        o_src = o_dst = np.zeros(0, np.int64)
        if n_out:
            weight = np.cumsum((k + 1.0) * opens)
            o_src = pages[np.minimum(np.searchsorted(
                weight, rng.random(n_out) * weight[-1]),
                len(pages) - 1)]
            host = popular_host[np.minimum(np.searchsorted(
                popular_cum, rng.random(n_out) * popular_cum[-1]),
                n_popular - 1)]
            deep = rng.random(n_out) < p["deep_link_share"]
            h_size = starts[host + 1] - starts[host]
            o_dst = starts[host] + np.where(
                deep, (rng.random(n_out) * h_size).astype(np.int64), 0)
            o_src = o_src + base
        gsrc = np.concatenate([src + base, c_src + base, o_src])
        gdst = np.concatenate([dst + base, c_dst + base, o_dst])
        ok = gsrc != gdst
        return gsrc[ok] * vertices + gdst[ok]

    if want < len(tree):
        raise ValueError(
            f"{want} arcs asked of a block whose directory trees alone "
            f"have {len(tree)}: too few arcs for this many pages")
    other = np.zeros(0, np.int64)
    short = int((want - len(tree)) * (1.0 + OVERSHOOT)) + 16
    # links a page may have beside its tree links
    room = np.maximum(p["out_degree_max"] - np.bincount(
        tree // vertices - base, minlength=n), 0)
    for _ in range(TOP_UP_ROUNDS):
        other = np.union1d(other, np.setdiff1d(more(short), tree))
        # no page past out_degree_max: of a page's links (sorted by
        # target) the first ``room`` stay
        page = other // vertices - base
        head = np.searchsorted(page, page, side="left")
        other = other[np.arange(len(other)) - head < room[page]]
        short = want - len(tree) - len(other)
        if short <= 0:
            break
        short = int(short * 1.5) + 16
    else:
        raise ValueError(
            f"a block of {n} pages cannot hold {want} distinct arcs")
    drop = len(tree) + len(other) - want
    if drop:
        gone = rng.choice(len(other), drop, replace=False)
        other = np.delete(other, gone)
    return np.union1d(tree, other)


def web_arcs(vertices: int, arcs: int, seed: int, **p):
    """-> (src, dst) int32 [arcs], sorted by (src, dst): the crawl's
    directed arcs, no self-loop, none twice, exactly ``arcs``.
    ``p``: the model's ``PARAMETERS``."""
    vertices, arcs = int(vertices), int(arcs)
    if vertices >= 2 ** 31 or arcs > vertices * (vertices - 1):
        raise ValueError("more arcs than distinct pairs, or ids past "
                         "int32")
    starts = host_starts(vertices, seed, p["host_size_median"],
                         p["host_size_sigma"])
    n_hosts = len(starts) - 1
    rng = np.random.default_rng([int(seed), 2])
    # the bow-tie (Broder et al.): hosts no other host links to (they
    # reach the core, nothing reaches them) and CLOSED hosts, whose
    # pages link to no other host (reached from the core, never back)
    kind = rng.random(n_hosts)
    unlinked = kind < p["unlinked_host_share"]
    host_open = ~((kind >= p["unlinked_host_share"])
                  & (kind < p["unlinked_host_share"]
                     + p["closed_host_share"]))
    popular_host = rng.permutation(np.flatnonzero(~unlinked))  # by rank
    popular_cum = np.cumsum(
        np.arange(1, len(popular_host) + 1, dtype=np.float64)
        ** -float(p["host_popularity_alpha"]))
    src = np.empty(arcs, np.int32)
    dst = np.empty(arcs, np.int32)
    done = 0
    for b, (h0, h1) in enumerate(_blocks(starts)):
        # the block's share of the arcs, by its pages: cumulative
        # rounding, so the shares add up to ``arcs``
        upto = arcs * int(starts[h1]) // vertices
        keys = _block_arcs(seed, b, starts, h0, h1, vertices,
                           upto - done, popular_cum, popular_host,
                           host_open, p)
        src[done:upto] = keys // vertices
        dst[done:upto] = keys % vertices
        done = upto
    assert done == arcs
    return src, dst


def by_destination(src, dst, vertices: int):
    """The reference's form: (offsets int64 [vertices + 1], src int32
    [arcs]) with a destination's in-arcs contiguous, by source (one
    sort of the packed (dst, src) keys)."""
    key = np.asarray(dst).astype(np.uint64)
    key <<= np.uint64(32)
    key |= np.asarray(src).astype(np.uint64)
    key.sort()
    by_src = (key & np.uint64(0xFFFFFFFF)).astype(np.int32)
    del key
    offsets = np.zeros(vertices + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=vertices), out=offsets[1:])
    return offsets, by_src


def largest_components(src, dst, vertices: int):
    """(share of the pages in the largest strongly connected
    component, in the largest weakly connected one), by plain label
    propagation: for small graphs (the rehearsal size)."""
    def reach(frm, to, seed_mask):
        seen = seed_mask.copy()
        while True:
            new = seen.copy()
            new[to[seen[frm]]] = True
            if np.array_equal(new, seen):
                return seen
            seen = new

    label = np.arange(vertices)
    s2 = np.concatenate([src, dst])
    d2 = np.concatenate([dst, src])
    while True:
        new = label.copy()
        np.maximum.at(new, d2, label[s2])
        if np.array_equal(new, label):
            break
        label = new
    weak = np.bincount(label, minlength=vertices).max() / vertices
    # the largest SCC: try the pivots of most in x out degree
    deg = np.bincount(src, minlength=vertices) * \
        np.bincount(dst, minlength=vertices)
    strong = 0
    for pivot in np.argsort(deg)[::-1][:3]:
        mask = np.zeros(vertices, bool)
        mask[pivot] = True
        both = reach(src, dst, mask) & reach(dst, src, mask)
        strong = max(strong, int(both.sum()))
    return strong / vertices, weak


def describe(src, dst, vertices: int, components: bool = False) -> dict:
    """What came out: degrees, the share of reciprocal arcs and, for a
    small graph, the largest components' shares."""
    out_deg = np.bincount(src, minlength=vertices)
    in_deg = np.bincount(dst, minlength=vertices)
    back = dst.astype(np.int64) * vertices + src
    back.sort()
    mutual = 0
    for a in range(0, len(src), 1 << 24):        # bounded temporaries
        fwd = src[a:a + (1 << 24)].astype(np.int64) * vertices \
            + dst[a:a + (1 << 24)]
        pos = np.minimum(np.searchsorted(back, fwd), len(back) - 1)
        mutual += int(np.count_nonzero(back[pos] == fwd))
    info = {
        "vertices": int(vertices), "arcs": int(len(src)),
        "mean_out_degree": float(len(src) / vertices),
        "max_out_degree": int(out_deg.max()),
        "max_in_degree": int(in_deg.max()),
        "no_out_link_share": float(np.mean(out_deg == 0)),
        "no_in_link_share": float(np.mean(in_deg == 0)),
        "reciprocal_arc_share": float(mutual / max(len(src), 1)),
    }
    if components:
        strong, weak = largest_components(src, dst, vertices)
        info["largest_scc_share"] = float(strong)
        info["largest_wcc_share"] = float(weak)
    return info
