"""A rating matrix of the NetFlix prize set's SHAPE, from a seed.

The prize set (480,189 users x 17,770 titles, 100,480,507 ratings of
1..5) cannot be distributed, so the deployment's data is generated to
its shape: endpoint popularity ~ rank^-skew on both sides (inverse CDF
over the ranks), every (user, item) pair at most once, EXACTLY
``ratings`` pairs, integer ratings from the public marginal.  Upstream
Lux stores each rating in both directions (its README's table counts
200,961,014 edges = 2 x 100,480,507), and so does ``stored_edges``.

Plain NumPy, the benchmark's own: nothing of ``lux_tpu`` is imported.
"""

from __future__ import annotations

import numpy as np

MARGINAL = (0.05, 0.10, 0.23, 0.34, 0.28)      # P(rating = 1..5)
DRAW_CHUNK = 1 << 24


def _draw_ranks(rng, n: int, skew: float, count: int) -> np.ndarray:
    """``count`` ranks in [0, n), P(rank r) ~ (r + 1)^-skew."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -skew)
    cdf /= cdf[-1]
    out = np.empty(count, dtype=np.uint64)
    for lo in range(0, count, DRAW_CHUNK):      # bounds the float64 draws
        hi = min(lo + DRAW_CHUNK, count)
        out[lo:hi] = np.minimum(
            np.searchsorted(cdf, rng.random(hi - lo)), n - 1)
    return out


def _draw_keys(rng, users, items, user_skew, item_skew, count):
    """``count`` pair keys ``user * items + item`` in draw order."""
    u = _draw_ranks(rng, users, user_skew, count)
    u *= np.uint64(items)
    u += _draw_ranks(rng, items, item_skew, count)
    return u


def _first_new(keys, have_sorted, want: int):
    """The first ``want`` keys of ``keys`` (in draw order) that are
    neither in ``have_sorted`` nor earlier in ``keys``."""
    uniq, first = np.unique(keys, return_index=True)
    if len(have_sorted):
        at = np.minimum(np.searchsorted(have_sorted, uniq),
                        len(have_sorted) - 1)
        fresh = have_sorted[at] != uniq
        uniq, first = uniq[fresh], first[fresh]
    order = np.argsort(first, kind="stable")[:want]
    return uniq[order]


def rating_pairs(users: int, items: int, ratings: int, seed: int,
                 user_skew: float, item_skew: float,
                 marginal=MARGINAL):
    """-> (user uint32 [ratings], item uint32 [ratings] in [0, items),
    rating uint8 [ratings] in 1..5): exactly ``ratings`` unique
    (user, item) pairs, sorted by (user, item).  Pairs are drawn, made
    unique, and the shortfall drawn again until the count stands."""
    if ratings > users * items:
        raise ValueError("more ratings than (user, item) cells")
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed), users, items, ratings]))
    base = np.unique(_draw_keys(rng, users, items, user_skew,
                                item_skew, ratings))
    extra = np.empty(0, dtype=np.uint64)        # sorted, disjoint of base
    while len(base) + len(extra) < ratings:
        need = ratings - len(base) - len(extra)
        # a little more than the shortfall, so the rounds shrink fast
        cand = _draw_keys(rng, users, items, user_skew, item_skew,
                          need + need // 8 + 1024)
        cand = _first_new(cand, base, len(cand))
        cand = _first_new(cand, extra, need)
        extra = np.sort(np.concatenate([extra, cand]))
    keys = base if not len(extra) else np.sort(
        np.concatenate([base, extra]))
    del base, extra
    user = (keys // np.uint64(items)).astype(np.uint32)
    item = (keys % np.uint64(items)).astype(np.uint32)
    del keys
    rating = rng.choice(np.arange(1, 6, dtype=np.uint8), size=ratings,
                        p=list(marginal))
    return user, item, rating


def both_directions(user, item, rating, users: int):
    """The stored directed edges: users are vertices [0, users), items
    [users, users + items); user -> item and item -> user carry the
    same rating.  -> (src, dst uint32 [2R], weight int32 [2R])."""
    iv = item + np.uint32(users)
    return (np.concatenate([user, iv]), np.concatenate([iv, user]),
            np.concatenate([rating, rating]).astype(np.int32))


def by_destination(user, item, rating, users: int, items: int):
    """The reference's own arrays: the stored edges sorted by
    destination.  -> (offsets int64 [nv + 1], src uint32 [2R], rating
    uint8 [2R]).  Destinations [0, users) are the pairs as they stand
    (sorted by user); destinations [users, nv) are the pairs sorted by
    item."""
    nv = users + items
    by_item = np.argsort(item, kind="stable")
    src = np.concatenate([item + np.uint32(users), user[by_item]])
    rat = np.concatenate([rating, rating[by_item]])
    counts = np.concatenate([np.bincount(user, minlength=users),
                             np.bincount(item, minlength=items)])
    offsets = np.zeros(nv + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    return offsets, src, rat
