"""Pair-lane delivery: gather-free edge values for dense tile pairs.

Measured fact (PERF_NOTES.md): the XLA gather costs ~9 ns per ROW
fetched, independent of row width.  So edges in a dense (src-tile,
dst-tile) pair — both tiles 128 vertices — can all be served by
fetching the pair's 128-wide source state row ONCE per pair-row:
lane = source offset within the src tile, so the value needs no
selection at all; the existing chunk-partial compare-reduce routes it
to its destination offset (``rel_dst``).

Under a degree-sorted vertex numbering (hubs share tiles), pairs with
>= 8 edges cover ~74% of RMAT edges at ~6x lane inflation — ~3 ns/edge
total against 9 ns for the per-edge gather.  The residual sparse-pair
edges keep the regular gather path.

Row layout: pair (s, t) with maximum per-source multiplicity m gets m
rows; occurrence o of source lane c carries the o-th edge (s*128+c ->
t*128+rel).  Unused lanes carry rel = -1 (matches no lane; int8).
Rows are grouped per destination tile and depth-classed so the
cross-row combine is a static reshape-reduce (the slotted classes of the
routing study PERF_NOTES.md closed).

Reference analogue: the CTA-shared staging of hub vertices in the
reference's GPU kernels (reference colfilter_gpu.cu:41-102 stages a
tile of destination state in shared memory; reference
pull_model.inl:454-461 materializes the whole remote region) — here
the "shared tile" is the 128-lane vector register shape itself.
"""

from __future__ import annotations

import dataclasses

import numpy as np

W = 128


@dataclasses.dataclass
class PairPlan:
    """Per-part pair-lane arrays (host numpy).

    rowbind   int32 [R]      global state2d row (= src tile) per row
    rel_dst   int8 [R, 128] dst offset in [0,128), -1 = dead lane
    weight    f32 [R, 128] | None  per-lane edge weight (0 dead lanes)
    classes   [(tile_start, tile_count, depth)] for the combine; rows
              are tile-major in ``tile_order`` with per-tile depth
              padded to the class depth (dead rows are all -1)
    tile_order int32 [n_tiles] part-local dst tile of each class slot
    residual  bool [ne_part]  True for edges NOT covered by pairs
    """

    rowbind: np.ndarray
    rel_dst: np.ndarray
    weight: np.ndarray | None
    classes: list
    tile_order: np.ndarray
    residual: np.ndarray
    n_tiles: int
    stats: dict
    # part-local dst tile of each row (pair_partial_dot fetches the
    # row's destination tile block for the <src, dst> MXU dots)
    row_tile: np.ndarray | None = None


def occurrence_index(pair: np.ndarray, slot: np.ndarray) -> np.ndarray:
    """Per-element occurrence counter within each (pair, slot) group:
    the o-th edge of a (pair id, source slot) pair gets o (any order).

    Overflow-safe: pair ids reach ~(num_state_rows * n_tiles), which
    passes 2^31 at RMAT25/np4 — a packed ``pair * 2^32 + slot`` key
    silently wraps mod 2^64 there, aliasing distinct groups and
    DROPPING the aliased edges at delivery time (two edges written to
    one (row, lane)).  Two stable FUSED radix passes (lexsort
    semantics: slot minor, pair major; native.sort_kv carries the
    companion key and the edge index as payloads — no argsort
    permutation reads, no post-sort gathers) never form a product."""
    from lux_tpu import native

    n = len(slot)
    # unconditional copies: sort_kv permutes IN PLACE and callers
    # keep using their arrays
    ks = np.array(slot, dtype=np.int64)
    kp = np.array(pair, dtype=np.int64)
    idx = np.arange(n, dtype=np.int64)
    native.sort_kv(ks, (kp, idx))        # stable by slot
    native.sort_kv(kp, (ks, idx))        # then stable by pair
    newg = np.ones(n, bool)
    newg[1:] = (kp[1:] != kp[:-1]) | (ks[1:] != ks[:-1])
    del kp, ks              # 16 B an edge, before 24 more are made
    pos = np.arange(n)
    gst = np.maximum.accumulate(np.where(newg, pos, 0))
    occ = np.empty(n, np.int64)
    occ[idx] = pos - gst
    return occ


def fill_histogram(pidx: np.ndarray, occ: np.ndarray):
    """Per-(pair, occurrence-level) fill counts, sorted by (pair,
    occ): returns (gp, go, fill) — pair id, occ level and the number
    of edges at that level (= the live-lane count of that pair row).
    The pack is safe: dense pidx < n_cov < 2^31, occ < max_occ.
    Shared by the min_fill cap (analyze_pairs) and the economics
    model (scripts/pair_fill_hist.py), so the modeled drop is exactly
    the planner's."""
    from lux_tpu import native

    key = (np.asarray(pidx, np.int64) << np.int64(32)) | occ
    native.sort_kv(key, ())
    newg = np.ones(len(key), bool)
    newg[1:] = key[1:] != key[:-1]
    gidx = np.nonzero(newg)[0]
    fill = np.diff(np.concatenate((gidx, [len(key)])))
    gp = (key[gidx] >> np.int64(32)).astype(np.int64)
    go = (key[gidx] & np.int64(0xFFFFFFFF)).astype(np.int64)
    return gp, go, fill


def quantize_depths(depth_sorted: np.ndarray,
                    levels_growth: float = 1.35) -> np.ndarray:
    """Round a descending per-slot row-count profile up to the fixed
    depth ladder (0..8 then *levels_growth), bounding the number of
    distinct classes to O(log max_depth)."""
    levels = [0, 1, 2, 3, 4, 5, 6, 7, 8]
    v = 8
    while v < int(np.max(depth_sorted, initial=0)):
        v = int(v * levels_growth) + 1
        levels.append(v)
    lev = np.asarray(levels, np.int64)
    return lev[np.searchsorted(lev, depth_sorted)]


@dataclasses.dataclass
class PairAnalysis:
    """The threshold-dependent (but layout-independent) half of pair
    planning: everything through the sorted per-tile depth profile.
    plan_sharded_pairs computes it ONCE per part and reuses it for
    both the profile pass and the final layout — at billion-edge
    scale the analysis is several argsorts of the whole edge list,
    previously paid twice (round-4 host-prep work)."""

    ne: int
    n_tiles: int
    residual: np.ndarray       # bool [ne]
    cov: np.ndarray            # int32 [n_cov] covered edge idx
    occ: np.ndarray            # int32 [n_cov] occurrence in (pair,slot)
    pidx: np.ndarray           # int32 [n_cov] dense selected-pair id
    nrows_pair: np.ndarray     # int64 [n_sel]
    pair_dt: np.ndarray        # int64 [n_sel] dst tile of each pair
    tile_sort: np.ndarray      # int64 [n_sel]
    t_order: np.ndarray        # int64 [n_tiles]
    depth_sorted: np.ndarray   # int64 [n_tiles] descending
    # NOTE: src_slot/dst_local are deliberately NOT stored —
    # plan_sharded_pairs holds every part's analysis simultaneously,
    # and int64 copies of the edge arrays would cost tens of GB at
    # billion-edge scale (build_pair_plan re-derives them from its
    # own parameters); cov/occ/pidx are int32 (epad < 2^31 is a
    # ShardedGraph.build invariant)


def resolve_min_fill(min_fill, kdim: int = 1) -> int | None:
    """The K-aware half of the min_fill economics: ``"auto"`` resolves
    to the modeled break-even fill for ``kdim``-wide rows
    (scalemodel.break_even_fill — row cost grows with K, so K-dim rows
    must be fuller to beat the residual: ~16 scalar, ~22 at K=20).
    Integers and None pass through unchanged."""
    if min_fill == "auto":
        from lux_tpu.scalemodel import break_even_fill
        return break_even_fill(kdim)
    if min_fill is not None and not isinstance(min_fill, (int,
                                                          np.integer)):
        raise ValueError(f"min_fill must be an int, None or 'auto', "
                         f"got {min_fill!r}")
    return min_fill


def analyze_pairs(src_slot: np.ndarray, dst_local: np.ndarray,
                  vpad: int, threshold: int = 8,
                  max_occ: int = 128,
                  min_fill: int | str | None = None,
                  kdim: int = 1) -> PairAnalysis:
    """See build_pair_plan; this is its sorting/selection half.

    min_fill (occupancy-aware row packing, round-5 north-star work):
    drop pair rows that would deliver fewer than ``min_fill`` live
    lanes, sending their edges to the residual path instead.  Row
    fill is MONOTONE DECREASING in occurrence depth within a pair
    (row o carries one edge per source lane with multiplicity > o),
    so the underfilled rows are exactly each pair's occurrence TAIL —
    the drop is a per-pair adaptive occurrence cap, computed from one
    (pidx, occ) histogram.  The break-even fill is the measured
    per-row delivery cost over the residual per-edge rate
    (~150 / ~10 ns, PERF_NOTES scale-25 decomposition) ~ 15 lanes;
    R-MAT tails spread multiplicity so hard that mean fill at RMAT25
    is 18.6 (inflation 6.88x) with a long sub-break-even tail.

    min_fill="auto" resolves to the K-aware modeled break-even for
    ``kdim``-wide rows (resolve_min_fill): SDDMM delivery rows
    (pair_partial_dot*) cost more per row than scalar rows, so their
    break-even fill is higher (~22 at K=20 vs ~16 scalar)."""
    min_fill = resolve_min_fill(min_fill, kdim)
    assert vpad % W == 0
    ne = len(dst_local)
    n_tiles = vpad // W
    src_slot = np.asarray(src_slot, np.int64)
    dst_local = np.asarray(dst_local, np.int64)

    # every [ne] int64 temporary below is 1.6 GB at 2e8 edges (the
    # NetFlix shape: 33 GB of them at once reached the one-chip
    # machine's 40 GiB): each is dropped where its last reader is
    pair = (src_slot // W) * n_tiles
    pair += dst_local // W
    del dst_local
    # fused radix sort carrying the edge index: replaces argsort +
    # key gather on the whole edge list (native.sort_kv, PERF_NOTES
    # round-4 host prep)
    pp = pair.copy()
    order = np.arange(ne, dtype=np.int64)
    from lux_tpu import native
    native.sort_kv(pp, (order,))
    # a part with zero edges has zero pairs (starts must then be [0],
    # not [0, 0], so the pp[starts[:-1]] lookups below stay in bounds)
    starts = (np.concatenate(
        ([0], np.nonzero(pp[1:] != pp[:-1])[0] + 1, [ne]))
        if ne else np.zeros(1, np.int64))
    sizes = np.diff(starts)
    pair_keys = pp[starts[:-1]]                   # sorted unique keys
    del pp

    sel_pair = sizes >= threshold
    # in pair-sorted order
    esel_sorted = np.repeat(sel_pair, sizes)
    # original edge idx of each covered edge
    cov = order[esel_sorted]
    del order, esel_sorted
    residual = np.ones(ne, bool)
    residual[cov] = False

    # occurrence index of each covered edge within (pair, src lane)
    occ = occurrence_index(pair[cov], src_slot[cov])

    # Optional occurrence-depth cap (edges beyond it ride the residual
    # gather).  Measured on RMAT21: capping LOSES — deep-occurrence
    # rows belong to hub pairs and are well-filled, so the default
    # effectively disables the cap.
    keep = occ < max_occ
    if not keep.all():
        # mark dropped edges residual; rebuild cov/occ on the kept set
        residual[cov[~keep]] = True
        cov = cov[keep]
        occ = occurrence_index(pair[cov], src_slot[cov])

    # per-pair row count = max occurrence + 1 (pair ids of the
    # possibly-reduced covered set, via the sorted unique pair keys)
    pid_cov = np.searchsorted(pair_keys, pair[cov])
    del pair
    # remap selected pair ids to dense [0, P)
    sel_ids = np.nonzero(sel_pair)[0]
    remap = np.full(len(sizes), -1, np.int64)
    remap[sel_ids] = np.arange(len(sel_ids))
    pidx = remap[pid_cov]                         # [n_cov]

    if min_fill is not None and min_fill > 1 and len(cov):
        # fill of row (pair, o) = #edges at occurrence o in the pair;
        # monotone decreasing in o, so the per-pair cap is the count
        # of leading occurrence levels with fill >= min_fill
        gp, go, fill = fill_histogram(pidx, occ)
        # leading run of occ levels with fill >= min_fill per pair:
        # occ levels are contiguous from 0 (groups sorted by occ), so
        # the cap is the first level that is absent or underfilled
        ok = fill >= min_fill
        run = np.zeros(len(sel_ids), np.int64)
        # count o where (pair, o) ok AND all o' < o ok: prefix-and via
        # cummax of the first failure position
        firstbad = np.full(len(sel_ids), np.iinfo(np.int64).max)
        np.minimum.at(firstbad, gp[~ok], go[~ok])
        np.maximum.at(run, gp[ok],
                      np.minimum(go[ok] + 1, firstbad[gp[ok]]))
        cap = run                                  # rows kept per pair
        keep2 = occ < cap[pidx]
        if not keep2.all():
            residual[cov[~keep2]] = True
            cov = cov[keep2]
            pidx = pidx[keep2]
            occ = occ[keep2]   # no holes: kept occ stay < cap

    nrows_pair = np.zeros(len(sel_ids), np.int64)
    if len(cov):
        np.maximum.at(nrows_pair, pidx, occ + 1)

    # order pairs by dst tile (for the per-tile combine), then src tile
    pair_dt = (pair_keys[sel_pair] % n_tiles)
    tile_sort = np.argsort(pair_dt, kind="stable")
    # per-tile total rows -> depth classes
    rows_by_tile = np.zeros(n_tiles, np.int64)
    np.add.at(rows_by_tile, pair_dt, nrows_pair)
    t_order = np.argsort(-rows_by_tile, kind="stable")
    depth_sorted = rows_by_tile[t_order]
    return PairAnalysis(
        ne=ne, n_tiles=n_tiles, residual=residual,
        cov=cov.astype(np.int32), occ=occ.astype(np.int32),
        pidx=pidx.astype(np.int32),
        nrows_pair=nrows_pair, pair_dt=pair_dt, tile_sort=tile_sort,
        t_order=t_order, depth_sorted=depth_sorted)


def build_pair_plan(src_slot: np.ndarray, dst_local: np.ndarray,
                    vpad: int, threshold: int = 8,
                    max_occ: int = 128,
                    levels_growth: float = 1.35,
                    weights: np.ndarray | None = None,
                    slot_depths: np.ndarray | None = None,
                    analysis: PairAnalysis | None = None,
                    min_fill: int | str | None = None,
                    kdim: int = 1):
    """src_slot: int [ne] global padded state slots (state2d row =
    slot // 128); dst_local: int [ne] part-local dst in [0, vpad);
    vpad must be a multiple of 128.  weights (optional, [ne]) are laid
    out per lane so weighted programs get each delivered edge's weight
    next to its value.

    slot_depths (optional, [n_tiles] descending, ladder-quantized):
    lay rows out against this EXTERNAL per-slot depth profile instead
    of the part's own — every part of a multi-part graph laid out
    against the elementwise-max profile gets IDENTICAL classes, so
    stacking pads no rows beyond the max profile (see
    plan_sharded_pairs).

    analysis: a precomputed analyze_pairs result for these arrays
    (must match threshold/max_occ/min_fill) — skips the sorting
    half.  min_fill/kdim: see analyze_pairs."""
    if analysis is None:
        analysis = analyze_pairs(src_slot, dst_local, vpad,
                                 threshold=threshold, max_occ=max_occ,
                                 min_fill=min_fill, kdim=kdim)
    a = analysis
    ne, n_tiles = a.ne, a.n_tiles
    src_slot = np.asarray(src_slot, np.int64)
    dst_local = np.asarray(dst_local, np.int64)
    residual, cov, occ, pidx = a.residual, a.cov, a.occ, a.pidx
    nrows_pair, pair_dt = a.nrows_pair, a.pair_dt
    tile_sort, t_order, depth_sorted = (a.tile_sort, a.t_order,
                                        a.depth_sorted)

    if slot_depths is None:
        depth = quantize_depths(depth_sorted, levels_growth)
    else:
        depth = np.asarray(slot_depths, np.int64)
        if depth.shape != (n_tiles,) or (depth < depth_sorted).any():
            raise ValueError("slot_depths must cover this part's own "
                             "sorted per-tile row counts")

    row_off_tile = np.concatenate(([0], np.cumsum(depth)))
    R = int(row_off_tile[-1])

    # rows of each pair: base = tile's offset + exclusive running row
    # count within the tile (pairs in tile_sort order are contiguous
    # per destination tile)
    tile_pos = np.empty(n_tiles, np.int64)        # tile -> class slot
    tile_pos[t_order] = np.arange(n_tiles)
    srt_rows = nrows_pair[tile_sort]
    cum = np.cumsum(srt_rows) - srt_rows          # exclusive prefix
    dts = pair_dt[tile_sort]
    newt = np.ones(len(dts), bool)
    newt[1:] = dts[1:] != dts[:-1]
    grp_base = np.maximum.accumulate(np.where(newt, cum, 0))
    within = cum - grp_base
    pair_base = np.zeros(len(nrows_pair), np.int64)
    pair_base[tile_sort] = row_off_tile[tile_pos[dts]] + within
    assert (within + srt_rows <= depth[tile_pos[dts]]).all()

    rowbind = np.zeros(R, np.int32)
    rel_dst = np.full((R, W), -1, np.int8)
    rows = pair_base[pidx] + occ
    rowbind_rows = (src_slot[cov] // W).astype(np.int32)
    rowbind[rows] = rowbind_rows
    rel_dst[rows, src_slot[cov] % W] = (dst_local[cov] % W).astype(
        np.int8)
    # every covered edge must own a distinct (row, lane) — a colliding
    # write means a planner bug silently DROPPED an edge (the int64
    # occurrence-key wrap at RMAT25/np4 scale did exactly that before
    # occurrence_index); count the delivered lanes, loudly
    delivered = int(np.count_nonzero(rel_dst != -1))
    if delivered != len(cov):
        raise AssertionError(
            f"pair plan dropped {len(cov) - delivered} of {len(cov)} "
            f"covered edges (colliding (row, lane) writes)")
    weight = None
    if weights is not None:
        weight = np.zeros((R, W), np.float32)
        weight[rows, src_slot[cov] % W] = np.asarray(
            weights, np.float32)[cov]

    classes = []
    t0 = 0
    for L in np.unique(depth)[::-1]:
        cnt = int((depth == L).sum())
        if L > 0:
            classes.append((t0, cnt, int(L)))
        t0 += cnt

    # slot s owns depth[s] rows for tile t_order[s], in slot order
    row_tile = np.repeat(t_order.astype(np.int32), depth)

    plan = PairPlan(rowbind=rowbind, rel_dst=rel_dst, weight=weight,
                    classes=classes,
                    tile_order=t_order.astype(np.int32),
                    residual=residual, n_tiles=n_tiles, stats={},
                    row_tile=row_tile)
    ncov = int((~residual).sum())
    plan.stats = dict(ne=ne, covered=ncov, R=R,
                      coverage=ncov / max(ne, 1),
                      inflation=R * W / max(ncov, 1),
                      depth_profile=depth_sorted)
    return plan


def pair_reduce_numpy(plan: PairPlan, state_flat: np.ndarray,
                      kind: str = "sum") -> np.ndarray:
    """Oracle: run the pair-lane delivery + reduce on host.
    Returns [vpad] partial reduction (identity where uncovered)."""
    s2d = np.asarray(state_flat).reshape(-1, W)
    vals = s2d[plan.rowbind]                       # [R, 128]
    ident = {"sum": 0.0, "min": np.inf, "max": -np.inf}[kind]
    op = {"sum": np.add, "min": np.minimum, "max": np.maximum}[kind]
    vpad = plan.n_tiles * W
    out = np.full(vpad, ident)
    # per-row compare-reduce + per-tile combine
    row0 = 0
    for (t0, cnt, L) in plan.classes:
        for i in range(cnt):
            tile = plan.tile_order[t0 + i]
            for r in range(row0 + i * L, row0 + (i + 1) * L):
                lanes = plan.rel_dst[r]
                for c in range(W):
                    w = int(lanes[c])   # int8 + python-int arithmetic
                    if 0 <= w < W:
                        out[tile * W + w] = op(out[tile * W + w],
                                               vals[r, c])
        row0 += cnt * L
    return out


# ---------------------------------------------------------------------
# Stacked (multi-part) plans: the per-part PairPlans are padded to ONE
# common class structure so they stack into rectangular [P, ...] arrays
# that vmap over parts and shard over a mesh axis exactly like the rest
# of the graph arrays.  The analogue of the reference running the same
# per-part app task on every partition of the gathered whole-state
# region (reference pull_model.inl:454-469).
# ---------------------------------------------------------------------


@dataclasses.dataclass
class StackedPairPlan:
    """Common-frame pair-lane arrays for all parts (host numpy).

    rowbind   int32 [P, Rp]       global state2d row per delivery row
    rel_dst   int8 [P, Rp, 128]  dst offset in [0,128), -1 = dead
    weight    f32 [P, Rp, 128] | None  per-lane edge weight
    tile_pos  int32 [P, n_tiles]  class slot of each part-local tile;
              tiles with no pair rows point at the trailing identity
              slot ``n_slots``
    classes   [(count, depth)] shared by every part, depth descending;
              a part with fewer tiles at some depth owns dead rows
              there (all-128 rel), which reduce to the identity and
              are never referenced by its tile_pos
    """

    rowbind: np.ndarray
    rel_dst: np.ndarray
    weight: np.ndarray | None
    tile_pos: np.ndarray
    classes: list
    n_tiles: int
    n_slots: int
    R: int
    Rp: int
    stats: dict
    row_tile: np.ndarray | None = None  # int32 [P, Rp], dead rows -> 0


def stack_pair_plans(plans: list, weighted: bool,
                     block_rows: int = 64) -> StackedPairPlan:
    """Pad per-part plans to a common class structure and stack.

    The depth ladder in build_pair_plan is a prefix of one fixed
    sequence, so per-part class depths are subsets of a common
    descending depth list; the common count per depth is the max over
    parts.  Rows are padded to ``block_rows`` granularity for the
    Pallas chunk-partial kernel.
    """
    P = len(plans)
    n_tiles = plans[0].n_tiles
    depths = sorted({L for pl in plans for (_t0, _c, L) in pl.classes},
                    reverse=True)
    cnt_by_depth = {
        L: max((c for pl in plans for (_t0, c, Ld) in pl.classes
                if Ld == L), default=0)
        for L in depths}
    classes = [(cnt_by_depth[L], L) for L in depths]
    n_slots = sum(c for c, _L in classes)
    R = sum(c * L for c, L in classes)
    Rp = max(R, block_rows)
    Rp = -(-Rp // block_rows) * block_rows

    slot_base, row_base = {}, {}
    s = r = 0
    for c, L in classes:
        slot_base[L], row_base[L] = s, r
        s += c
        r += c * L

    rowbind = np.zeros((P, Rp), np.int32)
    rel_dst = np.full((P, Rp, W), -1, np.int8)
    wgt = np.zeros((P, Rp, W), np.float32) if weighted else None
    tile_pos = np.full((P, n_tiles), n_slots, np.int32)
    row_tile = np.zeros((P, Rp), np.int32)
    for p, pl in enumerate(plans):
        prow = 0
        for (t0, c, L) in pl.classes:
            rb, sb = row_base[L], slot_base[L]
            rowbind[p, rb:rb + c * L] = pl.rowbind[prow:prow + c * L]
            rel_dst[p, rb:rb + c * L] = pl.rel_dst[prow:prow + c * L]
            if weighted:
                wgt[p, rb:rb + c * L] = pl.weight[prow:prow + c * L]
            row_tile[p, rb:rb + c * L] = pl.row_tile[prow:prow + c * L]
            tiles = pl.tile_order[t0:t0 + c]
            tile_pos[p, tiles] = sb + np.arange(c, dtype=np.int32)
            prow += c * L

    ne = sum(pl.stats["ne"] for pl in plans)
    cov = sum(pl.stats["covered"] for pl in plans)
    return StackedPairPlan(
        rowbind=rowbind, rel_dst=rel_dst, weight=wgt, tile_pos=tile_pos,
        classes=classes, n_tiles=n_tiles, n_slots=n_slots, R=R, Rp=Rp,
        stats=dict(ne=ne, covered=cov, coverage=cov / max(ne, 1),
                   inflation=P * Rp * W / max(cov, 1)),
        row_tile=row_tile)


def cost_balanced_starts(g, num_parts: int, threshold: int,
                         gather_cost: float = 9.0,
                         pair_cost: float = 2.5) -> np.ndarray:
    """Partition cut points balancing ESTIMATED per-part iteration
    cost under pair-lane delivery, instead of raw edge counts.

    Edge-balanced cuts leave the tail-destination parts with nearly
    all the residual (gather-served, ~9 ns) edges while hub parts'
    edges ride cheap pair rows — measured 0.8M..5.9M residual skew at
    RMAT21/np=4.  Cost model: an edge in a dense GLOBAL (src-tile,
    dst-tile) pair costs ``pair_cost`` ns, any other ``gather_cost``
    ns (PERF_NOTES.md).  Cuts are 128-aligned so part-local tile
    structure equals the global tiling and the estimate is exact.
    """
    from lux_tpu.partition import weighted_balanced_bounds

    src, dst = g.edge_arrays()
    n_st = (g.nv + W - 1) // W
    key = (src // W) * np.int64(n_st) + dst // W
    uniq, inv, cnt = np.unique(key, return_inverse=True,
                               return_counts=True)
    edge_cost = np.where(cnt[inv] >= threshold, pair_cost, gather_cost)
    ccum = np.concatenate(([0.0], np.cumsum(edge_cost)))
    cost_ptrs = ccum[np.asarray(g.row_ptrs, np.int64)]  # END offsets
    return weighted_balanced_bounds(cost_ptrs, num_parts, align=W)


def plan_sharded_pairs(sg, threshold: int,
                       min_fill: int | str | None = None,
                       kdim: int = 1):
    """``_plan_sharded_pairs`` under one ``build.pair_plan`` span
    (telemetry.span) whose counts say what the plan covers:
    ``pair_edges`` (edges served by pair rows) and ``residual_edges``
    (left to the gather path).

    A layout that carries a ``content_key`` (ShardedGraph.build) goes
    through the preparation store (lux_tpu/prepstore.py) under that
    key and ``threshold``, the resolved ``min_fill`` and ``kdim``: a
    hit loads plan and residual, the miss's arrays byte for byte.
    Multi-host local-parts builds stay away (the planner all-reduces
    a profile, so every process would have to agree on hit or
    miss)."""
    from lux_tpu import prepstore, telemetry

    with telemetry.span("build.pair_plan") as span:
        min_fill = resolve_min_fill(min_fill, kdim)
        key = None
        if sg.content_key is not None and sg.local_parts is None:
            key = prepstore.derive(sg.content_key, "pair_plan",
                                   threshold, min_fill, kdim)
        sp, residual = prepstore.through(
            "pair_plan", key,
            lambda: _plan_sharded_pairs(sg, threshold, min_fill, kdim),
            _plan_pack, lambda a, meta: _plan_unpack(sg, a, meta))
        if key is not None and sp is not None:
            # the residual is a layout of its own: its sparse view is
            # not the full layout's
            residual.content_key = prepstore.derive(key, "residual")
        covered = 0 if sp is None else int(sp.stats["covered"])
        # lanes the pair rows offer (128 a delivery row, every part):
        # pair_edges over pair_lanes is the rows' fill
        lanes = 0 if sp is None else W * sp.R * sp.rowbind.shape[0]
        span.count(pair_edges=covered,
                   residual_edges=int(np.sum(residual.ne_part)),
                   pair_lanes=lanes)
    return sp, residual


# the RESIDUAL layout's fields that _plan_sharded_pairs replaces
_RESIDUAL_ARRAYS = ("src_slot", "dst_local", "edge_weight",
                    "row_ptr_local", "ne_part")
_PLAN_ARRAYS = ("rowbind", "rel_dst", "weight", "tile_pos", "row_tile")
_PLAN_SCALARS = ("n_tiles", "n_slots", "R", "Rp")


def _plan_pack(product):
    """(arrays, meta) of a planner result for the store; the "no pair
    anywhere" outcome is an entry without arrays."""
    sp, residual = product
    if sp is None:
        return {}, {"none": True}
    arrays = {n: getattr(sp, n) for n in _PLAN_ARRAYS}
    arrays.update({"res_" + n: getattr(residual, n)
                   for n in _RESIDUAL_ARRAYS})
    meta = {n: int(getattr(sp, n)) for n in _PLAN_SCALARS}
    meta.update(classes=[[int(c), int(L)] for c, L in sp.classes],
                stats=sp.stats, epad=int(residual.epad))
    return arrays, meta


def _plan_unpack(sg, arrays, meta):
    if meta.get("none"):
        return None, sg
    sp = StackedPairPlan(
        classes=[(c, L) for c, L in meta["classes"]],
        stats=meta["stats"],
        **{n: arrays.get(n) for n in _PLAN_ARRAYS},
        **{n: meta[n] for n in _PLAN_SCALARS})
    residual = dataclasses.replace(
        sg, epad=meta["epad"], _src_sorted_cache=None,
        **{n: arrays.get("res_" + n) for n in _RESIDUAL_ARRAYS})
    return sp, residual


def _plan_sharded_pairs(sg, threshold: int, min_fill, kdim: int):
    """Build per-part pair plans for a ShardedGraph and the RESIDUAL
    ShardedGraph (uncovered edges, re-padded) the regular gather path
    should run on.  Returns (StackedPairPlan | None, residual_sg);
    None when no pair anywhere meets the threshold (residual is ``sg``
    itself).  Works for any num_parts; requires vpad % 128 == 0
    (build the ShardedGraph with vpad_align=128).

    Multi-host local-parts builds (sg.local_parts set): each process
    plans only its OWN rows, but against a process-group-allreduced
    common depth profile (multihost.allreduce_host — the s_pad-style
    agreement push uses, push.py), so every process compiles the SAME
    class structure and row shapes.

    ``min_fill`` arrives RESOLVED (plan_sharded_pairs: "auto" + kdim
    becomes the K-aware break-even fill ONCE, so every part — and
    every process — caps on the same fill; see resolve_min_fill)."""
    if sg.vpad % W:
        raise ValueError("pair delivery needs vpad % 128 == 0; build "
                         "the ShardedGraph with vpad_align=128")
    P = sg.num_parts
    rows = sg.part_ids()          # global part id per materialized row
    R = len(rows)
    local = sg.local_parts is not None

    def plan_row(r, slot_depths=None, analysis=None):
        nep = int(sg.ne_part[rows[r]])
        wp = (np.asarray(sg.edge_weight[r, :nep])
              if sg.weighted else None)
        return build_pair_plan(
            sg.src_slot[r, :nep], sg.dst_local[r, :nep], sg.vpad,
            threshold=threshold, weights=wp, slot_depths=slot_depths,
            analysis=analysis, min_fill=min_fill)

    if P > 1 or local:
        # Pass 1: per-part analyses (the expensive sorting half, done
        # ONCE and reused by the layout pass) yield sorted row-count
        # profiles.  Pass 2: lay every part out against the
        # elementwise-max profile so classes are IDENTICAL across
        # parts (and processes) and stacking pads no rows beyond the
        # max profile.  (Per-depth max-count stacking of heterogeneous
        # profiles measured 3.4x row inflation at RMAT21/np=4.)
        analyses = []
        for r in range(R):
            nep = int(sg.ne_part[rows[r]])
            analyses.append(analyze_pairs(
                sg.src_slot[r, :nep], sg.dst_local[r, :nep], sg.vpad,
                threshold=threshold, min_fill=min_fill))
        prof_max = (np.maximum.reduce(
            [a.depth_sorted for a in analyses]) if analyses
            else np.zeros(sg.vpad // W, np.int64))
        total = sum(int(a.depth_sorted.sum()) for a in analyses)
        if local:
            from lux_tpu.parallel.multihost import allreduce_host
            prof_max = allreduce_host(prof_max, "max")
            total = int(allreduce_host(np.int64(total), "sum"))
        if total == 0:
            return None, sg             # no pair anywhere dense enough
        common = quantize_depths(prof_max)
        plans = []
        for r in range(R):
            plans.append(plan_row(r, slot_depths=common,
                                  analysis=analyses[r]))
            analyses[r] = None          # release the per-part arrays
    else:
        plans = [plan_row(0)]
        if plans[0].stats["covered"] == 0:
            return None, sg

    sp = stack_pair_plans(plans, sg.weighted)

    ne_r = [int(pl.residual.sum()) for pl in plans]
    if local:
        # residual shapes (epad_r) and global metadata must agree
        # across processes; rows are disjoint, so max merges counts
        from lux_tpu.parallel.multihost import allreduce_host
        ne_part_r = np.zeros(P, np.int64)
        ne_part_r[np.asarray(rows)] = ne_r
        ne_part_r = allreduce_host(ne_part_r, "max")
    else:
        ne_part_r = np.asarray(ne_r, np.int64)
    epad_r = max(128, -(-int(ne_part_r.max(initial=0)) // 128) * 128)
    src_slot = np.zeros((R, epad_r), np.int32)
    dst_local = np.full((R, epad_r), sg.vpad, np.int32)
    ew = np.zeros((R, epad_r), np.float32) if sg.weighted else None
    row_ptr_local = np.zeros((R, sg.vpad + 1), np.int32)
    for r, pl in enumerate(plans):
        nep = int(sg.ne_part[rows[r]])
        res = pl.residual
        nr = ne_r[r]
        src_slot[r, :nr] = sg.src_slot[r, :nep][res]
        r_dst = sg.dst_local[r, :nep][res]
        dst_local[r, :nr] = r_dst
        if ew is not None:
            ew[r, :nr] = sg.edge_weight[r, :nep][res]
        counts = np.bincount(r_dst, minlength=sg.vpad)
        row_ptr_local[r, 1:] = np.cumsum(counts).astype(np.int32)
    # NOTE: a local-parts residual keeps the FULL graph's
    # row_ptr_global, so sizing_row_ptr() (chunk geometry) is an
    # overestimate of the residual's chunks — consistent across
    # processes, just padded; pad chunks are isolated identities.
    residual = dataclasses.replace(
        sg, src_slot=src_slot, dst_local=dst_local, edge_weight=ew,
        row_ptr_local=row_ptr_local,
        ne_part=ne_part_r, epad=epad_r,
        _src_sorted_cache=None, content_key=None)
    return sp, residual


def pair_partial(sp: StackedPairPlan, flat_state, rowbind, rel, weight,
                 tile_pos, kind: str, msg_fn,
                 reduce_method: str = "xla"):
    """Device-side delivery + reduce for ONE part -> [n_tiles * 128]
    partial (identity where pairs contribute nothing).

    flat_state: [n_state_rows * 128] flat vertex state (the all-
    gathered whole state); rowbind/rel/weight/tile_pos: this part's
    rows of the stacked arrays; msg_fn(vals [R,128],
    weight [R,128]|None) -> per-edge messages (dead lanes carry
    garbage, masked by rel == -1).
    """
    import jax.numpy as jnp

    from lux_tpu.ops.tiled import chunk_partials

    if flat_state.ndim != 1:
        raise ValueError("pair delivery supports scalar vertex state "
                         "only")
    s2d = flat_state.reshape(-1, W)
    vals = jnp.take(s2d, rowbind, axis=0)            # [Rp, 128] rows
    vals = msg_fn(vals, weight)
    if reduce_method.startswith("pallas"):
        from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
        # rows are short (E=128): large blocks amortize the grid
        partials = chunk_partials_pallas(
            vals, rel, W, kind, block_c=64,
            interpret=reduce_method == "pallas-interpret")
    else:
        partials = chunk_partials(vals, rel, W, kind)
    partials = partials[:sp.R]                       # drop pad rows
    red2d = _class_combine(sp, partials, tile_pos, kind)
    return red2d.reshape(-1)


def _class_combine(sp: StackedPairPlan, partials, tile_pos, kind: str):
    """Shared epilogue: per-class reshape-reduce of row partials
    [R, W, ...] into slot results, trailing identity slot, then the
    tile_pos take -> [n_tiles, W, ...]."""
    import jax.numpy as jnp

    from lux_tpu.ops.segment import identity_for

    ident = identity_for(kind, partials.dtype)
    red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[kind]
    outs = []
    row0 = 0
    for (cnt, L) in sp.classes:
        blk = partials[row0:row0 + cnt * L].reshape(
            (cnt, L) + partials.shape[1:])
        outs.append(red(blk, axis=1))
        row0 += cnt * L
    outs.append(jnp.full((1,) + partials.shape[1:], ident,
                         partials.dtype))
    slots = jnp.concatenate(outs, axis=0)            # [n_slots + 1, ...]
    return jnp.take(slots, tile_pos, axis=0)         # [n_tiles, ...]


# scalar streamed-delivery block budget (pair_partial_streamed): the
# delivered f32 value rows of ONE scan block
PAIR_STREAM_BLOCK_BYTES = 64 << 20


def pair_partial_streamed(sp: StackedPairPlan, flat_state, rowbind, rel,
                          weight, tile_pos, kind: str, msg_fn,
                          reduce_method: str = "xla",
                          block_bytes: int = PAIR_STREAM_BLOCK_BYTES):
    """Memory-bounded pair delivery: identical result to
    ``pair_partial`` but the delivered f32 value rows and their
    per-row partials never materialize beyond one scan block.

    At RMAT25 x np4 the monolithic path's vals+partials are ~15 GB
    (each Rp x 128 x f32) and the whole program OOMs a 16 GB chip
    (PERF_NOTES); here each depth class (cnt slots x L contiguous
    rows) is processed as a ``lax.scan`` over blocks of S whole slots
    (S*L rows, sized to ``block_bytes``), each step fetching, reducing
    and emitting per-SLOT results [S, 128] — the cross-row combine
    happens inside the step, so live memory is one block regardless of
    graph scale.
    """
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.segment import identity_for
    from lux_tpu.ops.tiled import chunk_partials

    if flat_state.ndim != 1:
        raise ValueError("pair delivery supports scalar vertex state "
                         "only")
    s2d = flat_state.reshape(-1, W)
    red_axis = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[kind]

    def slot_results(rb, rl, wt, S, L):
        """[S*L] rows -> [S, 128] per-slot results (one block)."""
        vals = jnp.take(s2d, rb, axis=0)               # [S*L, 128]
        msgs = msg_fn(vals, wt)
        B = msgs.shape[0]
        # Pallas needs 8-row block granularity; small unaligned
        # remainder blocks take the XLA formulation instead
        if reduce_method.startswith("pallas") and B % 8 == 0:
            from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
            p = chunk_partials_pallas(
                msgs, rl, W, kind, block_c=64 if B % 64 == 0 else 8,
                interpret=reduce_method == "pallas-interpret")
        else:
            if reduce_method.startswith("pallas"):
                msgs = jax.lax.optimization_barrier(msgs)
            p = chunk_partials(msgs, rl, W, kind)
        return red_axis(p.reshape(S, L, W), axis=1)

    outs = []
    row0 = 0
    for (cnt, L) in sp.classes:
        # whole slots per block, >= 1, sized so vals fit block_bytes;
        # keep S*L a multiple of the Pallas block granularity
        S = max(1, min(cnt, block_bytes // max(1, L * W * 4)))
        if L % 8 and S >= 8:
            S -= S % 8
        nB, rem = divmod(cnt, S)

        def seg(lo, n):
            sl = slice(row0 + lo * L, row0 + (lo + n) * L)
            return (rowbind[sl], rel[sl],
                    None if weight is None else weight[sl])

        cls_out = []
        if nB:
            rb, rl, wt = seg(0, nB * S)
            rb = rb.reshape(nB, S * L)
            rl = rl.reshape(nB, S * L, W)
            xs = (rb, rl) if wt is None else \
                (rb, rl, wt.reshape(nB, S * L, W))

            def step(_, x, S=S, L=L):
                return None, slot_results(
                    x[0], x[1], x[2] if len(x) > 2 else None, S, L)

            _, reds = jax.lax.scan(step, None, xs)     # [nB, S, 128]
            cls_out.append(reds.reshape(nB * S, W))
        if rem:
            rb, rl, wt = seg(nB * S, rem)
            cls_out.append(slot_results(rb, rl, wt, rem, L))
        outs.append(jnp.concatenate(cls_out, axis=0))
        row0 += cnt * L
    # identity slot in the MESSAGE dtype (msg_fn may promote), exactly
    # like pair_partial's partials-dtype identity; with zero classes
    # (plan_sharded_pairs normally returns None first) fall back to
    # the state dtype so the identity take still works
    out_dtype = outs[0].dtype if outs else flat_state.dtype
    ident = identity_for(kind, out_dtype)
    outs.append(jnp.full((1, W), ident, out_dtype))
    slots = jnp.concatenate(outs, axis=0)              # [n_slots+1, W]
    return jnp.take(slots, tile_pos, axis=0).reshape(-1)


def resolve_pair_stream(pair_stream, pairs) -> bool:
    """Streamed pair delivery (pair_partial_streamed) is the default:
    measured FASTER than the monolithic path even at RMAT21
    (0.124-0.127 vs 0.119-0.122 GTEPS, interleaved A/B) and its live
    memory is one scan block instead of Rp x 128 x f32 vals+partials —
    which OOM a 16 GB chip at RMAT25 (PERF_NOTES).  pair_stream=False
    keeps the monolithic path (micro-graphs, debugging)."""
    if pairs is None:
        return False
    return True if pair_stream is None else bool(pair_stream)


def dot_precision(dtype):
    """The contraction precision of the dot (SDDMM) path: D = S @ T^T,
    the one-hot gradient matmul and the residual's two einsums.  On
    the TPU a float32 operand of a default-precision contraction is
    rounded to bfloat16, which puts 2**-9 on every term of <src, dst>
    and of the gradient: at the NetFlix shape the learned displacement
    was then off by the bfloat16 control's level (PERF.md section 2,
    cf.netflix).  HIGHEST keeps the float32 operands, as
    ops/tiled._exact_sum_precision does for the sums; integer
    operands are exact as they are."""
    import jax
    return (jax.lax.Precision.HIGHEST
            if np.dtype(dtype).kind == "f" else None)


def pair_partial_dot(sp: StackedPairPlan, state, rowbind, rel, weight,
                     row_tile, tile_pos, part_tile0, msg_dot_fn,
                     block_rows: int = 256):
    """Pair-lane delivery for VECTOR-state programs whose dst
    dependence is only the inner product <src, dst>
    (PullProgram.edge_value_from_dot, e.g. colfilter's SGD) — the
    blocked-SDDMM formulation of matrix-factorization on the MXU:

    per delivery row (one dense (src-tile, dst-tile) pair occurrence):
      S = src tile block [128, K]   (ONE reshaped-row fetch — the
                                     gather costs ~9 ns per ROW
                                     regardless of width, PERF_NOTES)
      T = dst tile block [128, K]   (one more row fetch)
      D = S @ T^T                   (all (src-lane, dst-lane) dots;
                                     measured FASTER than the
                                     onehot-select-then-dot
                                     formulation, 0.091 vs 0.057
                                     GTEPS at RMAT16 ef128 — the MXU
                                     eats the [128,128] block, XLA
                                     fuses the select into it)
      dot[c] = D[c, rel[c]]         (lane compare-select)
      msgs = msg_dot_fn(S, dot, w)  ((w - dot) * src for colfilter)
      partial = onehot(rel)^T @ msgs  [128, K] to the row's dst tile

    state: [n_state_rows * 128, K] all-gathered flat vertex state;
    rowbind/rel/weight/row_tile/tile_pos: this part's rows of the
    stacked arrays; part_tile0: global state2d row of this part's
    tile 0 (= part index * vpad/128).  Rows are processed in
    ``block_rows`` lax.map blocks to bound the [B, 128, 128]
    intermediates.  Returns [n_tiles * 128, K] partial sum.
    """
    import jax
    import jax.numpy as jnp

    if weight is None:
        raise ValueError("pair_partial_dot needs per-lane weights")
    Kdim = state.shape[-1]
    s3 = state.reshape(-1, W * Kdim)
    Rp = rowbind.shape[0]
    B = max(1, min(block_rows, Rp))
    nB = -(-Rp // B)
    Rpp = nB * B

    def pad(x):
        return jnp.pad(x, ((0, Rpp - Rp),) + ((0, 0),) * (x.ndim - 1))

    lanes = jnp.arange(W, dtype=rel.dtype)

    def block(args):
        rb, rl, wt, rt = args
        S = jnp.take(s3, rb, axis=0).reshape(-1, W, Kdim)
        T = jnp.take(s3, part_tile0 + rt, axis=0).reshape(-1, W, Kdim)
        D = jnp.einsum("rck,rwk->rcw", S, T,
                       preferred_element_type=S.dtype,
                       precision=dot_precision(S.dtype))
        mask = rl[..., None] == lanes                  # [B, 128, 128]
        dot = jnp.sum(jnp.where(mask, D, 0), axis=-1)  # [B, 128]
        msgs = msg_dot_fn(S, dot, wt)                  # [B, 128, K]
        # dead lanes (rel == -1) match no output lane -> contribute 0
        return jnp.einsum("rcw,rck->rwk", mask.astype(S.dtype), msgs,
                          precision=dot_precision(msgs.dtype))

    partials = jax.lax.map(
        block, (pad(rowbind).reshape(nB, B),
                pad(rel).reshape(nB, B, W),
                pad(weight).reshape(nB, B, W),
                pad(row_tile).reshape(nB, B)))
    partials = partials.reshape(Rpp, W, Kdim)[:sp.R]
    red = _class_combine(sp, partials, tile_pos, "sum")
    return red.reshape(-1, Kdim)


# Streamed SDDMM block budget: live bytes of ONE scan block (delivered
# S/T tiles + the [B, 128, 128] dot blocks + messages/partials).  The
# [*, W, W] dot intermediate dominates for K < 128, so blocks land at
# a few hundred rows — the same order as the monolithic path's
# measured-best lax.map block (DOT_BLOCK_CHUNKS, engine/delivery.py).
PAIR_DOT_BLOCK_BYTES = 64 << 20


def pair_partial_dot_streamed(sp: StackedPairPlan, state, rowbind, rel,
                              weight, row_tile, tile_pos, part_tile0,
                              msg_dot_fn,
                              block_bytes: int = PAIR_DOT_BLOCK_BYTES):
    """Memory-bounded SDDMM pair delivery: identical result to
    ``pair_partial_dot`` but neither the delivered [Rp, 128, K] tile
    values nor the per-row [Rp, 128, K] gradient partials ever
    materialize beyond one scan block.

    The monolithic path's lax.map STACKS its per-row partials — at the
    NetFlix shape that is a reproducible f32[6454, 4, 256, 128, 20] =
    67.7 GB compile allocation (PERF_NOTES round 5), 4.3x the chip.
    Here each depth class (cnt slots x L contiguous rows) runs as a
    ``lax.scan`` over blocks of S WHOLE slots (S*L rows, sized to
    ``block_bytes``); each step fetches the block's src/dst tiles,
    forms D = S @ T^T, lane-selects the dots, applies ``msg_dot_fn``,
    reduces through the one-hot gradient matmul AND folds the
    cross-row (occurrence-depth) sum inside the step — emitting
    per-SLOT results [S, 128, K], so live memory is one block at any
    scale; a slot deeper than a block is cut into blocks of rows
    (``deep_class``).  The scalar analogue (and the original of the slot-block
    discipline) is ``pair_partial_streamed``.
    """
    import jax
    import jax.numpy as jnp

    if weight is None:
        raise ValueError("pair_partial_dot needs per-lane weights")
    Kdim = state.shape[-1]
    s3 = state.reshape(-1, W * Kdim)
    lanes = jnp.arange(W, dtype=rel.dtype)

    def slot_results(rb, rl, wt, rt, S, L):
        """[S*L] delivery rows -> [S, 128, K] per-slot gradient sums
        (one block; the body is pair_partial_dot's per-row pipeline
        plus the in-step depth reduction)."""
        Sv = jnp.take(s3, rb, axis=0).reshape(-1, W, Kdim)
        T = jnp.take(s3, part_tile0 + rt, axis=0).reshape(-1, W, Kdim)
        D = jnp.einsum("rck,rwk->rcw", Sv, T,
                       preferred_element_type=Sv.dtype,
                       precision=dot_precision(Sv.dtype))
        mask = rl[..., None] == lanes                  # [S*L, 128, 128]
        dot = jnp.sum(jnp.where(mask, D, 0), axis=-1)  # [S*L, 128]
        msgs = msg_dot_fn(Sv, dot, wt)                 # [S*L, 128, K]
        # dead lanes (rel == -1) match no output lane -> contribute 0
        p = jnp.einsum("rcw,rck->rwk", mask.astype(Sv.dtype), msgs,
                       precision=dot_precision(msgs.dtype))
        return jnp.sum(p.reshape(S, L, W, Kdim), axis=1)

    # per-row live bytes: S + T + msgs + partials tiles [W, K] each,
    # plus the [W, W] dot/mask blocks
    row_bytes = 4 * W * (W + 4 * Kdim)
    block_rows = max(1, block_bytes // row_bytes)

    def deep_class(row0, cnt, L):
        """A class whose ONE slot is deeper than a block (a hub tile
        pair: 252,981 rows at the NetFlix shape, 27 GB as one block):
        each slot's rows go through the scan ``block_rows`` at a time,
        read in place with dynamic slices (as scan inputs the chunks
        compiled to 40% more code, which is device memory), and the
        per-chunk sums [W, K] are added up per slot afterwards."""
        nF, rem = divmod(L, block_rows)

        def chunk(start, n):
            def cut(x):
                return jax.lax.dynamic_slice_in_dim(x, start, n)
            return slot_results(cut(rowbind), cut(rel), cut(weight),
                                cut(row_tile), 1, n)[0]

        def full(_, i):
            return None, chunk(row0 + (i // nF) * L
                               + (i % nF) * block_rows, block_rows)

        _, parts = jax.lax.scan(full, None, jnp.arange(cnt * nF))
        out = jnp.sum(parts.reshape(cnt, nF, W, Kdim), axis=1)
        if rem:
            def tail(_, slot):
                return None, chunk(row0 + slot * L + nF * block_rows,
                                   rem)
            _, tails = jax.lax.scan(tail, None, jnp.arange(cnt))
            out = out + tails
        return out

    outs = []
    row0 = 0
    for (cnt, L) in sp.classes:
        if L > block_rows:
            outs.append(deep_class(row0, cnt, L))
            row0 += cnt * L
            continue
        # whole slots per block (>= 1: L <= block_rows here), sized so
        # one block's rows stay under block_bytes
        S = min(cnt, block_rows // L)
        nB, rem = divmod(cnt, S)

        def seg(lo, n):
            sl = slice(row0 + lo * L, row0 + (lo + n) * L)
            return (rowbind[sl], rel[sl], weight[sl], row_tile[sl])

        cls_out = []
        if nB:
            rb, rl, wt, rt = seg(0, nB * S)
            xs = (rb.reshape(nB, S * L), rl.reshape(nB, S * L, W),
                  wt.reshape(nB, S * L, W), rt.reshape(nB, S * L))

            def step(_, x, S=S, L=L):
                return None, slot_results(*x, S, L)

            _, reds = jax.lax.scan(step, None, xs)   # [nB, S, 128, K]
            cls_out.append(reds.reshape(nB * S, W, Kdim))
        if rem:
            cls_out.append(slot_results(*seg(nB * S, rem), rem, L))
        outs.append(jnp.concatenate(cls_out, axis=0))
        row0 += cnt * L
    # trailing identity slot (sum identity = 0) in the message dtype,
    # exactly like _class_combine's; zero classes degenerate cleanly
    out_dtype = outs[0].dtype if outs else state.dtype
    outs.append(jnp.zeros((1, W, Kdim), out_dtype))
    slots = jnp.concatenate(outs, axis=0)          # [n_slots+1, 128, K]
    return jnp.take(slots, tile_pos, axis=0).reshape(-1, Kdim)


def resolve_pair_dot_stream(pair_stream, sp, rows: int,
                            kdim: int) -> bool:
    """Auto-engage rule for the streamed SDDMM delivery, mirroring the
    engines' chunk-streaming budget (ops/tiled.STREAM_MSG_BYTES, the
    1 GB rule): stream once the monolithic path's stacked per-row
    partials — f32 [rows, Rp, 128, kdim], what vmap over parts
    materializes together and what produced the 67.7 GB NetFlix
    compile allocation — would pass the budget.  pair_stream
    True/False forces; None picks by budget (the default K-dim pair
    path at scale)."""
    if sp is None:
        return False
    if pair_stream is not None:
        return bool(pair_stream)
    from lux_tpu.ops.tiled import STREAM_MSG_BYTES
    return rows * sp.Rp * W * max(1, kdim) * 4 > STREAM_MSG_BYTES


def stacked_pair_reduce_numpy(sp: StackedPairPlan, p: int,
                              state_flat: np.ndarray, kind: str = "sum",
                              msg=None) -> np.ndarray:
    """Oracle for one part of a stacked plan.  msg(vals, weight) maps
    delivered values (+ per-lane weights) to messages; default uses
    the values unchanged."""
    s2d = np.asarray(state_flat).reshape(-1, W)
    vals = s2d[sp.rowbind[p]].astype(np.float64)     # [Rp, 128]
    wp = sp.weight[p] if sp.weight is not None else None
    if msg is not None:
        vals = msg(vals, wp)
    ident = {"sum": 0.0, "min": np.inf, "max": -np.inf}[kind]
    op = {"sum": np.add, "min": np.minimum, "max": np.maximum}[kind]
    out = np.full(sp.n_tiles * W, ident)
    row_base = {}
    slot_base = {}
    s = r = 0
    for c, L in sp.classes:
        slot_base[L], row_base[L] = s, r
        s += c
        r += c * L
    for t in range(sp.n_tiles):
        slot = int(sp.tile_pos[p, t])
        if slot == sp.n_slots:
            continue
        for c, L in sp.classes:
            sb, rb = slot_base[L], row_base[L]
            if sb <= slot < sb + c:
                for rr in range(rb + (slot - sb) * L,
                                rb + (slot - sb + 1) * L):
                    lanes = sp.rel_dst[p, rr]
                    for col in range(W):
                        w = int(lanes[col])
                        if 0 <= w < W:
                            out[t * W + w] = op(
                                out[t * W + w], vals[rr, col])
                break
    return out


def stacked_pair_dot_numpy(sp: StackedPairPlan, p: int,
                           state: np.ndarray, part_tile0: int,
                           msg_dot_fn) -> np.ndarray:
    """float64 oracle for one part of the SDDMM pair delivery
    (pair_partial_dot / pair_partial_dot_streamed): per delivery row,
    dot[c] = <S[c], T[rel[c]]> over the row's dst tile, msgs =
    msg_dot_fn(S, dot, w), accumulated into the lane's dst vertex.
    state: [n_state_rows * 128, K]; returns [n_tiles * 128, K].

    With integer-valued states/weights whose products stay under 2^24
    this equals the f32 device result EXACTLY (all sums exact) — the
    equivalence tests' trick for order-independent exact matching."""
    s2 = np.asarray(state, np.float64)
    Kdim = s2.shape[-1]
    out = np.zeros((sp.n_tiles * W, Kdim))
    row_base, slot_base = {}, {}
    s = r = 0
    for c, L in sp.classes:
        slot_base[L], row_base[L] = s, r
        s += c
        r += c * L
    for t in range(sp.n_tiles):
        slot = int(sp.tile_pos[p, t])
        if slot == sp.n_slots:
            continue
        for c, L in sp.classes:
            sb, rb = slot_base[L], row_base[L]
            if sb <= slot < sb + c:
                for rr in range(rb + (slot - sb) * L,
                                rb + (slot - sb + 1) * L):
                    S = s2[sp.rowbind[p, rr] * W:
                           (sp.rowbind[p, rr] + 1) * W]       # [128, K]
                    tile = int(sp.row_tile[p, rr])
                    T = s2[(part_tile0 + tile) * W:
                           (part_tile0 + tile + 1) * W]       # [128, K]
                    lanes = sp.rel_dst[p, rr]
                    for col in range(W):
                        w = int(lanes[col])
                        if not 0 <= w < W:
                            continue
                        # numpy 0-d scalars so broadcasting program
                        # callbacks ((w - dot)[..., None] * src) work
                        dot = S[col] @ T[w]
                        msg = msg_dot_fn(
                            S[col], dot,
                            np.float64(sp.weight[p, rr, col]))
                        out[t * W + w] += np.asarray(msg).reshape(Kdim)
                break
    return out
