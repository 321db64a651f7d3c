"""Page-binned two-level gather: break the ~9 ns/edge delivery floor.

Every delivery path in the repo bottoms out at XLA's ~9 ns per 4-byte
random access (PERF_NOTES round 2: 8.96 ns/elem, flat from 16 KB to
64 MB tables) — ~90% of a pull iteration.  The same measurements show
the escape hatch on this hardware: STATIC row movement is cheap
(`jnp.take` of [*, 128] rows = 24 ns/row = 0.19 ns/elem) and the one
fast DYNAMIC primitive is the Pallas 128-lane shuffle
(`take_along_axis` axis=1 -> `tpu.dynamic_gather` dim 1, 0.38
ns/elem).  So a gather decomposed into *fetch unique 128-wide pages,
then shuffle within pages* is priced well under 2 ns/edge whenever
edges share pages — which degree-sorted power-law graphs do heavily.

The decomposition (the microbenchmark-driven primitive design of the
IPU dissection paper, PAPERS.md; the fixed-size-page blocking idiom is
Ragged Paged Attention's):

host plan (built once, shipped as jit ARGUMENTS like ops/pairs.py):
  1. bin every edge by (destination tile, source PAGE) where a page is
     one 128-wide row of the reshaped state table ``[T, 128]``;
  2. each bin of n edges becomes ceil(n/128) full delivery rows: a row
     binds to ONE page, its 128 lanes carry (source lane, destination
     offset) pairs — dead lanes carry rel = -1 (the identity-sentinel
     convention: they match no output lane downstream);
  3. pages are DEDUPLICATED per part into ``page_ids [n_pages]``; the
     per-edge (page_slot, lane) pair packs into one uint32
     ``page_slot << 7 | lane`` (lane is exactly 7 bits at W=128 — the
     round-5 owner ``src << 7 | rel`` encoding), and every lane of a
     row shares its page_slot, so the row's page decodes from lane 0;
  4. rows group per destination tile and depth-class exactly like the
     pair plan, so the cross-row combine is the same static
     reshape-reduce (ops/pairs._class_combine).

device (``paged_partial``):
  1. ``pages = take(state2d, page_ids)``     — THE state-table access
     of the iteration (row-granular; audited as the one access of a
     dense iteration, lux_tpu/audit.py gather-budget);
  2. ``rows = take(pages, page_slot)``       — row fetch from the
     small deduplicated buffer (0.19 ns/elem class);
  3. ``vals = take_along_axis(rows, lane)``  — the 0.38 ns/elem lane
     shuffle, as a Pallas kernel on TPU (interpret-mode on CPU like
     ops/pallas_reduce.py; plain XLA on the CPU test mesh);
  4. existing compare-reduce machinery delivers by rel
     (ops/tiled.chunk_partials / chunk_partials_pallas).

Coverage is TOTAL — every edge rides a paged row, so ``gather=
"paged"`` engines produce exactly the reduce the flat gather produces
(bitwise for order-independent min/max reductions; sum reductions
re-associate, proven exact on sub-2^24 integer states like the SDDMM
oracle trick, ops/pairs.stacked_pair_dot_numpy).  Whether the paged
path PAYS depends on the plan's measured row fill and unique-page
ratio: ``gather="auto"`` resolves by the scalemodel break-even
(scalemodel.page_gather_ns) on the stats the plan records.

Reference analogue: the reference stages remote regions whole and
indexes them per edge (reference pull_model.inl:454-461); here the
host pre-factors that per-edge index into static page movement plus a
lane-granular shuffle, because that is what the TPU prices cheaply.
"""

from __future__ import annotations

import dataclasses

import numpy as np

W = 128

# page_slot rides the high 25 bits of the packed uint32 (lane is 7
# bits at W=128) — same bound class as the owner layout's packed
# src << 7 | rel encoding (ops/owner.OwnerLayout.PACK_VPAD_MAX)
PAGE_SLOT_MAX = 1 << 25


@dataclasses.dataclass
class PagedPlan:
    """Stacked (all-parts) page-binned delivery plan (host numpy).

    page_ids  int32 [P, n_pages]    deduplicated state2d page rows per
                                    part (pad rows point at page 0)
    slot_lane uint32 [P, Rp, 128]   packed ``page_slot << 7 | lane``;
                                    every lane of a row shares the
                                    row's page_slot (decode from lane
                                    0); dead lanes carry lane 0
    rel_dst   int8 [P, Rp, 128]     dst offset in [0, 128); -1 = dead
    weight    f32 [P, Rp, 128] | None  per-lane edge weight (0 dead)
    row_tile  int32 [P, Rp]         dst tile of each row (dead -> 0)
    tile_pos  int32 [P, n_tiles]    class slot per tile; tiles with no
                                    slot point at the trailing
                                    identity slot ``n_slots``
    classes   [(count, depth)]      shared by every part (rows laid
                                    out against the common elementwise
                                    -max depth profile, like
                                    ops/pairs.plan_sharded_pairs)
    n_tiles   destination tiles per plan row (per-part tiles for the
              dense engines; GLOBAL tiles G for the owner plan)

    PAGE-MAJOR mode (``mode="pagemajor"``, round 16): delivery rows
    bind to source pages FIRST (full 128-lane GATHER rows — one page
    fetch + lane shuffle serves 128 edges regardless of how few share
    a destination tile), and the reduce runs over VIRTUAL rows — each
    the contiguous lane run of one (gather row, dst tile) pair,
    materialized by a row-granular ``take`` of the delivered values.
    ``slot_lane`` then holds the GATHER rows' packed page/lane pairs
    (leading dim Rg) and ``vrow_src [P, Rp]`` maps each virtual
    (reduce-level) row to its gather row; rel_dst/weight/row_tile/
    tile_pos keep their reduce-level meaning over the Rp virtual
    rows.  The OWNER page-major plan additionally groups gather rows
    by DESTINATION PART (``route`` = Mg rows per (src, dst) pair, the
    all_to_all routing quantum) with sender-side weights so messages
    are complete before the routing hop; vrow_src then indexes the
    RECEIVED ``[P_src * Mg]`` row buffer.
    """

    page_ids: np.ndarray
    slot_lane: np.ndarray
    rel_dst: np.ndarray
    weight: np.ndarray | None
    row_tile: np.ndarray
    tile_pos: np.ndarray
    classes: list
    n_tiles: int
    n_slots: int
    R: int
    Rp: int
    n_pages: int
    stats: dict
    mode: str = "paged"
    vrow_src: np.ndarray | None = None   # int32 [P, Rp] -> gather row
    Rg: int = 0                          # padded gather rows (pm mode)
    route: int = 0                       # Mg rows per (src, dst) pair


# ---------------------------------------------------------------------
# host plan builder
# ---------------------------------------------------------------------


def _part_rows(src_idx, dst_tile, dst_rel, n_dst_tiles: int,
               n_src_rows: int, weights=None):
    """Bin one part's edges by (dst tile, source page) and lay each
    bin into ceil(count/128) full 128-lane rows (tile-major order).

    Returns (row_page, lane int8 [R, 128], rel int8 [R, 128],
    weight f32 [R, 128] | None, row_tile, rows_by_tile) host arrays;
    R = 0 for an edge-less part."""
    ne = len(src_idx)
    if ne == 0:
        z = np.zeros((0, W), np.int8)
        wz = np.zeros((0, W), np.float32) if weights is not None else None
        return (np.zeros(0, np.int64), z, z.copy(), wz,
                np.zeros(0, np.int64),
                np.zeros(n_dst_tiles, np.int64))
    src_idx = np.asarray(src_idx, np.int64)
    page = src_idx // W
    lane = (src_idx % W).astype(np.int8)
    rel8 = np.asarray(dst_rel, np.int64).astype(np.int8)
    key = np.asarray(dst_tile, np.int64) * np.int64(n_src_rows) + page
    idx = np.arange(ne, dtype=np.int64)
    from lux_tpu import native
    native.sort_kv(key, (idx,))          # fused radix: key + edge idx
    newg = np.ones(ne, bool)
    newg[1:] = key[1:] != key[:-1]
    bstart = np.nonzero(newg)[0]
    cnt = np.diff(np.concatenate((bstart, [ne])))
    bin_of = np.cumsum(newg) - 1                     # sorted pos -> bin
    off = np.arange(ne, dtype=np.int64) - bstart[bin_of]
    rows_of_bin = -(-cnt // W)
    row_base = np.concatenate(([0], np.cumsum(rows_of_bin)[:-1]))
    row_of = row_base[bin_of] + off // W
    lanepos = off % W
    R = int(rows_of_bin.sum())
    bin_page = key[bstart] % np.int64(n_src_rows)
    bin_tile = key[bstart] // np.int64(n_src_rows)
    row_page = np.repeat(bin_page, rows_of_bin)
    row_tile = np.repeat(bin_tile, rows_of_bin)
    lane_arr = np.zeros((R, W), np.int8)
    rel_arr = np.full((R, W), -1, np.int8)
    lane_arr[row_of, lanepos] = lane[idx]
    rel_arr[row_of, lanepos] = rel8[idx]
    w_arr = None
    if weights is not None:
        w_arr = np.zeros((R, W), np.float32)
        w_arr[row_of, lanepos] = np.asarray(weights, np.float32)[idx]
    # every edge must own a distinct (row, lane) — the pair planner's
    # loud collision check (ops/pairs.build_pair_plan)
    delivered = int(np.count_nonzero(rel_arr != -1))
    if delivered != ne:
        raise AssertionError(
            f"paged plan dropped {ne - delivered} of {ne} edges "
            f"(colliding (row, lane) writes)")
    rows_by_tile = np.bincount(row_tile, minlength=n_dst_tiles)
    return row_page, lane_arr, rel_arr, w_arr, row_tile, rows_by_tile


def _pad8_distinct(n: int, avoid: int) -> int:
    """Round up to the Pallas 8-row block granularity, keeping the
    result distinct from ``avoid`` — the padded leading dim must never
    equal the reshaped state table's row count, or the audit's
    operand-shape accounting (lux_tpu/audit.py gather-budget paged
    recognition) could mistake a buffer fetch for the table access."""
    n = max(8, -(-n // 8) * 8)
    while n == avoid:
        n += 8
    return n


def _assemble(parts, n_dst_tiles: int, n_src_rows: int, ne_total: int,
              weighted: bool) -> PagedPlan:
    """Stack per-part ``_part_rows`` outputs against a COMMON depth
    profile (elementwise max over parts, ladder-quantized) so every
    part compiles the same class structure — the
    plan_sharded_pairs two-pass discipline."""
    from lux_tpu.ops.pairs import quantize_depths

    if n_src_rows > PAGE_SLOT_MAX:
        raise ValueError(
            f"paged gather needs a state table of <= {PAGE_SLOT_MAX} "
            f"128-wide pages (25-bit page_slot), got {n_src_rows}")
    P = len(parts)
    prof = np.zeros(n_dst_tiles, np.int64)
    for pr in parts:
        prof = np.maximum(prof, np.sort(pr[5])[::-1])
    depth = quantize_depths(prof)
    row_off = np.concatenate(([0], np.cumsum(depth)))
    Rtot = int(row_off[-1])
    Rp = _pad8_distinct(Rtot, n_src_rows)

    classes = []
    for L in np.unique(depth)[::-1]:
        cnt = int((depth == L).sum())
        if L > 0:
            classes.append((cnt, int(L)))
    n_slots = sum(c for c, _L in classes)

    uniq_pages = [np.unique(pr[0]) for pr in parts]
    max_pages = max((len(u) for u in uniq_pages), default=1) or 1
    n_pages = _pad8_distinct(max_pages, n_src_rows)

    page_ids = np.zeros((P, n_pages), np.int32)
    slot_lane = np.zeros((P, Rp, W), np.uint32)
    rel_dst = np.full((P, Rp, W), -1, np.int8)
    wgt = np.zeros((P, Rp, W), np.float32) if weighted else None
    row_tile = np.zeros((P, Rp), np.int32)
    tile_pos = np.full((P, n_dst_tiles), n_slots, np.int32)

    rows_real = 0
    for p, pr in enumerate(parts):
        r_page, lane, rel, w, r_tile, by_tile = pr
        rows_real += len(r_page)
        u = uniq_pages[p]
        page_ids[p, :len(u)] = u.astype(np.int32)
        t_order = np.argsort(-by_tile, kind="stable")
        # slot s (depth[s] > 0) hosts tile t_order[s]; depth-0 slots
        # and the tiles beyond them reduce to the identity slot
        live = depth > 0
        tile_pos[p, t_order[live]] = np.nonzero(live)[0].astype(np.int32)
        if not len(r_page):
            continue
        if (by_tile[t_order] > depth).any():
            raise AssertionError("common depth profile does not cover "
                                 "a part's per-tile row counts")
        # rows come out of _part_rows tile-major: place each tile's
        # run at its slot's row offset
        slot_of_tile = np.full(n_dst_tiles, -1, np.int64)
        slot_of_tile[t_order] = np.arange(n_dst_tiles)
        first = np.zeros(n_dst_tiles, np.int64)
        np.add.at(first, r_tile, 1)
        first = np.concatenate(([0], np.cumsum(first)[:-1]))
        within = np.arange(len(r_page)) - first[r_tile]
        dst = row_off[slot_of_tile[r_tile]] + within
        pslot = np.searchsorted(u, r_page).astype(np.uint32)
        slot_lane[p, dst] = ((pslot[:, None] << np.uint32(7))
                             | lane.astype(np.uint32) & np.uint32(0x7F))
        rel_dst[p, dst] = rel
        row_tile[p, dst] = r_tile.astype(np.int32)
        if weighted:
            wgt[p, dst] = w

    fill = ne_total / max(rows_real, 1)
    unique_total = sum(len(u) for u in uniq_pages)
    stats = dict(
        ne=ne_total, rows=rows_real, fill=fill,
        unique_pages=unique_total,
        page_ratio=unique_total * W / max(ne_total, 1),
        # live lanes per PADDED row: class-ladder pad rows pay the
        # same per-row machinery, so cost models divide by this
        padded_fill=ne_total / max(P * Rp, 1),
        lane_inflation=P * Rp * W / max(ne_total, 1))
    return PagedPlan(
        page_ids=page_ids, slot_lane=slot_lane, rel_dst=rel_dst,
        weight=wgt, row_tile=row_tile, tile_pos=tile_pos,
        classes=classes, n_tiles=n_dst_tiles, n_slots=n_slots,
        R=Rtot, Rp=Rp, n_pages=n_pages, stats=stats)


# ---------------------------------------------------------------------
# page-major layout (round 16): gather rows bind to pages FIRST
# ---------------------------------------------------------------------


def _pm_layout(src_idx, dst_local, n_dst_tiles: int, n_src_rows: int,
               group=None):
    """Shared index math of the page-major layout: sort this part's
    edges by (row group, source page, destination), then derive each
    edge's GATHER row (full 128-lane rows binding to one
    (group, page)) and its VIRTUAL row (the contiguous lane run of
    one (gather row, dst tile) — the reduce-level unit).  ``group``
    is the optional row-group key (the DESTINATION PART for the owner
    routing plan; None = one group).  Returns a dict of per-edge /
    per-row host arrays consumed by both the array builder
    (``_part_rows_pm``) and the counting pass
    (``_part_bin_stats_pm``)."""
    from lux_tpu import native

    ne = len(src_idx)
    src_idx = np.asarray(src_idx, np.int64)
    dst = np.asarray(dst_local, np.int64)
    page = src_idx // W
    lane = (src_idx % W).astype(np.int8)
    grp = (np.zeros(ne, np.int64) if group is None
           else np.asarray(group, np.int64))
    D = np.int64(n_dst_tiles) * W
    key = (grp * np.int64(n_src_rows) + page) * D + dst
    idx = np.arange(ne, dtype=np.int64)
    native.sort_kv(key, (idx,))
    gp_key = key // D                    # group * n_src_rows + page
    dst_s = key % D
    newp = np.ones(ne, bool)
    newp[1:] = gp_key[1:] != gp_key[:-1]
    pstart = np.nonzero(newp)[0]
    pcnt = np.diff(np.concatenate((pstart, [ne])))
    rows_of = -(-pcnt // W)
    row_base = np.concatenate(([0], np.cumsum(rows_of)[:-1]))
    pbin = np.cumsum(newp) - 1
    off = np.arange(ne, dtype=np.int64) - pstart[pbin]
    gr = row_base[pbin] + off // W       # gather row of each edge
    lpos = off % W                       # its lane within that row
    Rg = int(rows_of.sum())
    g_page = np.repeat(gp_key[pstart] % np.int64(n_src_rows), rows_of)
    g_group = np.repeat(gp_key[pstart] // np.int64(n_src_rows),
                        rows_of)
    # virtual rows: contiguous (gather row, dst tile) runs — gr is
    # non-decreasing along the sort and dst is sorted within a
    # (group, page) bin, so the run key is non-decreasing too
    tile = dst_s // W
    vkey = gr * np.int64(n_dst_tiles) + tile
    newv = np.ones(ne, bool)
    newv[1:] = vkey[1:] != vkey[:-1]
    vb = np.nonzero(newv)[0]
    return dict(ne=ne, idx=idx, gr=gr, lpos=lpos, lane=lane,
                dst_s=dst_s, tile=tile, Rg=Rg, g_page=g_page,
                g_group=g_group,
                vid=np.cumsum(newv) - 1, vb=vb,
                vrow_gr=gr[vb], vrow_tile=tile[vb])


def _part_rows_pm(src_idx, dst_local, n_dst_tiles: int,
                  n_src_rows: int, weights=None, group=None):
    """One part's PAGE-MAJOR rows: full gather rows (one per 128
    edges of a (group, page) bin) plus the virtual reduce rows.
    Returns (g_page, g_group, glane int8 [Rg, 128], w_gather,
    vrow_gr, vrow_tile, rel int8 [Rv, 128], w_virtual,
    rows_by_tile)."""
    ne = len(src_idx)
    if ne == 0:
        z8 = np.zeros((0, W), np.int8)
        zw = np.zeros((0, W), np.float32) if weights is not None \
            else None
        zi = np.zeros(0, np.int64)
        return (zi, zi.copy(), z8, zw, zi.copy(), zi.copy(),
                z8.copy(),
                zw.copy() if zw is not None else None,
                np.zeros(n_dst_tiles, np.int64))
    L = _pm_layout(src_idx, dst_local, n_dst_tiles, n_src_rows, group)
    idx, gr, lpos = L["idx"], L["gr"], L["lpos"]
    glane = np.zeros((L["Rg"], W), np.int8)
    glane[gr, lpos] = L["lane"][idx]
    w_g = w_v = None
    if weights is not None:
        ws = np.asarray(weights, np.float32)[idx]
        w_g = np.zeros((L["Rg"], W), np.float32)
        w_g[gr, lpos] = ws
    Rv = len(L["vb"])
    rel = np.full((Rv, W), -1, np.int8)
    rel[L["vid"], lpos] = (L["dst_s"] % W).astype(np.int8)
    if weights is not None:
        w_v = np.zeros((Rv, W), np.float32)
        w_v[L["vid"], lpos] = ws
    # every edge owns a distinct (virtual row, lane) — the planner's
    # loud collision check (same contract as _part_rows)
    delivered = int(np.count_nonzero(rel != -1))
    if delivered != ne:
        raise AssertionError(
            f"page-major plan dropped {ne - delivered} of {ne} edges "
            f"(colliding (row, lane) writes)")
    rows_by_tile = np.bincount(L["vrow_tile"], minlength=n_dst_tiles)
    return (L["g_page"], L["g_group"], glane, w_g, L["vrow_gr"],
            L["vrow_tile"], rel, w_v, rows_by_tile)


def _part_bin_stats_pm(src_idx, dst_local, n_dst_tiles: int,
                       n_src_rows: int, group=None,
                       n_groups: int = 1):
    """Counting half of ``_part_rows_pm``: (virtual rows by tile,
    n virtual rows, n gather rows, gather rows by group) from the
    sort only — what ``gather="auto"`` prices the page-major mode
    from without materializing it."""
    ne = len(src_idx)
    if ne == 0:
        return (np.zeros(n_dst_tiles, np.int64), 0, 0,
                np.zeros(n_groups, np.int64))
    L = _pm_layout(src_idx, dst_local, n_dst_tiles, n_src_rows, group)
    by_tile = np.bincount(L["vrow_tile"], minlength=n_dst_tiles)
    by_group = np.bincount(L["g_group"], minlength=n_groups)
    return by_tile, len(L["vb"]), L["Rg"], by_group


def _assemble_pm(parts, n_dst_tiles: int, n_src_rows: int,
                 ne_total: int, weighted: bool) -> PagedPlan:
    """Stack per-part ``_part_rows_pm`` outputs (dense, group=None)
    against a common depth profile over the VIRTUAL rows — the same
    two-pass discipline as ``_assemble``; the gather rows pad to a
    common Rg."""
    from lux_tpu.ops.pairs import quantize_depths

    if n_src_rows > PAGE_SLOT_MAX:
        raise ValueError(
            f"paged gather needs a state table of <= {PAGE_SLOT_MAX} "
            f"128-wide pages (25-bit page_slot), got {n_src_rows}")
    P = len(parts)
    prof = np.zeros(n_dst_tiles, np.int64)
    for pr in parts:
        prof = np.maximum(prof, np.sort(pr[8])[::-1])
    depth = quantize_depths(prof)
    row_off = np.concatenate(([0], np.cumsum(depth)))
    Rtot = int(row_off[-1])
    Rp = _pad8_distinct(Rtot, n_src_rows)
    classes = []
    for Lv in np.unique(depth)[::-1]:
        cnt = int((depth == Lv).sum())
        if Lv > 0:
            classes.append((cnt, int(Lv)))
    n_slots = sum(c for c, _L in classes)

    uniq_pages = [np.unique(pr[0]) for pr in parts]
    max_pages = max((len(u) for u in uniq_pages), default=1) or 1
    n_pages = _pad8_distinct(max_pages, n_src_rows)
    Rg_max = max((len(pr[0]) for pr in parts), default=1) or 1
    Rg = _pad8_distinct(Rg_max, n_src_rows)

    page_ids = np.zeros((P, n_pages), np.int32)
    gsl = np.zeros((P, Rg, W), np.uint32)
    rel_dst = np.full((P, Rp, W), -1, np.int8)
    wgt = np.zeros((P, Rp, W), np.float32) if weighted else None
    row_tile = np.zeros((P, Rp), np.int32)
    vrow_src = np.zeros((P, Rp), np.int32)
    tile_pos = np.full((P, n_dst_tiles), n_slots, np.int32)

    g_rows_real = v_rows_real = 0
    for p, pr in enumerate(parts):
        (g_page, _gg, glane, _wg, vrow_gr, vrow_tile, rel, w_v,
         by_tile) = pr
        g_rows_real += len(g_page)
        v_rows_real += len(vrow_gr)
        u = uniq_pages[p]
        page_ids[p, :len(u)] = u.astype(np.int32)
        t_order = np.argsort(-by_tile, kind="stable")
        live = depth > 0
        tile_pos[p, t_order[live]] = np.nonzero(live)[0].astype(
            np.int32)
        if not len(g_page):
            continue
        pslot = np.searchsorted(u, g_page).astype(np.uint32)
        gsl[p, :len(g_page)] = ((pslot[:, None] << np.uint32(7))
                                | glane.astype(np.uint32)
                                & np.uint32(0x7F))
        if (by_tile[t_order] > depth).any():
            raise AssertionError("common depth profile does not cover "
                                 "a part's per-tile row counts")
        # virtual rows tile-major into the class slots (like
        # _assemble; they come out page-major, so re-sort by tile)
        ordv = np.argsort(vrow_tile, kind="stable")
        vt = vrow_tile[ordv]
        slot_of_tile = np.full(n_dst_tiles, -1, np.int64)
        slot_of_tile[t_order] = np.arange(n_dst_tiles)
        first = np.zeros(n_dst_tiles, np.int64)
        np.add.at(first, vt, 1)
        first = np.concatenate(([0], np.cumsum(first)[:-1]))
        within = np.arange(len(vt)) - first[vt]
        dst = row_off[slot_of_tile[vt]] + within
        rel_dst[p, dst] = rel[ordv]
        row_tile[p, dst] = vt.astype(np.int32)
        vrow_src[p, dst] = vrow_gr[ordv].astype(np.int32)
        if weighted:
            wgt[p, dst] = w_v[ordv]

    stats = dict(
        ne=ne_total, rows=v_rows_real,
        fill=ne_total / max(v_rows_real, 1),
        unique_pages=sum(len(u) for u in uniq_pages),
        page_ratio=(sum(len(u) for u in uniq_pages) * W
                    / max(ne_total, 1)),
        padded_fill=ne_total / max(P * Rp, 1),
        lane_inflation=P * Rp * W / max(ne_total, 1),
        mode="pagemajor", g_rows=g_rows_real,
        g_fill=ne_total / max(g_rows_real, 1),
        padded_g_fill=ne_total / max(P * Rg, 1))
    return PagedPlan(
        page_ids=page_ids, slot_lane=gsl, rel_dst=rel_dst, weight=wgt,
        row_tile=row_tile, tile_pos=tile_pos, classes=classes,
        n_tiles=n_dst_tiles, n_slots=n_slots, R=Rtot, Rp=Rp,
        n_pages=n_pages, stats=stats, mode="pagemajor",
        vrow_src=vrow_src, Rg=Rg)


def plan_pagemajor(sg) -> PagedPlan:
    """Dense-engine PAGE-MAJOR plan: gather rows bind to pages of the
    full flat state table (merging across the part's own destination
    tiles buys near-full rows), virtual rows carry the per-tile
    reduce.  No routing — a dense part's edges all land in the part.
    Same build requirements as ``plan_paged_gather``."""
    if sg.local_parts is not None:
        raise ValueError("paged gather does not support multi-host "
                         "local-parts builds yet")
    if sg.vpad % W:
        raise ValueError("paged gather needs vpad % 128 == 0; build "
                         "the ShardedGraph with vpad_align=128")
    n_src_rows = sg.num_parts * sg.vpad // W
    n_dst_tiles = sg.vpad // W
    parts = []
    for r in range(sg.num_parts):
        nep = int(sg.ne_part[r])
        wp = (np.asarray(sg.edge_weight[r, :nep]) if sg.weighted
              else None)
        parts.append(_part_rows_pm(sg.src_slot[r, :nep],
                                   sg.dst_local[r, :nep],
                                   n_dst_tiles, n_src_rows, wp))
    return _assemble_pm(parts, n_dst_tiles, n_src_rows, int(sg.ne),
                        sg.weighted)


def plan_owner_pagemajor(sg) -> PagedPlan:
    """Owner-exchange PAGE-MAJOR plan: each SOURCE part's gather rows
    bind to (destination part, page-of-own-shard) — full rows built
    from the shard, grouped by destination part and padded to a
    common ``Mg`` rows per (src, dst) pair so completed rows ROUTE
    whole through one ``all_to_all`` (the owner machinery's
    collective, ops/owner.owner_exchange's min/max route) — and each
    DESTINATION part reduces its received ``[P_src * Mg]`` row buffer
    through virtual rows over its own local tiles.  Sender-side
    weights: messages are complete before the hop, the receiver only
    reduces."""
    from lux_tpu.ops.pairs import quantize_depths

    if sg.local_parts is not None:
        raise ValueError("paged gather does not support multi-host "
                         "local-parts builds yet")
    if sg.vpad % W:
        raise ValueError("paged gather needs vpad % 128 == 0; build "
                         "the ShardedGraph with vpad_align=128")
    P, vpad = sg.num_parts, sg.vpad
    n_tiles = vpad // W
    n_src_rows = vpad // W
    if n_src_rows > PAGE_SLOT_MAX:
        raise ValueError(
            f"paged gather needs a state shard of <= {PAGE_SLOT_MAX} "
            f"128-wide pages (25-bit page_slot), got {n_src_rows}")
    built = []
    for srcl, gt, rel, w in _owner_part_edges(sg):
        d = gt // n_tiles
        dstl = (gt % n_tiles) * W + rel
        built.append(_part_rows_pm(srcl, dstl, n_tiles, n_src_rows,
                                   weights=w, group=d))
    # routing quantum: Mg rows per (src, dst) pair — all_to_all needs
    # equal splits, so every pair pads to the max
    Mg = 8
    for pr in built:
        if len(pr[1]):
            Mg = max(Mg, int(np.bincount(pr[1], minlength=P).max()))
    Mg = -(-Mg // 8) * 8

    prof = np.zeros(n_tiles, np.int64)
    by_tile_d = np.zeros((P, n_tiles), np.int64)   # dst part x tile
    for s, pr in enumerate(built):
        (_gp, g_group, _gl, _wg, vrow_gr, vrow_tile, _rel, _wv,
         _bt) = pr
        vg = g_group[vrow_gr]                      # dst part per vrow
        np.add.at(by_tile_d, (vg, vrow_tile), 1)
    for d in range(P):
        prof = np.maximum(prof, np.sort(by_tile_d[d])[::-1])
    depth = quantize_depths(prof)
    row_off = np.concatenate(([0], np.cumsum(depth)))
    Rtot = int(row_off[-1])
    Rp = _pad8_distinct(Rtot, n_src_rows)
    classes = []
    for Lv in np.unique(depth)[::-1]:
        cnt = int((depth == Lv).sum())
        if Lv > 0:
            classes.append((cnt, int(Lv)))
    n_slots = sum(c for c, _L in classes)

    uniq_pages = [np.unique(pr[0]) for pr in built]
    max_pages = max((len(u) for u in uniq_pages), default=1) or 1
    n_pages = _pad8_distinct(max_pages, n_src_rows)

    page_ids = np.zeros((P, n_pages), np.int32)
    gsl = np.zeros((P, P * Mg, W), np.uint32)
    w_send = (np.zeros((P, P * Mg, W), np.float32) if sg.weighted
              else None)
    rel_dst = np.full((P, Rp, W), -1, np.int8)
    row_tile = np.zeros((P, Rp), np.int32)
    vrow_src = np.zeros((P, Rp), np.int32)
    tile_pos = np.full((P, n_tiles), n_slots, np.int32)

    # receiver-side collection: per dst part, virtual rows arrive
    # from every source part (vrow_src indexes the routed buffer
    # [P_src * Mg]); gather per-dst placement cursors from the
    # common profile
    t_order_d, slot_of_tile_d, cursor_d = [], [], []
    for d in range(P):
        t_order = np.argsort(-by_tile_d[d], kind="stable")
        live = depth > 0
        tile_pos[d, t_order[live]] = np.nonzero(live)[0].astype(
            np.int32)
        if (by_tile_d[d][t_order] > depth).any():
            raise AssertionError("common depth profile does not "
                                 "cover a dst part's row counts")
        sot = np.full(n_tiles, -1, np.int64)
        sot[t_order] = np.arange(n_tiles)
        t_order_d.append(t_order)
        slot_of_tile_d.append(sot)
        cursor_d.append(np.zeros(n_tiles, np.int64))

    g_rows_real = v_rows_real = 0
    for s, pr in enumerate(built):
        (g_page, g_group, glane, w_g, vrow_gr, vrow_tile, rel, _wv,
         _bt) = pr
        g_rows_real += len(g_page)
        v_rows_real += len(vrow_gr)
        u = uniq_pages[s]
        page_ids[s, :len(u)] = u.astype(np.int32)
        if not len(g_page):
            continue
        # gather rows grouped by dst part (the sort made them
        # contiguous): row j of the (s -> d) block lands at d*Mg + j
        first_of_d = np.zeros(P, np.int64)
        np.add.at(first_of_d, g_group, 1)
        if (first_of_d > Mg).any():
            raise AssertionError("Mg does not cover a (src, dst) "
                                 "row block")
        first_of_d = np.concatenate(([0], np.cumsum(first_of_d)[:-1]))
        j = np.arange(len(g_page)) - first_of_d[g_group]
        send_pos = g_group * Mg + j
        pslot = np.searchsorted(u, g_page).astype(np.uint32)
        gsl[s, send_pos] = ((pslot[:, None] << np.uint32(7))
                            | glane.astype(np.uint32) & np.uint32(0x7F))
        if w_send is not None and w_g is not None:
            w_send[s, send_pos] = w_g
        # virtual rows land on their dst part's receive plan; the
        # routed buffer index of gather row g is s*Mg + j[g]
        vg = g_group[vrow_gr]
        buf_idx = s * Mg + j[vrow_gr]
        for d in range(P):
            m = vg == d
            if not m.any():
                continue
            vt = vrow_tile[m]
            ordv = np.argsort(vt, kind="stable")
            vt = vt[ordv]
            # per-tile cursors persist across source parts: rows of
            # the same tile from different senders stack in s order
            within = cursor_d[d][vt] + _runpos(vt)
            cursor_d[d][:] += np.bincount(vt, minlength=n_tiles)
            dstp = row_off[slot_of_tile_d[d][vt]] + within
            rel_dst[d, dstp] = rel[m][ordv]
            row_tile[d, dstp] = vt.astype(np.int32)
            vrow_src[d, dstp] = buf_idx[m][ordv].astype(np.int32)

    unique_total = sum(len(u) for u in uniq_pages)
    stats = dict(
        ne=int(sg.ne), rows=v_rows_real,
        fill=int(sg.ne) / max(v_rows_real, 1),
        unique_pages=unique_total,
        page_ratio=unique_total * W / max(int(sg.ne), 1),
        padded_fill=int(sg.ne) / max(P * Rp, 1),
        lane_inflation=P * Rp * W / max(int(sg.ne), 1),
        mode="pagemajor", g_rows=g_rows_real,
        g_fill=int(sg.ne) / max(g_rows_real, 1),
        padded_g_fill=int(sg.ne) / max(P * P * Mg, 1),
        route_rows=P * P * Mg,
        route_inflation=P * P * Mg * W / max(int(sg.ne), 1))
    return PagedPlan(
        page_ids=page_ids, slot_lane=gsl, rel_dst=rel_dst,
        weight=w_send, row_tile=row_tile, tile_pos=tile_pos,
        classes=classes, n_tiles=n_tiles, n_slots=n_slots, R=Rtot,
        Rp=Rp, n_pages=n_pages, stats=stats, mode="pagemajor",
        vrow_src=vrow_src, Rg=P * Mg, route=Mg)


def _runpos(sorted_vals: np.ndarray) -> np.ndarray:
    """Position of each element within its run of equal values
    (``sorted_vals`` sorted ascending)."""
    n = len(sorted_vals)
    if n == 0:
        return np.zeros(0, np.int64)
    new = np.ones(n, bool)
    new[1:] = sorted_vals[1:] != sorted_vals[:-1]
    start = np.nonzero(new)[0]
    return np.arange(n) - start[np.cumsum(new) - 1]


def _part_bin_stats(src_idx, dst_tile, n_dst_tiles: int,
                    n_src_rows: int):
    """The counting half of ``_part_rows``: per-tile row counts, real
    row count and unique-page count from ONE payload-free key sort —
    no lane/rel array fills, so ``gather="auto"`` can price a plan
    without materializing it (the stats formulas must mirror
    ``_assemble``; tests/test_pagegather.py pins the equality)."""
    ne = len(src_idx)
    if ne == 0:
        return np.zeros(n_dst_tiles, np.int64), 0, 0
    page = np.asarray(src_idx, np.int64) // W
    key = np.asarray(dst_tile, np.int64) * np.int64(n_src_rows) + page
    from lux_tpu import native
    native.sort_kv(key, ())
    newg = np.ones(ne, bool)
    newg[1:] = key[1:] != key[:-1]
    bstart = np.nonzero(newg)[0]
    cnt = np.diff(np.concatenate((bstart, [ne])))
    rows_of_bin = -(-cnt // W)
    bin_tile = key[bstart] // np.int64(n_src_rows)
    rows_by_tile = np.zeros(n_dst_tiles, np.int64)
    np.add.at(rows_by_tile, bin_tile, rows_of_bin)
    uniq = len(np.unique(key[bstart] % np.int64(n_src_rows)))
    return rows_by_tile, int(rows_of_bin.sum()), uniq


def plan_paged_stats(sg, exchange: str = "gather",
                     pagemajor: bool = False) -> dict:
    """The plan's recorded stats WITHOUT building the plan arrays:
    the same binning key sort, none of the [P, Rp, 128] assembly —
    what ``gather="auto"`` resolution and the bench A/B's flat line
    read (a flat-resolving billion-edge build must not pay multi-GB
    of discarded plan arrays for a number).

    ``pagemajor=True`` additionally runs the PAGE-MAJOR counting pass
    (one more payload-free sort) and records its gather/virtual row
    stats under ``pm_*`` keys — the inputs of the three-way auto
    arbitration (scalemodel.pagemajor_gather_ns)."""
    from lux_tpu.ops.pairs import quantize_depths

    if sg.local_parts is not None:
        raise ValueError("paged gather does not support multi-host "
                         "local-parts builds yet")
    if sg.vpad % W:
        raise ValueError("paged gather needs vpad % 128 == 0; build "
                         "the ShardedGraph with vpad_align=128")
    owner = exchange == "owner"
    ntp = sg.vpad // W                        # tiles per part
    if owner:
        n_dst_tiles = sg.num_parts * ntp
        n_src_rows = ntp
        pm_tiles = ntp
        parts = [(srcl, gt, (gt % ntp) * W + rel, gt // ntp)
                 for srcl, gt, rel, _w in _owner_part_edges(sg)]
    else:
        n_dst_tiles = ntp
        n_src_rows = sg.num_parts * sg.vpad // W
        pm_tiles = ntp
        parts = []
        for r in range(sg.num_parts):
            nep = int(sg.ne_part[r])
            dl = sg.dst_local[r, :nep].astype(np.int64)
            parts.append((sg.src_slot[r, :nep], dl // W, dl, None))
    P = len(parts)
    prof = np.zeros(n_dst_tiles, np.int64)
    rows_real = unique_total = 0
    pm_prof = np.zeros(pm_tiles, np.int64)
    pm_vrows = pm_grows = 0
    pm_max_sd = 0
    # owner pm: virtual rows per (dst part, tile) ACCUMULATE across
    # source parts (a dst tile receives rows from every sender)
    pm_bt_d = np.zeros((sg.num_parts, pm_tiles), np.int64)
    for src_idx, dst_tile, dst_local, group in parts:
        by_tile, n_rows, uniq = _part_bin_stats(
            src_idx, dst_tile, n_dst_tiles, n_src_rows)
        prof = np.maximum(prof, np.sort(by_tile)[::-1])
        rows_real += n_rows
        unique_total += uniq
        if pagemajor:
            if owner:
                if len(src_idx):
                    Lm = _pm_layout(src_idx, dst_local, pm_tiles,
                                    n_src_rows, group)
                    vg = Lm["g_group"][Lm["vrow_gr"]]
                    np.add.at(pm_bt_d, (vg, Lm["vrow_tile"]), 1)
                    pm_vrows += len(Lm["vb"])
                    pm_grows += Lm["Rg"]
                    pm_max_sd = max(pm_max_sd, int(np.bincount(
                        Lm["g_group"],
                        minlength=sg.num_parts).max()))
            else:
                bt, nv_rows, ng_rows, _bg = _part_bin_stats_pm(
                    src_idx, dst_local, pm_tiles, n_src_rows)
                pm_prof = np.maximum(pm_prof, np.sort(bt)[::-1])
                pm_vrows += nv_rows
                pm_grows += ng_rows
                # the built plan pads every part's gather rows to the
                # per-part MAX (the _assemble_pm Rg) — the priced
                # g_fill must see the padded count or auto would
                # engage page-major optimistically on part-skewed
                # graphs
                pm_max_sd = max(pm_max_sd, ng_rows)
    if pagemajor and owner:
        for d in range(sg.num_parts):
            pm_prof = np.maximum(pm_prof, np.sort(pm_bt_d[d])[::-1])
    Rtot = int(np.cumsum(quantize_depths(prof))[-1]) if n_dst_tiles \
        else 0
    Rp = _pad8_distinct(Rtot, n_src_rows)
    ne = int(sg.ne)
    stats = dict(
        ne=ne, rows=rows_real, fill=ne / max(rows_real, 1),
        unique_pages=unique_total,
        page_ratio=unique_total * W / max(ne, 1),
        padded_fill=ne / max(P * Rp, 1),
        lane_inflation=P * Rp * W / max(ne, 1))
    if pagemajor:
        pm_Rtot = int(np.cumsum(quantize_depths(pm_prof))[-1]) \
            if pm_tiles else 0
        # receiver plans lead with DST parts (= num_parts) in owner
        # mode; dense pm plans with the same P as the paged plan
        pm_P = sg.num_parts if owner else P
        pm_Rp = _pad8_distinct(pm_Rtot, n_src_rows)
        if owner:
            Mg = max(8, -(-max(pm_max_sd, 1) // 8) * 8)
            pm_Rg_total = sg.num_parts * sg.num_parts * Mg
        else:
            # mirror _assemble_pm exactly: every part pads to the
            # max part's gather-row count (pad8, table-distinct)
            pm_Rg_total = P * _pad8_distinct(max(pm_max_sd, 1),
                                             n_src_rows)
        stats.update(
            pm_rows=pm_vrows,
            pm_vfill=ne / max(pm_vrows, 1),
            pm_padded_vfill=ne / max(pm_P * pm_Rp, 1),
            pm_g_rows=pm_grows,
            pm_g_fill=ne / max(pm_grows, 1),
            pm_g_padded_fill=ne / max(pm_Rg_total, 1))
    return stats


def plan_paged_gather(sg) -> PagedPlan:
    """Dense-engine plan: one part per row, pages of the FULL
    ``[num_parts * vpad]`` flat state table, destination tiles the
    part's own ``vpad // 128``.  Requires vpad % 128 == 0 (build the
    ShardedGraph with vpad_align=128, like pair delivery)."""
    if sg.local_parts is not None:
        raise ValueError("paged gather does not support multi-host "
                         "local-parts builds yet")
    if sg.vpad % W:
        raise ValueError("paged gather needs vpad % 128 == 0; build "
                         "the ShardedGraph with vpad_align=128")
    n_src_rows = sg.num_parts * sg.vpad // W
    n_dst_tiles = sg.vpad // W
    parts = []
    for r in range(sg.num_parts):
        nep = int(sg.ne_part[r])
        dst = sg.dst_local[r, :nep].astype(np.int64)
        wp = (np.asarray(sg.edge_weight[r, :nep]) if sg.weighted
              else None)
        parts.append(_part_rows(sg.src_slot[r, :nep], dst // W,
                                dst % W, n_dst_tiles, n_src_rows, wp))
    return _assemble(parts, n_dst_tiles, n_src_rows, int(sg.ne),
                     sg.weighted)


def _owner_part_edges(sg):
    """Edges regrouped per SOURCE part (the owner layout's
    src-part-major view, ops/owner.OwnerLayout.build): yields one
    (src_local, global_dst_tile, rel, weight) tuple per src part."""
    P, vpad = sg.num_parts, sg.vpad
    n_tiles_part = vpad // W
    sp_l, srcl_l, gt_l, rel_l, w_l = [], [], [], [], []
    for r in range(P):
        nep = int(sg.ne_part[r])
        slot = sg.src_slot[r, :nep].astype(np.int64)
        s = slot // vpad
        dst = sg.dst_local[r, :nep].astype(np.int64)
        sp_l.append(s)
        srcl_l.append(slot - s * vpad)
        gt_l.append(r * n_tiles_part + dst // W)
        rel_l.append(dst % W)
        if sg.weighted:
            w_l.append(np.asarray(sg.edge_weight[r, :nep]))
    sp = np.concatenate(sp_l) if sp_l else np.zeros(0, np.int64)
    srcl = np.concatenate(srcl_l) if srcl_l else np.zeros(0, np.int64)
    gt = np.concatenate(gt_l) if gt_l else np.zeros(0, np.int64)
    rel = np.concatenate(rel_l) if rel_l else np.zeros(0, np.int64)
    wall = np.concatenate(w_l) if w_l else None
    for s in range(P):
        m = sp == s
        yield (srcl[m], gt[m], rel[m],
               wall[m] if wall is not None else None)


def plan_owner_paged(sg) -> PagedPlan:
    """Owner-exchange plan: one row per SOURCE part, pages within the
    part's OWN ``[vpad]`` state shard, destination tiles GLOBAL
    (G = num_parts * vpad // 128) — the paged form of the owner
    layout's src-part-major re-lay (ops/owner.OwnerLayout.build).
    Each generation-scan step then runs ``paged_partial`` against one
    shard and contributes ``[G, 128]`` tile partials."""
    if sg.local_parts is not None:
        raise ValueError("paged gather does not support multi-host "
                         "local-parts builds yet")
    if sg.vpad % W:
        raise ValueError("paged gather needs vpad % 128 == 0; build "
                         "the ShardedGraph with vpad_align=128")
    G = sg.num_parts * sg.vpad // W
    n_src_rows = sg.vpad // W
    parts = [_part_rows(srcl, gt, rel, G, n_src_rows, w)
             for srcl, gt, rel, w in _owner_part_edges(sg)]
    return _assemble(parts, G, n_src_rows, int(sg.ne), sg.weighted)


def engine_page_plan(sg, gather: str, program,
                     exchange: str) -> PagedPlan | None:
    """The engines' shared plan-or-not resolution: build the paged or
    page-major plan (owner- or dense-shaped by ``exchange``) and
    resolve ``gather`` via ``resolve_gather``.  Returns the plan when
    a page-binned path engages, None when the flat gather stays; an
    explicit ``gather="paged"``/``"pagemajor"`` raises on unsupported
    configurations while ``"auto"`` silently stays flat."""
    dot = getattr(program, "edge_value_from_dot", None) is not None
    explicit = gather in ("paged", "pagemajor")
    why = None
    if getattr(program, "needs_dst", False) and not dot:
        why = ("programs reading destination state (needs_dst "
               "without edge_value_from_dot) keep the flat gather")
    elif sg.local_parts is not None:
        why = "multi-host local-parts builds are not paged yet"
    elif sg.vpad % W:
        why = ("paged gather needs vpad % 128 == 0; build the "
               "ShardedGraph with vpad_align=128")
    elif gather == "pagemajor" and dot:
        why = ("page-major rows split the reduce from the MXU dot "
               "pipeline; K-dim (SDDMM) programs keep gather='paged'")
    if why is not None:
        if explicit:
            raise ValueError(f"gather={gather!r}: {why}")
        return None
    if gather == "auto":
        # resolve from the COUNTING pass only — a flat-resolving
        # build must not pay the full [P, Rp, 128] plan-array
        # assembly (multi-GB at billion-edge scale) for two numbers
        itemsize = getattr(program, "state_bytes", None)
        if itemsize is None:
            ident = getattr(program, "identity", None)
            itemsize = (np.asarray(ident).dtype.itemsize
                        if ident is not None else 4)
            itemsize *= getattr(program, "batch", None) or 1
        table = sg.num_parts * sg.vpad * itemsize
        kdim = 1
        if dot:
            sb = getattr(program, "state_bytes", None)
            kdim = max(1, (sb or 4) // 4)
        stats = plan_paged_stats(sg, exchange=exchange,
                                 pagemajor=not dot)
        gather = resolve_gather("auto", stats, table, kdim,
                                exchange=exchange)
        if gather == "flat":
            return None
    if gather == "pagemajor":
        return (plan_owner_pagemajor(sg) if exchange == "owner"
                else plan_pagemajor(sg))
    return (plan_owner_paged(sg) if exchange == "owner"
            else plan_paged_gather(sg))


def resolve_gather(gather: str, stats: dict, table_bytes: int,
                   kdim: int = 1, exchange: str = "gather") -> str:
    """'auto' resolves by the scalemodel break-even on the plan's
    MEASURED unique-page ratio and row fills (R-MAT vs real-graph
    ratios differ, which is why the plan records them): a page-binned
    mode wins when its modeled delivered ns/edge undercuts what the
    SAME engine would otherwise run — the flat gather rate for this
    table size (scalemodel.page_gather_ns / flat_gather_ns), or, for
    ``exchange="owner"`` engines, the owner scan's per-slot rate
    (OWNER_SLOT_NS x the default chunk inflation, the same baseline
    scalemodel.phase_model prices the flat owner delivery at) —
    comparing an owner plan against the flat-gather cliff rate would
    flip paged on in exactly the 11.9-14.6 ns window where the owner
    scan is cheaper.  When the stats carry the page-major counting
    (``pm_*`` keys, scalar programs only) the arbitration is
    THREE-way: flat vs paged vs page-major, the latter priced with
    its split gather/virtual rates plus the routing hop
    (scalemodel.pagemajor_gather_ns)."""
    if gather in ("paged", "flat", "pagemajor"):
        return gather
    if gather != "auto":
        raise ValueError(f"unknown gather {gather!r} (one of 'paged',"
                         f" 'pagemajor', 'flat', 'auto')")
    from lux_tpu import scalemodel
    paged = scalemodel.page_gather_ns(
        stats["page_ratio"], stats.get("padded_fill", stats["fill"]),
        kdim)
    if exchange == "owner":
        baseline = scalemodel.OWNER_SLOT_NS * 1.2
    elif kdim > 1:
        baseline = scalemodel.residual_edge_ns(kdim)
    else:
        baseline = scalemodel.flat_gather_ns(table_bytes)
    best, best_ns = "flat", baseline
    if paged < best_ns:
        best, best_ns = "paged", paged
    if kdim <= 1 and "pm_padded_vfill" in stats:
        pm = scalemodel.pagemajor_gather_ns(
            stats["page_ratio"], stats["pm_g_padded_fill"],
            stats["pm_padded_vfill"], routed=exchange == "owner")
        if pm < best_ns:
            best = "pagemajor"
    return best


# ---------------------------------------------------------------------
# device side
# ---------------------------------------------------------------------


def _shuffle_kernel(rows_ref, sl_ref, out_ref):
    import jax.numpy as jnp

    # decode inside the kernel: the lane field is the low 7 bits of
    # the packed uint32; Mosaic's dynamic_gather wants int32 indices
    lane = (sl_ref[:] & jnp.uint32(0x7F)).astype(jnp.int32)
    out_ref[:] = jnp.take_along_axis(rows_ref[:], lane, axis=1)


def _lane_shuffle_pallas(rows, slot_lane, block_r: int = 512,
                         interpret: bool = False):
    """[R, 128] lane shuffle as a Pallas kernel — ``take_along_axis``
    axis=1 lowers to ``tpu.dynamic_gather`` dim 1, the measured 0.38
    ns/elem primitive (PERF_NOTES round 2).
    R must be a multiple of 8 (PagedPlan pads to this)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from lux_tpu.parallel.mesh import vma_of

    R, Wd = rows.shape
    bm = block_r if R % block_r == 0 else 8
    return pl.pallas_call(
        _shuffle_kernel,
        grid=(R // bm,),
        in_specs=[
            pl.BlockSpec((bm, Wd), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, Wd), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, Wd), lambda b: (b, 0),
                               memory_space=pltpu.VMEM),
        # under shard_map the result varies over the inputs' mesh
        # axes; the VMA check needs it stated (see pallas_reduce.py)
        out_shape=jax.ShapeDtypeStruct((R, Wd), rows.dtype,
                                       vma=vma_of(rows, slot_lane)),
        interpret=interpret,
    )(rows, slot_lane)


def lane_resolve(rows, slot_lane, reduce_method: str = "xla"):
    """Resolve each lane's value within its row's page:
    ``out[r, c] = rows[r, lane[r, c]]``.  Pallas kernel for scalar
    rows under a pallas reduce_method; plain XLA ``take_along_axis``
    otherwise (the CPU formulation, and the vector/batched payload
    path — Mosaic's dynamic_gather is 2D)."""
    import jax.numpy as jnp

    if (reduce_method.startswith("pallas") and rows.ndim == 2
            and rows.shape[0] % 8 == 0):
        return _lane_shuffle_pallas(
            rows, slot_lane,
            interpret=reduce_method == "pallas-interpret")
    lane = (slot_lane & jnp.uint32(0x7F)).astype(jnp.int32)
    lane = lane.reshape(lane.shape + (1,) * (rows.ndim - 2))
    return jnp.take_along_axis(rows, lane, axis=1)


def paged_values(pp: PagedPlan, flat_state, page_ids, slot_lane,
                 reduce_method: str = "xla"):
    """The two-level gather itself: unique-page row fetch (THE
    state-table access), buffer row fetch, lane shuffle.  Returns the
    delivered values ``[Rp, 128, ...]``."""
    import jax
    import jax.numpy as jnp

    trail = flat_state.shape[1:]
    s2d = flat_state.reshape((-1, W) + trail)
    pages = jnp.take(s2d, page_ids, axis=0)          # [n_pages, 128, .]
    row_slot = jax.lax.shift_right_logical(
        slot_lane[:, 0], jnp.uint32(7)).astype(jnp.int32)
    rows = jnp.take(pages, row_slot, axis=0)         # [Rp, 128, ...]
    return lane_resolve(rows, slot_lane, reduce_method)


def paged_partial(pp: PagedPlan, flat_state, page_ids, slot_lane, rel,
                  weight, tile_pos, kind: str, msg_fn,
                  reduce_method: str = "xla", vrow_src=None):
    """Full paged delivery + reduce for ONE part ->
    ``[n_tiles * 128, ...]`` partial (identity where no row delivers).
    msg_fn(vals [Rp, 128, ...], weight [Rp, 128] | None) -> messages;
    dead lanes carry garbage masked by rel == -1 downstream.

    ``vrow_src`` (page-major plans): the gather level ran over FULL
    page-bound rows (``slot_lane`` holds the Rg gather rows); each
    virtual reduce row materializes by one row-granular ``take`` of
    the delivered values — the 24 ns/row static class, not a second
    state-table access (the take's operand is the [Rg, 128] value
    buffer, shape-distinct from the table by _pad8_distinct)."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.pairs import _class_combine
    from lux_tpu.ops.tiled import chunk_partials

    vals = paged_values(pp, flat_state, page_ids, slot_lane,
                        reduce_method)
    if vrow_src is not None:
        vals = jnp.take(vals, vrow_src, axis=0)      # [Rp, 128, ...]
    msgs = msg_fn(vals, weight)
    if reduce_method.startswith("pallas") and msgs.ndim == 2:
        from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
        partials = chunk_partials_pallas(
            msgs, rel, W, kind,
            block_c=64 if msgs.shape[0] % 64 == 0 else 8,
            interpret=reduce_method == "pallas-interpret")
    else:
        # keep the shuffle/gather out of the W-wide broadcast consumer
        # on the XLA path (the PullEngine._part_msgs barrier rationale)
        msgs = jax.lax.optimization_barrier(msgs)
        partials = chunk_partials(msgs, rel, W, kind)
    red = _class_combine(pp, partials[:pp.R], tile_pos, kind)
    return red.reshape((pp.n_tiles * W,) + red.shape[2:])


def paged_partial_dot(pp: PagedPlan, state, page_ids, slot_lane, rel,
                      weight, row_tile, tile_pos, part_tile0,
                      msg_dot_fn, block_rows: int = 256):
    """Paged delivery for VECTOR-state dot programs (colfilter's
    SDDMM, PullProgram.edge_value_from_dot) — pair_partial_dot's MXU
    pipeline with one extra one-hot shuffle matmul resolving each
    lane's source row within the fetched page block:

      P  = page block [128, K]      (one reshaped-row fetch from the
                                     deduplicated page buffer)
      S  = onehot(lane) @ P         (the lane shuffle as an MXU
                                     contraction — 128-way selection
                                     costs about one shuffle,
                                     PERF_NOTES round 2)
      T  = dst tile block [128, K]
      D  = S @ T^T; dot[c] = D[c, rel[c]]; msgs = msg_dot_fn(S, dot, w)
      partial = onehot(rel)^T @ msgs

    Rows are processed in ``block_rows`` lax.map blocks to bound the
    [B, 128, 128] intermediates.  Returns [n_tiles * 128, K] sums."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.pairs import _class_combine, dot_precision

    if weight is None:
        raise ValueError("paged_partial_dot needs per-lane weights")
    Kdim = state.shape[-1]
    s3 = state.reshape(-1, W * Kdim)
    pages = jnp.take(s3, page_ids, axis=0)       # [n_pages, 128*K]
    Rp = slot_lane.shape[0]
    B = max(1, min(block_rows, Rp))
    nB = -(-Rp // B)
    Rpp = nB * B

    def pad(x):
        return jnp.pad(x, ((0, Rpp - Rp),) + ((0, 0),) * (x.ndim - 1))

    lanes32 = jnp.arange(W, dtype=jnp.int32)
    lanes8 = jnp.arange(W, dtype=rel.dtype)

    def block(args):
        sl, rl, wt, rt = args
        rs = jax.lax.shift_right_logical(
            sl[:, 0], jnp.uint32(7)).astype(jnp.int32)
        Pg = jnp.take(pages, rs, axis=0).reshape(-1, W, Kdim)
        lane = (sl & jnp.uint32(0x7F)).astype(jnp.int32)
        sel = (lane[..., None] == lanes32).astype(state.dtype)
        S = jnp.einsum("rcl,rlk->rck", sel, Pg,
                       preferred_element_type=state.dtype,
                       precision=dot_precision(state.dtype))
        # dst-tile block fetch: row-granular [*, 128K] movement (the
        # 24 ns/row static class) — the SAME fetch pair_partial_dot
        # makes, exempt there because its operand shape differs from
        # the flat table; here the paged table view shares the shape,
        # so the exemption is explicit:
        # audit: allow(gather-budget)
        T = jnp.take(s3, part_tile0 + rt, axis=0).reshape(-1, W, Kdim)
        D = jnp.einsum("rck,rwk->rcw", S, T,
                       preferred_element_type=S.dtype,
                       precision=dot_precision(S.dtype))
        mask = rl[..., None] == lanes8               # [B, 128, 128]
        dot = jnp.sum(jnp.where(mask, D, 0), axis=-1)
        msgs = msg_dot_fn(S, dot, wt)                # [B, 128, K]
        # dead lanes (rel == -1) match no output lane -> contribute 0
        return jnp.einsum("rcw,rck->rwk", mask.astype(S.dtype), msgs,
                          precision=dot_precision(msgs.dtype))

    partials = jax.lax.map(
        block, (pad(slot_lane).reshape(nB, B, W),
                pad(rel).reshape(nB, B, W),
                pad(weight).reshape(nB, B, W),
                pad(row_tile).reshape(nB, B)))
    partials = partials.reshape(Rpp, W, Kdim)[:pp.R]
    red = _class_combine(pp, partials, tile_pos, "sum")
    return red.reshape(-1, Kdim)


# graph-array dict keys the paged OWNER generation scan consumes
# (leading dim = local src-part rows, like ops/owner.OWNER_SCAN_KEYS)
PAGED_OWNER_KEYS = ("own_pg_ids", "own_pg_sl", "own_pg_rel",
                    "own_pg_w", "own_pg_tp")

# page-major owner routing (round 16): SENDER keys ride the
# generation scan (leading dim = local SRC parts); RECEIVER keys are
# consumed after the all_to_all routing hop (leading dim = local DST
# parts)
PAGEMAJOR_OWNER_SEND_KEYS = ("own_pm_ids", "own_pm_gsl", "own_pm_w")
PAGEMAJOR_OWNER_RECV_KEYS = ("own_pm_vrs", "own_pm_rel", "own_pm_tp")


def plan_graph_arrays(pp: PagedPlan, dev, owner: bool, dot: bool,
                      num_parts: int, vpad: int) -> dict:
    """The plan's per-part graph arrays for an engine's array dict
    (leading dim num_parts; owner plans lead with SOURCE parts —
    page-major owner plans split sender/receiver key sets, both
    leading with num_parts so they shard identically)."""
    if owner and pp.mode == "pagemajor":
        arrays = {"own_pm_ids": dev(pp.page_ids),
                  "own_pm_gsl": dev(pp.slot_lane),
                  "own_pm_vrs": dev(pp.vrow_src),
                  "own_pm_rel": dev(pp.rel_dst),
                  "own_pm_tp": dev(pp.tile_pos)}
        if pp.weight is not None:
            arrays["own_pm_w"] = dev(pp.weight)
        return arrays
    pre = "own_pg_" if owner else "pg_"
    arrays = {pre + "ids": dev(pp.page_ids),
              pre + "sl": dev(pp.slot_lane),
              pre + "rel": dev(pp.rel_dst),
              pre + "tp": dev(pp.tile_pos)}
    if pp.weight is not None:
        arrays[pre + "w"] = dev(pp.weight)
    if not owner and pp.vrow_src is not None:
        arrays["pg_vrs"] = dev(pp.vrow_src)
    if not owner and dot:
        # the paged SDDMM path also fetches each row's dst tile
        arrays["pg_rt"] = dev(pp.row_tile)
        arrays["pg_t0"] = dev(
            (np.arange(num_parts) * (vpad // W)).astype(
                np.int32)[:, None])
    return arrays


def paged_owner_contribs(pp: PagedPlan, state_rows, g: dict, kind: str,
                         msg_fn, msg_dtype, num_parts: int,
                         reduce_method: str):
    """lax.scan over the locally-held SOURCE parts, each step running
    the paged delivery against ONE [vpad, ...] state shard (the shard
    reshapes to its own [vpad/128, 128, ...] page table — the scan
    keeps the XLA emitter at the small-table rate exactly like
    ops/owner.owner_contribs) and folding its [G, W] global-tile
    partials into the accumulated per-destination-part contribution
    ``[num_parts, n_tiles*W, ...]``."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.segment import identity_for
    from lux_tpu.ops.tiled import combine_op
    from lux_tpu.parallel.mesh import vary_like

    ntw = pp.n_tiles * W // num_parts
    comb = combine_op(kind)
    xs = {k: g[k] for k in PAGED_OWNER_KEYS if k in g}

    def step(acc, x):
        st_s, d = x
        tiles = paged_partial(
            pp, st_s, d["own_pg_ids"], d["own_pg_sl"], d["own_pg_rel"],
            d.get("own_pg_w"), d["own_pg_tp"], kind, msg_fn,
            reduce_method)
        contrib = tiles.reshape((num_parts, ntw) + tiles.shape[1:])
        return comb(acc, contrib), None

    acc0 = jnp.full((num_parts, ntw) + state_rows.shape[2:],
                    identity_for(kind, msg_dtype), msg_dtype)
    acc, _ = jax.lax.scan(step, vary_like(acc0, state_rows, xs),
                          (state_rows, xs))
    return acc


def pagemajor_owner_deliver(pp: PagedPlan, state_rows, g: dict,
                            kind: str, msg_fn, msg_dtype,
                            num_parts: int, reduce_method: str,
                            axis=None):
    """The PAGE-MAJOR owner delivery, routing included: a lax.scan
    over the locally-held SOURCE parts runs the full-fill gather-row
    pipeline against each shard's own page table and emits COMPLETE
    message rows grouped by destination part (weights applied
    sender-side); one ``all_to_all`` over the mesh axis routes each
    destination part its ``[P_src, Mg]`` row block (the owner
    exchange's routing collective, ops/owner.owner_exchange — here
    carrying un-reduced full rows instead of reduced partials, the
    priced trade: scalemodel.pagemajor_route_ns); each local
    DESTINATION part then reduces its received buffer through its
    virtual-row plan.  Returns ``[local_parts, n_tiles * 128, ...]``
    — already routed, no further exchange."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.pairs import _class_combine
    from lux_tpu.ops.tiled import chunk_partials

    Mg = pp.route
    xs = {k: g[k] for k in PAGEMAJOR_OWNER_SEND_KEYS if k in g}

    def step(c, x):
        st_s, d = x
        vals = paged_values(pp, st_s, d["own_pm_ids"],
                            d["own_pm_gsl"], reduce_method)
        msgs = msg_fn(vals, d.get("own_pm_w")).astype(msg_dtype)
        return c, msgs

    _, msgs = jax.lax.scan(step, None, (state_rows, xs))
    # msgs [L_src, P_dst * Mg, 128, ...] -> route whole rows
    L = msgs.shape[0]
    m = msgs.reshape((L, num_parts, Mg) + msgs.shape[2:])
    if axis is None:
        recv = jnp.swapaxes(m, 0, 1)       # [P_dst, P_src, Mg, ...]
    else:
        recv = jax.lax.all_to_all(m, axis, split_axis=1,
                                  concat_axis=0, tiled=True)
        recv = jnp.swapaxes(recv, 0, 1)    # [L_dst, P_src, Mg, ...]

    def reduce_part(rows_sd, d):
        rb = rows_sd.reshape((-1,) + rows_sd.shape[2:])  # [P*Mg, 128]
        vals = jnp.take(rb, d["own_pm_vrs"], axis=0)     # [Rp, 128]
        if reduce_method.startswith("pallas") and vals.ndim == 2:
            from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
            partials = chunk_partials_pallas(
                vals, d["own_pm_rel"], W, kind,
                block_c=64 if vals.shape[0] % 64 == 0 else 8,
                interpret=reduce_method == "pallas-interpret")
        else:
            vals = jax.lax.optimization_barrier(vals)
            partials = chunk_partials(vals, d["own_pm_rel"], W, kind)
        red = _class_combine(pp, partials[:pp.R], d["own_pm_tp"],
                             kind)
        return red.reshape((pp.n_tiles * W,) + red.shape[2:])

    rkeys = {k: g[k] for k in PAGEMAJOR_OWNER_RECV_KEYS if k in g}
    return jax.vmap(reduce_part)(recv, rkeys)


# ---------------------------------------------------------------------
# NumPy oracles
# ---------------------------------------------------------------------


def decode_plan(pp: PagedPlan, p: int):
    """Decode part ``p``'s live lanes back to (src index, dst index)
    pairs — the plan-resolution oracle's view: src = page_ids[slot] *
    128 + lane, dst = row_tile * 128 + rel.  Page-major plans decode
    through the virtual row's gather row (``vrow_src``); the OWNER
    page-major plan's vrow_src indexes the routed buffer and is
    decoded by ``decode_pagemajor_owner`` instead."""
    if pp.mode == "pagemajor" and pp.route:
        raise ValueError("owner page-major plans decode via "
                         "decode_pagemajor_owner (vrow_src indexes "
                         "the routed buffer, not this part's rows)")
    sl = pp.slot_lane[p]
    rel = pp.rel_dst[p]
    live = rel != -1
    rows, cols = np.nonzero(live)
    gr = (pp.vrow_src[p][rows].astype(np.int64)
          if pp.vrow_src is not None else rows)
    slot = (sl[gr, 0] >> np.uint32(7)).astype(np.int64)
    lane = (sl[gr, cols] & np.uint32(0x7F)).astype(np.int64)
    src = pp.page_ids[p][slot].astype(np.int64) * W + lane
    dst = pp.row_tile[p][rows].astype(np.int64) * W \
        + rel[rows, cols].astype(np.int64)
    return src, dst


def decode_pagemajor_owner(pp: PagedPlan, d: int):
    """Decode DESTINATION part ``d``'s live lanes of an owner
    page-major plan back to (src part, src local index, local dst
    index) — vrow_src indexes the routed ``[P_src * Mg]`` buffer, so
    the sender and its gather row recover as divmod(vrow_src, Mg)."""
    if not (pp.mode == "pagemajor" and pp.route):
        raise ValueError("not an owner page-major plan")
    Mg = pp.route
    rel = pp.rel_dst[d]
    live = rel != -1
    rows, cols = np.nonzero(live)
    buf = pp.vrow_src[d][rows].astype(np.int64)
    s = buf // Mg                       # source part
    j = buf % Mg                        # row within the (s -> d) block
    send_row = d * Mg + j               # its slot in s's send layout
    sl = pp.slot_lane[s, send_row]      # [n, 128]
    slot = (sl[:, 0] >> np.uint32(7)).astype(np.int64)
    lane = (sl[np.arange(len(rows)), cols]
            & np.uint32(0x7F)).astype(np.int64)
    src_local = pp.page_ids[s, slot].astype(np.int64) * W + lane
    dst_local = pp.row_tile[d][rows].astype(np.int64) * W \
        + rel[rows, cols].astype(np.int64)
    return s, src_local, dst_local


def paged_reduce_numpy(pp: PagedPlan, p: int, state_flat: np.ndarray,
                       kind: str = "sum", msg=None) -> np.ndarray:
    """Oracle for one part of a paged plan -> [n_tiles * 128] partial
    (identity where no row delivers).  msg(vals [Rp, 128], weight)
    maps delivered values to messages; default passes them through.
    Padding (rel == -1, dead rows) contributes the identity."""
    if pp.mode == "pagemajor" and pp.route:
        # owner page-major vrow_src indexes the ROUTED [P_src * Mg]
        # buffer, not this part's own send rows — the same guard as
        # decode_plan, or the oracle would silently reduce the wrong
        # rows
        raise ValueError("owner page-major plans have no single-part "
                         "reduce oracle (vrow_src indexes the routed "
                         "buffer); compare whole engines instead")
    s2d = np.asarray(state_flat, np.float64).reshape(-1, W)
    sl = pp.slot_lane[p]
    slot = (sl[:, 0] >> np.uint32(7)).astype(np.int64)
    lane = (sl & np.uint32(0x7F)).astype(np.int64)
    pages = s2d[pp.page_ids[p].astype(np.int64)]
    vals = np.take_along_axis(pages[slot], lane, axis=1)  # [Rg, 128]
    if pp.vrow_src is not None:
        # page-major: virtual reduce rows read their gather row's
        # delivered values (the device's row-granular take)
        vals = vals[pp.vrow_src[p].astype(np.int64)]      # [Rp, 128]
    wp = pp.weight[p] if pp.weight is not None else None
    if msg is not None:
        vals = msg(vals, wp)
    ident = {"sum": 0.0, "min": np.inf, "max": -np.inf}[kind]
    op = {"sum": np.add, "min": np.minimum, "max": np.maximum}[kind]
    out = np.full(pp.n_tiles * W, ident)
    rel = pp.rel_dst[p]
    for r in range(pp.Rp):
        t = int(pp.row_tile[p, r])
        for c in range(W):
            w = int(rel[r, c])
            if 0 <= w < W:
                out[t * W + w] = op(out[t * W + w], vals[r, c])
    return out


def paged_dot_numpy(pp: PagedPlan, p: int, state: np.ndarray,
                    part_tile0: int, msg_dot_fn) -> np.ndarray:
    """float64 oracle for one part of the paged SDDMM delivery
    (paged_partial_dot).  With integer-valued states/weights whose
    products stay under 2^24 this equals the f32 device result
    EXACTLY (the pair-dot oracle's order-independent-exactness trick,
    ops/pairs.stacked_pair_dot_numpy)."""
    s2 = np.asarray(state, np.float64)
    Kdim = s2.shape[-1]
    out = np.zeros((pp.n_tiles * W, Kdim))
    sl = pp.slot_lane[p]
    rel = pp.rel_dst[p]
    for r in range(pp.Rp):
        t = int(pp.row_tile[p, r])
        slot = int(sl[r, 0] >> np.uint32(7))
        page = int(pp.page_ids[p][slot])
        Pg = s2[page * W:(page + 1) * W]                    # [128, K]
        T = s2[(part_tile0 + t) * W:(part_tile0 + t + 1) * W]
        for c in range(W):
            w = int(rel[r, c])
            if not 0 <= w < W:
                continue
            lane = int(sl[r, c] & np.uint32(0x7F))
            S = Pg[lane]
            dot = S @ T[w]
            m = msg_dot_fn(S, dot, np.float64(pp.weight[p, r, c]))
            out[t * W + w] += np.asarray(m).reshape(Kdim)
    return out
