"""Tiled (chunked) edge layout and scatter-free segment reduction.

The portable ``ops.segment.segment_reduce`` lowers to an XLA scatter,
which TPUs execute (near-)serially — measured ~0.05 GTEPS on the hot
loop.  This module is the TPU-native replacement for the reference's
CUB BlockScan + atomic scatter CTA pattern (reference
pagerank_gpu.cu:49-102, SURVEY.md §3.3): the host re-lays each
partition's dst-sorted edges into fixed-shape chunks bound to output
vertex tiles, so the device-side reduction is nothing but dense,
static-shape VPU/MXU work plus one short segmented scan:

- Output vertices are grouped into tiles of ``W``; edges (already
  dst-sorted and therefore tile-contiguous) are padded so each tile
  owns a whole number of ``E``-edge chunks -> arrays ``[C, E]``.
- Within a chunk, every edge's destination is a *relative* index in
  ``[0, W)`` (``W`` marks padding lanes).  The chunk's partial result
  ``[W]`` is a masked broadcast-reduce (VPU) or a one-hot matmul (MXU)
  — both fuse in XLA, neither scatters.
- Chunks of the same tile are combined along the chunk axis
  (flag-reset, exact — no cumsum boundary-difference cancellation),
  then the last chunk of each tile is gathered (``combine_chunks``,
  scope ``lux_combine``).  Where the reduce method is ``pallas`` the
  combine is ONE sequential pass with a carry
  (ops/pallas_combine.segmented_combine_pallas); the portable ``xla``
  method keeps the segmented ``associative_scan`` (``_segscan``,
  blocked above SCAN_BLOCKED_ABOVE chunks) as the oracle, and the MXU
  sum keeps ``_segscan_matmul``.  Vector payloads are combined in the
  lane-dense order ``[C, K, W]`` and only the ``n_tiles`` rows that
  survive the last-chunk take are moved to ``[n_tiles, W, K]``.  When
  every tile fits in one chunk the combine is skipped statically.

Degree skew (the Twitter/RMAT power-law "hard part", SURVEY.md §7) is
absorbed by construction: a hub vertex simply owns many chunks, and
every chunk is the same shape — the TPU analogue of the reference's
edge-parallel load balancing.

Lane-aligned placement (``TiledLayout.build(aligned=True)``; the
delivery asks for it where the program is query-batched): the
destinations are ranked by in-degree and tile ``t`` holds ranks
``128 t .. 128 t + 127``, so a tile's destinations have almost the same
depth and slot ``e`` of a chunk can be given to lane ``e mod 128``
alone (a SELL-128-sigma sparse format).  Such a chunk needs no lane
compare: its partial is a fold over its ``E / 128`` depth rows
(``aligned_partials``, scope ``lux_aligned``).  The few tiles whose
depths differ too much (the hubs) keep the one-hot chunks above; the
result leaves in rank order and one row gather (``tile_rank``) puts it
back in vertex order.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu.ops.segment import identity_for
from lux_tpu.parallel.mesh import vary_like


def _ceil_div(a: int, b: int) -> int:
    return (a + b - 1) // b


def warn_sub128_tile(E: int) -> None:
    """Warn on chunk widths that leave the minor dim under the TPU
    tile: [.., C, E] edge arrays with E % 128 pad the minor dim to
    128 (2x HBM at E=64) AND the compiler inserts relayout copies of
    the whole arrays — measured as the difference between fitting and
    OOMing a 16 GB chip (PERF_NOTES round 4).  Shared by TiledLayout
    and OwnerLayout, which stack edges in the same shape."""
    if E % 128:
        import warnings
        warnings.warn(
            f"chunk width E={E} is not a multiple of 128: TPU tiled "
            f"layouts pad the minor dim to 128 and relayout-copy the "
            f"edge arrays (PERF_NOTES round 4); use multiples of 128",
            stacklevel=3)


# A tile of the degree-ranked order is lane-ALIGNED where its edges
# fill at least this share of its 128 x (largest in-degree) aligned
# slots, else it keeps one-hot chunks.  From the unit costs of a dense
# batched iteration on the chip (ksssp.kron20.closed; PERF.md section
# 6, PR 40): a slot costs the row gather's 6.38 ns either way
# (lux_relax, whatever order the slots are in), an aligned slot 0.13
# ns more (the fold over depth) and a one-hot slot 5.2 ns more (the
# compare-reduce and the relayout copy that feeds it), so the aligned
# placement of a tile with n edges and depth D is the cheaper one
# while 128 D x 6.51 <= n x 11.58: n / (128 D) >= 0.56.  The ledger's
# figures before the first run gave 0.55 and the layout is the same
# from 0.3 to 0.55 on that graph, so 0.55 stands.  Read at call time,
# so tests can move it.
ALIGNED_MIN_FILL = 0.55


class _RankedPart(NamedTuple):
    """One part's destinations by in-degree rank (stable, descending),
    padded to whole tiles, and the chunks each tile takes."""
    order: np.ndarray       # int64 [vpad] the vertex of each rank
    deg: np.ndarray         # int64 [n_tiles * W] in-degree by rank
    lo: np.ndarray          # int64 [n_tiles * W] first in-edge by rank
    total: np.ndarray       # int64 [n_tiles] edges of each tile
    n_aligned: np.ndarray   # int64 [n_tiles] aligned chunks (0 = none)
    n_onehot: np.ndarray    # int64 [n_tiles] one-hot chunks (0 = none)


@dataclasses.dataclass
class TiledLayout:
    """Host-side chunk plan for one partitioned graph (stacked over
    parts; all chunk arrays are ``[num_parts, C, ...]``)."""

    W: int                      # vertices per output tile
    E: int                      # edges per chunk
    n_tiles: int                # ceil(vpad / W), same for every part
    n_chunks: int               # padded chunk count C (max over parts)
    needs_scan: bool            # False when every tile fits in 1 chunk
    edge_gather: np.ndarray     # int64 [P, C, E] index into flat [epad]
    rel_dst: np.ndarray         # int8 [P, C, E] in [0, W); -1 = pad
                                #   lane (int8: quarters the second-
                                #   largest device array; valid values
                                #   are 0..127 and the pad marker only
                                #   needs to MATCH NO LANE, so -1
                                #   serves where W=128 cannot fit)
    chunk_tile: np.ndarray      # int32 [P, C] owning tile; n_tiles = pad
    chunk_start: np.ndarray     # bool  [P, C] True at each tile's 1st chunk
    last_chunk: np.ndarray      # int32 [P, n_tiles] index of tile's last
                                #   chunk, -1 for edge-less tiles
    # the lane-aligned placement (build(aligned=True)); today's layout
    # has none of it
    n_aligned: int = 0          # C_a: chunks [0, C_a) are lane-aligned
                                #   (slot e = lane e mod W), the rest
                                #   one-hot; max over parts
    tile_vertex: np.ndarray | None = None   # int32 [P, vpad] the vertex
                                #   of each rank (stable, by in-degree,
                                #   descending); None = vertex order
    tile_rank: np.ndarray | None = None     # int32 [P, vpad] its
                                #   inverse: the row of the tile-order
                                #   result that is vertex v's

    @classmethod
    def build(cls, row_ptr_local: np.ndarray, dst_local: np.ndarray,
              vpad: int, W: int = 128, E: int = 512,
              sizing_row_ptr: np.ndarray | None = None,
              aligned: bool = False) -> "TiledLayout":
        """row_ptr_local: int [P, vpad+1] END offsets; dst_local:
        int32 [P, epad] part-local sorted destinations (pad -> vpad).

        sizing_row_ptr: row_ptr_local rows of ALL parts, when
        ``row_ptr_local`` holds only a process's local parts — chunk
        count and scan-necessity are program SHAPE/structure and must
        be identical on every process of a multi-host run.

        aligned: the lane-aligned placement over the degree-ranked
        destinations (module docstring; ``_build_aligned``)."""
        if W > 128:
            raise ValueError(
                f"tile width W={W} > 128: rel_dst is int8 (valid lane "
                f"offsets 0..127, -1 = pad) and wider tiles would wrap "
                f"offsets >= 128 negative, silently dropping edges")
        warn_sub128_tile(E)
        if aligned:
            return cls._build_aligned(row_ptr_local, vpad, W, E,
                                      sizing_row_ptr)
        P = row_ptr_local.shape[0]
        n_tiles = max(1, _ceil_div(vpad, W))

        def tile_chunks(rp_row):
            rp = rp_row.astype(np.int64)
            tile_lo = rp[np.minimum(np.arange(n_tiles) * W, vpad)]
            tile_hi = rp[np.minimum((np.arange(n_tiles) + 1) * W, vpad)]
            n_ch = np.maximum(0, _ceil_div_arr(tile_hi - tile_lo, E))
            return tile_lo, tile_hi, n_ch

        per_part = [tile_chunks(row_ptr_local[p]) for p in range(P)]
        sizing = (per_part if sizing_row_ptr is None else
                  [tile_chunks(r) for r in sizing_row_ptr])

        # Pad the chunk count to the Pallas kernel's block granularity
        # (pad chunks are isolated identity segments, dropped by the
        # last-chunk gather).
        C = max(1, int(max(int(x[2].sum()) for x in sizing)))
        C = _ceil_div(C, 8) * 8
        global_needs_scan = any(x[2].max(initial=0) > 1 for x in sizing)

        edge_gather = np.zeros((P, C, E), dtype=np.int64)
        rel_dst = np.full((P, C, E), -1, dtype=np.int8)
        chunk_tile = np.full((P, C), n_tiles, dtype=np.int32)
        chunk_start = np.ones((P, C), dtype=bool)   # pad chunks isolated
        last_chunk = np.full((P, n_tiles), -1, dtype=np.int32)
        needs_scan = global_needs_scan

        lanes = np.arange(E, dtype=np.int64)
        for p in range(P):
            tile_lo, tile_hi, n_ch = per_part[p]
            nc = int(n_ch.sum())
            if nc == 0:
                continue
            # chunk -> owning tile, and chunk's index within that tile
            ct, cj = _tile_chunks(n_ch)
            start = tile_lo[ct] + cj * E
            idx = start[:, None] + lanes[None, :]          # [nc, E]
            valid = idx < tile_hi[ct][:, None]
            idx = np.where(valid, idx, 0)
            edge_gather[p, :nc] = idx
            rel_dst[p, :nc] = np.where(
                valid, dst_local[p][idx] - (ct * W)[:, None], -1)
            chunk_tile[p, :nc] = ct
            chunk_start[p, :nc] = cj == 0
            last_chunk[p] = np.where(n_ch > 0, np.cumsum(n_ch) - 1, -1)

        return cls(W=W, E=E, n_tiles=n_tiles, n_chunks=C,
                   needs_scan=needs_scan, edge_gather=edge_gather,
                   rel_dst=rel_dst, chunk_tile=chunk_tile,
                   chunk_start=chunk_start, last_chunk=last_chunk)

    @classmethod
    def _build_aligned(cls, row_ptr_local, vpad: int, W: int, E: int,
                       sizing_row_ptr) -> "TiledLayout":
        """The lane-aligned placement.  Per part the destinations are
        ranked by in-degree (stable, descending); tile ``t`` is ranks
        ``W t .. W t + W - 1``.  In an ALIGNED tile (ALIGNED_MIN_FILL)
        slot ``e`` of the tile's chunk ``j`` holds in-edge number
        ``(E / W) j + e div W`` of the destination in lane ``e mod W``
        (a destination's in-edges keep their source order down the
        depth axis), or a pad; a ONE-HOT tile lays its edges out in
        lane order, E at a time, as the default layout does.  Aligned
        chunks come first on the chunk axis, one-hot chunks after;
        both counts are padded to the kernels' block of 8."""
        if E % W:
            raise ValueError(
                f"the lane-aligned placement needs whole depth rows a "
                f"chunk: E={E} is no multiple of W={W}")
        R = E // W                          # depth rows a chunk
        P = row_ptr_local.shape[0]
        n_tiles = max(1, _ceil_div(vpad, W))
        min_fill = ALIGNED_MIN_FILL

        def plan(rp_row):
            rp = rp_row.astype(np.int64)
            deg = np.diff(rp)
            order = np.argsort(-deg, kind="stable")
            # by rank, padded to whole tiles: in-degree, first edge
            deg_r = np.zeros(n_tiles * W, np.int64)
            deg_r[:vpad] = deg[order]
            lo_r = np.zeros(n_tiles * W, np.int64)
            lo_r[:vpad] = rp[:-1][order]
            tiles = deg_r.reshape(n_tiles, W)
            total, depth = tiles.sum(axis=1), tiles[:, 0]
            is_al = (depth > 0) & (total >= min_fill * W * depth)
            return _RankedPart(
                order, deg_r, lo_r, total,
                n_aligned=np.where(is_al, _ceil_div_arr(depth, R), 0),
                n_onehot=np.where(is_al, 0, _ceil_div_arr(total, E)))

        plans = [plan(row_ptr_local[p]) for p in range(P)]
        sizing = (plans if sizing_row_ptr is None else
                  [plan(r) for r in sizing_row_ptr])
        Ca = _ceil_div(max(int(x.n_aligned.sum()) for x in sizing), 8) * 8
        Ch = _ceil_div(max(int(x.n_onehot.sum()) for x in sizing), 8) * 8
        if Ca + Ch == 0:
            Ch = 8
        C = Ca + Ch
        needs_scan = any(max(x.n_aligned.max(initial=0),
                             x.n_onehot.max(initial=0)) > 1
                         for x in sizing)

        edge_gather = np.zeros((P, C, E), dtype=np.int64)
        rel_dst = np.full((P, C, E), -1, dtype=np.int8)
        chunk_tile = np.full((P, C), n_tiles, dtype=np.int32)
        chunk_start = np.ones((P, C), dtype=bool)   # pad chunks isolated
        last_chunk = np.full((P, n_tiles), -1, dtype=np.int32)
        tile_vertex = np.zeros((P, vpad), dtype=np.int32)
        tile_rank = np.zeros((P, vpad), dtype=np.int32)

        slots = np.arange(E, dtype=np.int64)
        rows = np.arange(R, dtype=np.int64)
        lanes = np.arange(W, dtype=np.int8)
        for p in range(P):
            order, deg_r, lo_r, total, n_a, n_h = plans[p]
            tile_vertex[p] = order
            tile_rank[p, order] = np.arange(vpad, dtype=np.int32)
            na = int(n_a.sum())
            if na:
                ct, cj = _tile_chunks(n_a)
                # [na, R, W]: a chunk's depth rows over its tile's lanes
                depth = (cj * R)[:, None, None] + rows[None, :, None]
                valid = depth < deg_r.reshape(n_tiles, 1, W)[ct]
                edge_gather[p, :na] = np.where(
                    valid, lo_r.reshape(n_tiles, 1, W)[ct] + depth,
                    0).reshape(na, E)
                rel_dst[p, :na] = np.where(valid, lanes, -1).reshape(na, E)
                chunk_tile[p, :na] = ct
                chunk_start[p, :na] = cj == 0
            nh = int(n_h.sum())
            if nh:
                # the one-hot tiles' edges in lane order, end to end
                hub = np.nonzero(n_h)[0]
                ranks = (hub[:, None] * W +
                         np.arange(W, dtype=np.int64)).ravel()
                cnt = deg_r[ranks]
                off = np.cumsum(cnt) - cnt
                edge_of = (np.repeat(lo_r[ranks] - off, cnt) +
                           np.arange(int(cnt.sum()), dtype=np.int64))
                lane_of = np.repeat(ranks % W, cnt).astype(np.int8)
                ct, cj = _tile_chunks(n_h[hub])
                tile_lo = off[::W]
                pos = (tile_lo[ct] + cj * E)[:, None] + slots[None, :]
                valid = pos < (tile_lo + total[hub])[ct][:, None]
                pos = np.where(valid, pos, 0)
                edge_gather[p, Ca:Ca + nh] = np.where(
                    valid, edge_of[pos], 0)
                rel_dst[p, Ca:Ca + nh] = np.where(
                    valid, lane_of[pos], -1)
                chunk_tile[p, Ca:Ca + nh] = hub[ct]
                chunk_start[p, Ca:Ca + nh] = cj == 0
            last_chunk[p] = np.where(
                n_a > 0, np.cumsum(n_a) - 1,
                np.where(n_h > 0, Ca + np.cumsum(n_h) - 1, -1))

        return cls(W=W, E=E, n_tiles=n_tiles, n_chunks=C,
                   needs_scan=needs_scan, edge_gather=edge_gather,
                   rel_dst=rel_dst, chunk_tile=chunk_tile,
                   chunk_start=chunk_start, last_chunk=last_chunk,
                   n_aligned=Ca, tile_vertex=tile_vertex,
                   tile_rank=tile_rank)

    def chunk(self, flat: np.ndarray) -> np.ndarray:
        """Re-lay a per-part flat edge array [P, epad, ...] into chunk
        form [P, C, E, ...] (host, done once at build time)."""
        parts = np.arange(flat.shape[0])[:, None, None]
        return flat[parts, self.edge_gather]

    def counts(self) -> dict:
        """Edges and slots of the chunk arrays (the materialized
        parts'), whole layout and aligned chunks alone: the counts of
        the ``build.dense_layout`` span."""
        live = self.rel_dst >= 0
        P, C, E = self.rel_dst.shape
        return dict(tiled_edges=int(live.sum()),
                    aligned_edges=int(live[:, :self.n_aligned].sum()),
                    tiled_slots=P * C * E,
                    aligned_slots=P * self.n_aligned * E)


def _ceil_div_arr(a, b):
    return (a + b - 1) // b


def _tile_chunks(n_ch):
    """For tiles owning ``n_ch`` chunks each, laid end to end: every
    chunk's tile and its index within that tile (int64 [sum n_ch])."""
    ct = np.repeat(np.arange(len(n_ch), dtype=np.int64), n_ch)
    first = np.cumsum(n_ch) - n_ch
    return ct, np.arange(len(ct), dtype=np.int64) - first[ct]


def combine_op(kind: str):
    """The binary combiner for a reduce kind (shared lookup)."""
    return {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[kind]


_combine = combine_op


class MXUUnsupportedError(ValueError):
    """A (kind, dtype) combination the MXU contraction port does not
    cover.  After the round-23 port the one-hot paths serve sum, min
    and max over every <= 32-bit int/uint/float payload; what remains
    genuinely unsupported is named here so callers (and the auto
    resolver) can fall back to the VPU formulation deliberately
    instead of tripping an anonymous ValueError."""

    def __init__(self, kind: str, dtype, why: str):
        self.kind = kind
        self.dtype = np.dtype(dtype) if dtype is not None else None
        super().__init__(
            f"MXU one-hot path does not support kind={kind!r} on "
            f"dtype {self.dtype}: {why}")


def _exact_sum_precision(dtype):
    """The contraction precision of an MXU SUM against an exact 0/1
    int8 operand.  On the TPU a float32 operand of a default-precision
    contraction is rounded to bfloat16 (one pass: a relative error of
    2**-9 per term); HIGHEST keeps the float32 operand (the compiler
    splits it into bfloat16 parts, each exact against the 0/1
    operand), so the sum is the float32 one.  Integer payloads are
    exact as they are."""
    return (jax.lax.Precision.HIGHEST
            if np.dtype(dtype).kind == "f" else None)


def _lane_onehot(rel_dst, W: int):
    """int8 lane-membership matrix [..., E, W]: row e is one-hot at
    rel_dst[..., e] and ALL-ZERO for pad lanes (rel == -1 matches no
    lane) — int8 is the narrowest operand dtype the mixed-dtype MXU
    contraction accepts (`preferred_element_type` keeps the
    accumulator in the payload dtype), 4x narrower than the payload-
    dtype one-hot the round-5 sum path materialized."""
    return (rel_dst[..., None] ==
            jnp.arange(W, dtype=rel_dst.dtype)).astype(jnp.int8)


# Order-preserving bit encodings for the compare-reduce tournament:
# map the payload to uint bit patterns whose UNSIGNED order matches
# the payload order, so min/max become bitwise votes MSB-first.
_MXU_SIGN32 = jnp.uint32(0x80000000)


def _order_bits(dtype) -> int:
    """Tournament rounds for a payload dtype (bits of its order
    encoding); raises the typed error for unsupported combos."""
    dt = np.dtype(dtype)
    if dt.kind in "iu":
        return dt.itemsize * 8
    if dt.kind == "f":
        if dt.itemsize > 4:
            raise MXUUnsupportedError(
                "min/max", dt, "f64 violates the 4-byte dtype "
                "discipline (no order encoding fits uint32)")
        return dt.itemsize * 8
    raise MXUUnsupportedError(
        "min/max", dt, "no order-preserving bit encoding (only "
        "int/uint/float payloads reduce by comparison)")


def _order_encode(x):
    """Payload -> uint32 whose unsigned order matches the payload
    order.  Ints: two's-complement bias.  Floats: the IEEE-754
    sign-magnitude fold (negative -> flip all bits, else set the sign
    bit) — a TOTAL order agreeing with < on non-NaN values; -0.0
    sorts below +0.0 and NaN payloads are out of contract (the repo's
    oracles never produce them)."""
    dt = np.dtype(x.dtype)
    bits = _order_bits(dt)
    if dt.kind == "u":
        return x.astype(jnp.uint32)
    if dt.kind == "i":
        if dt.itemsize == 4:
            return jax.lax.bitcast_convert_type(
                x, jnp.uint32) ^ _MXU_SIGN32
        # narrow ints: bias into [0, 2^bits) in int32, then reinterpret
        lo = int(np.iinfo(dt).min)
        return (x.astype(jnp.int32) - lo).astype(jnp.uint32)
    # floats: fold via the same-width uint, then widen
    udt = {2: jnp.uint16, 4: jnp.uint32}[dt.itemsize]
    u = jax.lax.bitcast_convert_type(x, udt).astype(jnp.uint32)
    sign = jnp.uint32(1) << (bits - 1)
    mask = (jnp.uint32(0xFFFFFFFF) >> (32 - bits))
    return jnp.where((u & sign) != 0, (~u) & mask, u | sign)


def _order_decode(m, dtype):
    """Inverse of _order_encode (m uint32 -> payload dtype)."""
    dt = np.dtype(dtype)
    bits = _order_bits(dt)
    if dt.kind == "u":
        return m.astype(dt)
    if dt.kind == "i":
        if dt.itemsize == 4:
            return jax.lax.bitcast_convert_type(m ^ _MXU_SIGN32,
                                                jnp.int32)
        lo = int(np.iinfo(dt).min)
        return (m.astype(jnp.int32) + lo).astype(dt)
    sign = jnp.uint32(1) << (bits - 1)
    mask = (jnp.uint32(0xFFFFFFFF) >> (32 - bits))
    u = jnp.where((m & sign) != 0, m ^ sign, (~m) & mask)
    udt = {2: jnp.uint16, 4: jnp.uint32}[dt.itemsize]
    return jax.lax.bitcast_convert_type(u.astype(udt), dt)


def _mxu_compare_reduce(vals, rel_dst, W: int, kind: str):
    """min/max per-chunk reduction as one-hot MXU contractions: a
    radix tournament over the payload's order encoding, MSB first.
    Per bitplane, two contractions against the SHARED int8 one-hot
    lane-membership matrix: a vote (does any still-candidate lane of
    this dst slot carry the bit?) and the transposed route-back that
    narrows each lane's candidacy to the slot's winning prefix — the
    same forward/transpose pairing as the pair path's one-hot
    gradient matmul (ops/pairs.pair_partial_dot).  Bitwise-equal to
    the VPU masked reduce for integer payloads; floats inherit the
    encoding's total order (-0.0/+0.0 ties resolve deterministically
    instead of by reduction order).  K/B trailing payload axes ride
    as free minor dims of every contraction.

    Padding contract: pad lanes (rel == -1) have all-zero one-hot
    rows, so they never vote; slots no live lane maps to keep an
    occupancy of 0 and are filled with the reduce identity — padding
    contributes the identity, per the one-identity convention."""
    if kind not in ("min", "max"):
        raise MXUUnsupportedError(kind, vals.dtype,
                                  "unknown compare-reduce kind")
    bits = _order_bits(vals.dtype)
    onehot = _lane_onehot(rel_dst, W)              # [C, E, W] int8
    m = _order_encode(vals)                        # [C, E, ...] uint32
    if kind == "min":
        # min = bitwise complement of max in the order domain
        m = (~m) & (jnp.uint32(0xFFFFFFFF) >> (32 - bits))
    C, E = m.shape[:2]
    trail = m.shape[2:]
    occ = jnp.einsum("ce,cew->cw", jnp.ones((C, E), jnp.int8), onehot,
                     preferred_element_type=jnp.int32) > 0   # [C, W]
    cand0 = jnp.ones(m.shape, jnp.bool_)
    res0 = jnp.zeros((C, W) + trail, jnp.uint32)

    def bitplane(i, carry):
        cand, res = carry
        b = (bits - 1 - i).astype(jnp.uint32)
        bit = (jnp.right_shift(m, b) & jnp.uint32(1)).astype(jnp.int32)
        t = jnp.where(cand, bit, 0).astype(jnp.int8)
        cnt = jnp.einsum("ce...,cew->cw...", t, onehot,
                         preferred_element_type=jnp.int32)
        has = cnt > 0                                # [C, W, ...]
        res = res | jnp.left_shift(has.astype(jnp.uint32), b)
        back = jnp.einsum("cw...,cew->ce...", has.astype(jnp.int8),
                          onehot, preferred_element_type=jnp.int32)
        cand = cand & (back == bit)
        return cand, res

    _, res = jax.lax.fori_loop(
        0, bits, bitplane, vary_like((cand0, res0), m, onehot))
    if kind == "min":
        res = (~res) & (jnp.uint32(0xFFFFFFFF) >> (32 - bits))
    out = _order_decode(res, vals.dtype)
    ident = identity_for(kind, vals.dtype)
    occb = occ.reshape(occ.shape + (1,) * len(trail))
    return jnp.where(occb, out, ident)


def chunk_partials(vals, rel_dst, W: int, kind: str, use_mxu: bool = False,
                   lane_minor: bool = False):
    """Per-chunk reduction [C, E, ...] -> [C, W, ...].

    lane_minor=True leaves a vector payload's result in the order the
    VPU reduce produces it, [C, K, W] (the W lanes minor), for
    ``combine_chunks(..., lane_minor=True)``, which moves only the
    tile rows it keeps; scalar payloads and the MXU contraction are
    [C, W, ...] either way.

    use_mxu=True contracts against an int8 one-hot lane-membership
    matrix on the MXU: sum is one mixed-dtype contraction
    (`preferred_element_type` pins the accumulator to the payload
    dtype, keeping the dtype-discipline audit green); min/max run the
    radix tournament (_mxu_compare_reduce) — bitwise-equal to the VPU
    path for integer payloads, total-order-equal for floats.  The
    default masked broadcast-reduce stays on the VPU and fuses without
    materializing the [C, E, W] intermediate; the MXU path holds the
    one-hot live ([C, E, W] int8 — priced by graph.memory_report's
    ``mxu_temp`` term and amortized by the streamed block bound).
    """
    if use_mxu:
        dt = np.dtype(vals.dtype)
        if dt.kind not in "iuf" or dt.itemsize > 4:
            raise MXUUnsupportedError(
                kind, dt, "payload has no MXU contraction (only "
                "<= 32-bit int/uint/float states)")
        if kind == "sum":
            onehot = _lane_onehot(rel_dst, W)
            # [C, E, ...] x [C, E, W] -> [C, W, ...]; pad lanes have
            # all-zero one-hot rows = the sum identity
            return jnp.einsum("ce...,cew->cw...", vals, onehot,
                              precision=_exact_sum_precision(dt),
                              preferred_element_type=vals.dtype)
        if kind in ("min", "max"):
            return _mxu_compare_reduce(vals, rel_dst, W, kind)
        raise MXUUnsupportedError(kind, dt, "unknown reduce kind")
    ident = identity_for(kind, vals.dtype)
    match = rel_dst[..., None] == jnp.arange(W, dtype=rel_dst.dtype)
    if vals.ndim > 2:                       # vector payload [C, E, K]
        match = match[:, :, None, :]        # [C, E, 1, W]
        masked = jnp.where(match, vals[..., None], ident)
        red = _reduce_axis(masked, 1, kind)     # [C, K, W]
        return red if lane_minor else jnp.moveaxis(red, -1, 1)
    masked = jnp.where(match, vals[..., None], ident)   # [C, E, W]
    return _reduce_axis(masked, 1, kind)


def _reduce_axis(x, axis, kind):
    return {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[kind](
        x, axis=axis)


def aligned_partials(vals, rel_dst, W: int, kind: str,
                     lane_minor: bool = False):
    """Per-chunk reduction [C, E, ...] -> [C, W, ...] of LANE-ALIGNED
    chunks (TiledLayout.build(aligned=True)): slot ``e`` holds an edge
    of lane ``e mod W`` or a pad (rel -1), so the partial is the
    masked fold over the chunk's E / W depth rows — one combine an
    element, no lane compare and nothing broadcast over W.
    lane_minor as in ``chunk_partials``.

    The fold is written over the depth rows' SLICES of the slot axis,
    not as a reshape to [C, E / W, W] and a reduce: the chip keeps the
    messages with the slots minor, in (8, 128) tiles, where a slice at
    a multiple of 128 is a whole tile and the fold an elementwise
    combine of tiles, while the reshape is a relayout of the whole
    array that the reduce then does not fuse with (compiled for v5e
    at the kron20 shape, PR 40: two more passes over 1.08 GB)."""
    with jax.named_scope("lux_aligned"):
        ident = identity_for(kind, vals.dtype)
        pad = (rel_dst < 0).reshape(
            rel_dst.shape + (1,) * (vals.ndim - 2))
        comb = combine_op(kind)

        def row(lo):    # masked per row: each select has one consumer
            return jnp.where(pad[:, lo:lo + W], ident,
                             vals[:, lo:lo + W])

        red = row(0)
        for lo in range(W, vals.shape[1], W):
            red = comb(red, row(lo))
        return jnp.moveaxis(red, -1, 1) if lane_minor else red


# The ``xla`` reduce method only (``pallas`` combines in one pass with
# a carry and holds no tree, ops/pallas_combine.py): combine_chunks
# switches to the BLOCKED segmented scan once the
# chunk axis passes this length: jax.lax.associative_scan over
# [C, W] materializes O(log C) tree levels of BOTH tuple operands, ~2
# * log2(C) * C * W * 4 bytes of program memory — measured as the
# 11.17 GB "program" term that OOM'd the 16 GB chip at C~1.4M/part
# (RMAT26 pair residual; also the round-3 E=128/scale-26 worker
# crash).  The blocked form scans SCAN_BLOCK-chunk slices with a
# carry, so live memory is one block's tree + the [C, W] output.
SCAN_BLOCK_CHUNKS = 16384
SCAN_BLOCKED_ABOVE = 1 << 17


def _segscan(partials, flags, kind):
    """Flag-reset segmented combine along axis 0 (within one block).
    flags broadcast [C, 1...] bool; True = position starts a segment."""
    comb = _combine(kind)

    def op(a, b):
        va, fa = a
        vb, fb = b
        return jnp.where(fb, vb, comb(va, vb)), fa | fb

    vals, _ = jax.lax.associative_scan(
        op, (partials, jnp.broadcast_to(flags, partials.shape)))
    return vals


def method_args(reduce_method: str) -> dict:
    """A resolved ``reduce_method`` ('xla' | 'pallas' |
    'pallas-interpret') as the ``method`` / ``interpret`` arguments
    of tiled_segment_reduce / combine_partials / combine_chunks."""
    return dict(
        method="pallas" if reduce_method.startswith("pallas") else "xla",
        interpret=reduce_method == "pallas-interpret")


def _kernel_takes(partials, lane_minor: bool) -> bool:
    """Whether the one-pass combine kernel lays these partials out:
    the W lanes minor ([C, W], or [C, K, W] when lane_minor) and a
    shape and dtype ops/pallas_combine.kernel_takes accepts."""
    if not (lane_minor or partials.ndim == 2):
        return False
    from lux_tpu.ops.pallas_combine import kernel_takes
    return kernel_takes(partials.shape, partials.dtype)


def combine_chunks(partials, layout: TiledLayout, chunk_start, last_chunk,
                   kind: str, use_mxu: bool = False, method: str = "xla",
                   interpret: bool = False, lane_minor: bool = False):
    """Segmented combine of per-chunk partials [C, W, ...] into tile
    results [n_tiles, W, ...]; chunk_start/last_chunk are this part's
    rows of the layout arrays (device).

    lane_minor=True: the partials are [C, K, W] (``chunk_partials(...,
    lane_minor=True)``); they are combined in that order and only the
    n_tiles rows the last-chunk take keeps are moved to [n_tiles, W,
    K].

    method 'pallas' combines in one sequential pass with a carry
    (ops/pallas_combine.py) wherever the payload has the kernel's
    shape (``kernel_takes``: 4-byte, the W = 128 lanes minor, [C, W]
    or lane-minor [C, K, W]).  Everything else, and method 'xla', runs
    the flag-reset associative scan (the portable oracle).

    use_mxu=True routes the sum-kind scan through _segscan_matmul (the
    TCU-paper scan-as-matmul recurrence); min/max segmented scans stay
    on the VPU — a prefix scan's candidacy is per-OUTPUT-position, so
    the bit-serial tournament that serves chunk_partials has no
    matmul form here (each row of the segment matrix would need its
    own vote), and the flag-reset associative scan is already
    O(C log C) compares."""
    with jax.named_scope("lux_combine"):
        if layout.needs_scan:
            C = partials.shape[0]
            if use_mxu and kind == "sum":
                partials = _segscan_matmul(partials, chunk_start)
            elif method == "pallas" and _kernel_takes(partials, lane_minor):
                from lux_tpu.ops.pallas_combine import (
                    segmented_combine_pallas)
                partials = segmented_combine_pallas(
                    partials, chunk_start, kind, interpret=interpret)
            elif C <= SCAN_BLOCKED_ABOVE:
                flags = chunk_start.reshape(
                    chunk_start.shape + (1,) * (partials.ndim - 1))
                partials = _segscan(partials, flags, kind)
            else:
                partials = _segscan_blocked(partials, chunk_start, kind)
        ident = identity_for(kind, partials.dtype)
        out = jnp.take(partials, jnp.maximum(last_chunk, 0), axis=0)
        if lane_minor:
            out = jnp.moveaxis(out, -1, 1)      # [n_tiles, W, K]
        empty = (last_chunk < 0).reshape(
            last_chunk.shape + (1,) * (out.ndim - 1))
        return jnp.where(empty, ident, out)


def _segscan_blocked(partials, chunk_start, kind,
                     block: int | None = None):
    """Blocked segmented combine: lax.scan over SCAN_BLOCK-chunk
    slices; each step runs the in-block associative scan, then folds
    the carry (the previous block's running value) into every
    position BEFORE the block's first segment flag.  Identical result
    to the monolithic scan with O(block) live tree memory."""
    if block is None:
        # read at call time so tests can shrink the module constant
        block = SCAN_BLOCK_CHUNKS
    comb = _combine(kind)
    C = partials.shape[0]
    trail = partials.shape[1:]
    nB = _ceil_div(C, block)
    Cp = nB * block
    ident = identity_for(kind, partials.dtype)
    if Cp != C:
        # pad chunks are isolated identity segments (same convention
        # as the layout's pad chunks)
        partials = jnp.concatenate(
            [partials, jnp.full((Cp - C,) + trail, ident,
                                partials.dtype)], axis=0)
        chunk_start = jnp.concatenate(
            [chunk_start, jnp.ones(Cp - C, bool)], axis=0)

    def step(carry, x):
        p_b, f_b = x
        fb = f_b.reshape(f_b.shape + (1,) * len(trail))
        inner = _segscan(p_b, fb, kind)
        # positions with NO flag at-or-before them continue the
        # previous block's segment
        absorb = jnp.cumsum(f_b.astype(jnp.int32)) == 0
        ab = absorb.reshape(absorb.shape + (1,) * len(trail))
        out = jnp.where(ab, comb(carry, inner), inner)
        return out[-1], out

    carry0 = jnp.full(trail, ident, partials.dtype)
    _, blocks = jax.lax.scan(
        step, vary_like(carry0, partials, chunk_start),
        (partials.reshape((nB, block) + trail),
         chunk_start.reshape(nB, block)))
    return blocks.reshape((Cp,) + trail)[:C]


# Block length for the scan-as-matmul segmented combine: the int8
# segment matrix is block^2 bytes (64 KB at 256) and one einsum row
# is a 256-wide MXU contraction — small enough to stay resident,
# large enough to amortize the lax.scan step (the blocked-memory
# contract above is preserved: live memory is one block's [B, B]
# matrix + the [C, W] output, never an O(log C) tree).
MXU_SCAN_BLOCK = 256


def _segscan_matmul(partials, chunk_start, block: int | None = None):
    """Segmented inclusive SUM scan along axis 0 as blocked matrix
    products (TCU scan-as-matmul, PAPERS.md): per block the lower-
    triangular same-segment matrix T[i, j] = (i >= j) & (seg i == seg
    j) is built ON DEVICE from cumsum(flags) (no baked constant — the
    const-bytes audit stays green) and one int8 contraction
    produces every prefix in the block; the carry folds into rows
    before the block's first flag exactly as _segscan_blocked.
    Sum-only: min/max have no matmul recurrence (see combine_chunks).
    Bitwise-equal to the flag-reset scan for integer payloads."""
    if block is None:
        block = MXU_SCAN_BLOCK
    C = partials.shape[0]
    trail = partials.shape[1:]
    nB = _ceil_div(C, block)
    Cp = nB * block
    ident = identity_for("sum", partials.dtype)
    if Cp != C:
        # pad chunks are isolated identity segments, as in
        # _segscan_blocked
        partials = jnp.concatenate(
            [partials, jnp.full((Cp - C,) + trail, ident,
                                partials.dtype)], axis=0)
        chunk_start = jnp.concatenate(
            [chunk_start, jnp.ones(Cp - C, bool)], axis=0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)

    def step(carry, x):
        p_b, f_b = x
        sid = jnp.cumsum(f_b.astype(jnp.int32))
        T = ((ii >= jj) &
             (sid[:, None] == sid[None, :])).astype(jnp.int8)
        inner = jnp.einsum("ij,j...->i...", T, p_b,
                           precision=_exact_sum_precision(p_b.dtype),
                           preferred_element_type=p_b.dtype)
        absorb = sid == 0       # no flag at-or-before: continue carry
        ab = absorb.reshape(absorb.shape + (1,) * len(trail))
        out = jnp.where(ab, carry + inner, inner)
        return out[-1], out

    carry0 = jnp.full(trail, ident, partials.dtype)
    _, blocks = jax.lax.scan(
        step, vary_like(carry0, partials, chunk_start),
        (partials.reshape((nB, block) + trail),
         chunk_start.reshape(nB, block)))
    return blocks.reshape((Cp,) + trail)[:C]


# lax.map block size for streamed_chunk_partials (chunks per block)
STREAM_BLOCK_CHUNKS = 1024

# Engines stream the gather + partials once the [rows, C, E] f32
# message/candidate temporary would exceed this many bytes — it is
# what OOMs billion-edge single-chip runs (PERF_NOTES RMAT26 ledger).
STREAM_MSG_BYTES = 1 << 30


def unpack_src_rel(packed, n_valid):
    """Decode the PACKED owner slot encoding (ops/owner.OwnerLayout:
    uint32 src_local << 7 | rel, live-lane counts per chunk) back to
    (src int32, rel int8 with -1 pads) — done INSIDE each streamed
    block so the decoded arrays only ever exist one block at a time
    (the entire point: the packed form saves the int8 rel array's
    2.66 GB at RMAT27, PERF_NOTES round 5)."""
    src = jax.lax.shift_right_logical(
        packed, jnp.uint32(7)).astype(jnp.int32)
    rel = (packed & jnp.uint32(0x7F)).astype(jnp.int8)
    lane = jax.lax.broadcasted_iota(jnp.int32, packed.shape,
                                    packed.ndim - 1)
    live = lane < n_valid[..., None].astype(jnp.int32)
    return jnp.where(live, src, 0), jnp.where(live, rel, jnp.int8(-1))


def _block_partials(flat_state, src_b, rel_b, w_b, msg_fn, kind: str,
                    E: int, W: int, reduce_method: str,
                    use_mxu: bool, nv_b=None, aligned: bool = False):
    """One chunk block's gather + message + per-chunk partials
    [B, E, ...] -> [B, W, ...] (shared by the streamed partial and
    FUSED streamed combine paths — keep the Pallas VMEM sizing and
    the barrier rationale in ONE place).  nv_b set => src_b is the
    packed owner encoding (see unpack_src_rel) and rel_b must be
    None.  aligned: the block's chunks are lane-aligned."""
    if nv_b is not None:
        src_b, rel_b = unpack_src_rel(src_b, nv_b)
    vals = jnp.take(flat_state, src_b, axis=0)
    msgs = msg_fn(vals, w_b)
    if aligned:
        return aligned_partials(msgs, rel_b, W, kind)
    if reduce_method.startswith("pallas") and msgs.ndim == 2:
        from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
        # the kernel's [bc, E, W] masked intermediate must fit
        # scoped VMEM (~16 MB): bc=64 fits E<=128 (pair-residual
        # tile_e), E=512 needs bc=8
        bc = 64 if E * 64 * W * 4 <= (8 << 20) else 8
        return chunk_partials_pallas(
            msgs, rel_b, W, kind,
            block_c=bc if msgs.shape[0] % bc == 0 else 8,
            interpret=reduce_method == "pallas-interpret")
    # keep the (serial, expensive) gather out of the W-wide
    # broadcast consumer on EVERY non-kernel path (see the barrier
    # note in PullEngine._part_msgs)
    msgs = jax.lax.optimization_barrier(msgs)
    return chunk_partials(msgs, rel_b, W, kind, use_mxu=use_mxu)


def streamed_chunk_partials(flat_state, src_slot, rel_dst, weight,
                            layout: TiledLayout, kind: str, msg_fn,
                            reduce_method: str, use_mxu: bool = False,
                            block_chunks: int = STREAM_BLOCK_CHUNKS,
                            nvalid=None):
    """Gather + message + per-chunk partials for ONE part, streamed in
    lax.map blocks over the chunk axis -> [C, W, ...] partials.

    Bounds the [C, E] message/gather temporaries that OOM billion-edge
    single-chip runs (PERF_NOTES RMAT26 ledger).  msg_fn(vals [B, E,
    ...], weight [B, E]|None) -> messages; dead lanes are masked by
    rel == -1 (matching no output lane) downstream.  Shared by the pull engine's step and the
    push engine's dense iterations.  A layout's lane-aligned chunks
    (``n_aligned`` of them, first on the chunk axis) are streamed
    first, its one-hot chunks after them."""
    C, Ca = layout.n_chunks, getattr(layout, "n_aligned", 0)

    def stream(lo, hi, aligned):
        def cut(x):     # the whole axis stays unsliced: a layout
            # without aligned chunks lowers to the program it had
            return x if x is None or (lo, hi) == (0, C) else x[lo:hi]

        return _stream_blocks(
            flat_state, cut(src_slot),
            cut(rel_dst if nvalid is None else nvalid), cut(weight),
            layout.E, layout.W, kind, msg_fn, reduce_method, use_mxu,
            block_chunks, packed=nvalid is not None, aligned=aligned)

    if not Ca:
        return stream(0, C, False)
    cuts = [(0, Ca, True)] + [(Ca, C, False)] * (Ca < C)
    return jnp.concatenate([stream(*c) for c in cuts], axis=0)


def _stream_blocks(flat_state, src_slot, second, weight, E: int, W: int,
                   kind: str, msg_fn, reduce_method: str, use_mxu: bool,
                   block_chunks: int, packed: bool, aligned: bool):
    """streamed_chunk_partials over one run of like chunks; ``second``
    is their rel_dst, or the live-lane counts where ``src_slot`` is
    the packed owner encoding."""
    C = src_slot.shape[0]
    B = max(8, min(block_chunks, C))
    nB, rem = divmod(C, B)

    def partial_block(src_b, rel_b, w_b, nv_b=None):
        return _block_partials(flat_state, src_b, rel_b, w_b, msg_fn,
                               kind, E, W, reduce_method, use_mxu,
                               nv_b=nv_b, aligned=aligned)

    parts = []
    if nB:
        def seg(x):
            return x[:nB * B].reshape((nB, B) + x.shape[1:])

        xs = (seg(src_slot), seg(second)) + \
            (() if weight is None else (seg(weight),))

        def one(x):
            w_b = x[2] if len(x) > 2 else None
            if packed:
                return partial_block(x[0], None, w_b, nv_b=x[1])
            return partial_block(x[0], x[1], w_b)

        blocks = jax.lax.map(one, xs)     # [nB, B, W, ...]
        parts.append(blocks.reshape((nB * B,) + blocks.shape[2:]))
    if rem:
        tail2 = second[nB * B:]
        parts.append(partial_block(
            src_slot[nB * B:], None if packed else tail2,
            None if weight is None else weight[nB * B:],
            nv_b=tail2 if packed else None))
    return jnp.concatenate(parts, axis=0)


def build_extract_plan(last_chunk_rows: np.ndarray, C: int,
                       block: int | None = None,
                       L: int | None = None):
    """Host-side plan for extracting per-tile results from the FUSED
    streamed combine (streamed_chunk_combined) without materializing
    the [C, W] running values: for each ``block``-chunk slice, the
    in-block positions of the tiles whose LAST chunk falls in it.

    last_chunk_rows: int32 [R, n_tiles] (-1 = edge-less tile).
    Returns (extr_pos int32 [R, nB, L], extr_tile int32 [R, nB, L]):
    at each scan step the fused combine reads the block's running
    values at extr_pos (pad -> 0) and SCATTERS them into the carried
    [n_tiles + 1, W] output at extr_tile (pad -> n_tiles, the trash
    row) — carrying the output instead of stacking per-block rows,
    because runs of single-chunk tiles (sparse tails) make every
    chunk a last chunk and a stacked emission degenerates to the very
    [C, W] array this path exists to avoid.  L is the max last-chunk
    count of any (row, block) — it is PROGRAM SHAPE, so multi-host
    callers must pass an allreduced value (OwnerLayout.extract_plan
    does); default = this build's max."""
    lc = np.asarray(last_chunk_rows, np.int64)
    R, n_tiles = lc.shape
    if block is None:
        # read at call time: must match streamed_chunk_combined's
        # block (both default to the module constant)
        block = STREAM_BLOCK_CHUNKS
    nB = max(1, _ceil_div(C, block))
    need = extract_plan_width(lc, C, block)
    if L is None:
        L = need
    elif L < need:
        raise ValueError(f"extract width L={L} < this build's {need}")
    extr_pos = np.zeros((R, nB, L), np.int32)
    extr_tile = np.full((R, nB, L), n_tiles, np.int32)
    for r in range(R):
        live = np.nonzero(lc[r] >= 0)[0]
        if not live.size:
            continue
        c = lc[r][live]
        b = c // block
        order = np.argsort(b, kind="stable")
        bs = b[order]
        newb = np.ones(len(bs), bool)
        newb[1:] = bs[1:] != bs[:-1]
        pos = np.arange(len(bs))
        gst = np.maximum.accumulate(np.where(newb, pos, 0))
        slot = pos - gst                     # rank within block
        extr_pos[r, bs, slot] = (c[order] - bs * block).astype(np.int32)
        extr_tile[r, bs, slot] = live[order].astype(np.int32)
    return extr_pos, extr_tile


def extract_plan_width(last_chunk_rows: np.ndarray, C: int,
                       block: int | None = None) -> int:
    """Max last-chunks per (row, block) — the L this build needs."""
    lc = np.asarray(last_chunk_rows, np.int64)
    if block is None:
        block = STREAM_BLOCK_CHUNKS
    nB = max(1, _ceil_div(C, block))
    best = 1
    for r in range(lc.shape[0]):
        live = lc[r] >= 0
        if live.any():
            cnt = np.bincount(lc[r][live] // block, minlength=nB)
            best = max(best, int(cnt.max()))
    return best


def streamed_chunk_combined(flat_state, src_slot, rel_dst, weight,
                            layout, kind: str, msg_fn,
                            reduce_method: str, chunk_start,
                            extr_pos, extr_tile, last_chunk,
                            use_mxu: bool = False,
                            block_chunks: int | None = None,
                            nvalid=None):
    """Fused streamed gather + message + per-chunk partials +
    BLOCKED segmented combine + last-chunk extraction for ONE part:
    returns per-tile results [n_tiles, W, ...] WITHOUT ever
    materializing the [C, W] running values — the two [C, W]
    temporaries (stacked partials + combined output) are what pushes
    billion-edge owner programs past HBM even with the blocked scan
    (PERF_NOTES round 4).

    extr_pos/extr_tile: this part's rows of build_extract_plan(...,
    block=block_chunks); chunk_start bool [C]; last_chunk int32
    [n_tiles] (only its < 0 mask is used here).  The scan carries the
    running segmented value across blocks exactly like
    _segscan_blocked PLUS the [n_tiles + 1, W] output, scattering
    each block's last-chunk rows into it (the trailing trash row
    absorbs pad slots) — the carried output is written in place by
    XLA, so live memory stays one block plus one result."""
    C, E, W = layout.n_chunks, layout.E, layout.W
    if block_chunks is None:
        block_chunks = STREAM_BLOCK_CHUNKS
    B = max(8, min(block_chunks, C))
    nB = _ceil_div(C, B)
    Cp = nB * B
    comb = _combine(kind)

    def pad_c(x, fill):
        if Cp == C:
            return x
        return jnp.concatenate(
            [x, jnp.full((Cp - C,) + x.shape[1:], fill, x.dtype)],
            axis=0)

    packed = nvalid is not None
    src_slot = pad_c(src_slot, 0)
    second = pad_c(nvalid, 0) if packed else pad_c(rel_dst, -1)
    if weight is not None:
        weight = pad_c(weight, 0)
    chunk_start = pad_c(chunk_start, True)

    def partial_block(src_b, rel_b, w_b, nv_b=None):
        return _block_partials(flat_state, src_b, rel_b, w_b, msg_fn,
                               kind, E, W, reduce_method, use_mxu,
                               nv_b=nv_b)

    msg_aval = jax.eval_shape(
        lambda: msg_fn(jnp.take(flat_state,
                                src_slot[:1].astype(jnp.int32),
                                axis=0),
                       None if weight is None else weight[:1]))
    ident = identity_for(kind, msg_aval.dtype)
    trail = msg_aval.shape[2:]

    def step(carry, x):
        run, acc = carry
        src_b, sec_b, f_b, ep, et = x[:5]
        w_b = x[5] if len(x) > 5 else None
        if packed:
            partials = partial_block(src_b, None, w_b, nv_b=sec_b)
        else:
            partials = partial_block(src_b, sec_b, w_b)   # [B, W, ...]
        fb = f_b.reshape(f_b.shape + (1,) * (partials.ndim - 1))
        inner = _segscan(partials, fb, kind)
        absorb = jnp.cumsum(f_b.astype(jnp.int32)) == 0
        ab = absorb.reshape(absorb.shape + (1,) * (partials.ndim - 1))
        out = jnp.where(ab, comb(run, inner), inner)
        # each tile's last chunk occurs exactly once across all
        # blocks: a plain set into the carried output (pad slots land
        # in the trailing trash row)
        acc = acc.at[et].set(jnp.take(out, ep, axis=0))
        return (out[-1], acc), None

    def seg(x):
        return x.reshape((nB, B) + x.shape[1:])

    xs = (seg(src_slot), seg(second), seg(chunk_start), extr_pos,
          extr_tile)
    if weight is not None:
        xs = xs + (seg(weight),)
    n_tiles = last_chunk.shape[0]
    run0 = jnp.full((W,) + trail, ident, msg_aval.dtype)
    acc0 = jnp.full((n_tiles + 1, W) + trail, ident, msg_aval.dtype)
    (_, acc), _ = jax.lax.scan(
        step, vary_like((run0, acc0), flat_state, xs), xs)
    out = acc[:n_tiles]                               # [n_tiles, W, ..]
    empty = (last_chunk < 0).reshape(
        last_chunk.shape + (1,) * (out.ndim - 1))
    return jnp.where(empty, ident, out)


def combine_partials(partials, layout: TiledLayout, chunk_start,
                     last_chunk, vpad: int, kind: str,
                     use_mxu: bool = False, method: str = "xla",
                     interpret: bool = False, lane_minor: bool = False,
                     tile_rank=None):
    """Per-chunk partials [C, W, ...] -> flat [vpad, ...] (the shared
    tail of tiled_segment_reduce, also used by the streamed engines
    that produce partials block-wise).  tile_rank: this part's row of
    a lane-aligned layout's ``tile_rank``, whose tiles hold the
    destinations in rank order: one row gather takes the result back
    to vertex order."""
    tiles = combine_chunks(partials, layout, chunk_start, last_chunk,
                           kind, use_mxu=use_mxu, method=method,
                           interpret=interpret, lane_minor=lane_minor)
    flatshape = (layout.n_tiles * layout.W,) + tiles.shape[2:]
    flat = tiles.reshape(flatshape)
    if tile_rank is None:
        return flat[:vpad]
    return jnp.take(flat, tile_rank, axis=0)


def tiled_segment_reduce(vals, layout: TiledLayout, chunk_start,
                         last_chunk, rel_dst, vpad: int, kind: str,
                         use_mxu: bool = False, method: str = "xla",
                         interpret: bool = False, tile_rank=None):
    """Full scatter-free segment reduce for ONE part.

    vals [C, E, ...] chunked edge messages; returns [vpad, ...] —
    drop-in for ``segment_reduce(msgs, dst_local, vpad+1, kind)[:vpad]``.

    method 'pallas' runs the per-chunk partial reduction (scalar
    payloads only, ops/pallas_reduce.py) and the chunk combine
    (ops/pallas_combine.py) as Pallas TPU kernels; 'xla' is the
    portable broadcast-compare formulation with the associative scan.

    A lane-aligned layout's first ``n_aligned`` chunks take the fold
    over depth (``aligned_partials``: no compare, so nothing for
    ``use_mxu`` to contract), the rest the formulation above;
    ``tile_rank`` is this part's row of the layout's (see
    ``combine_partials``).
    """
    # the VPU reduce of a vector payload comes out [C, K, W]
    lane_minor = vals.ndim > 2 and not use_mxu

    def onehot(vals, rel_dst):
        if method == "pallas" and vals.ndim == 2:
            from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
            return chunk_partials_pallas(vals, rel_dst, layout.W, kind,
                                         interpret=interpret)
        return chunk_partials(vals, rel_dst, layout.W, kind,
                              use_mxu=use_mxu, lane_minor=lane_minor)

    Ca = layout.n_aligned
    if Ca:
        parts = [aligned_partials(vals[:Ca], rel_dst[:Ca], layout.W,
                                  kind, lane_minor=lane_minor)]
        if Ca < layout.n_chunks:
            parts.append(onehot(vals[Ca:], rel_dst[Ca:]))
        partials = jnp.concatenate(parts, axis=0)
    else:
        partials = onehot(vals, rel_dst)
    return combine_partials(partials, layout, chunk_start, last_chunk,
                            vpad, kind, use_mxu=use_mxu, method=method,
                            interpret=interpret, lane_minor=lane_minor,
                            tile_rank=tile_rank)
