"""Owner-side (source-part-major) edge layout for the pull exchange.

The pull engine's default exchange makes the FULL vertex state visible
to every part and gathers per edge from the flattened ``[P*vpad]``
table — the analogue of the reference's whole-region READ_ONLY
requirement (reference pull_model.inl:454-461).  Past ~64-128 MB of
table the XLA gather emitter steps from ~8.8 to ~14.6 ns/elem
(PERF_NOTES.md round 3; a step, not locality decay — sorted
indices are WORSE), which capped every round-2 big-graph number at
~27 ns/edge.

This module flips the exchange to OWNER-SIDE message generation — the
structural cousin of the reference's per-source-part push processing
(reference sssp_gpu.cu:422-459, one CUDA stream per source part):

- Edges are re-laid SRC-part-major: each source part's out-edges are
  sorted by global destination tile (dst part x 128-vertex tile) and
  chunked exactly like ops/tiled.py, but with ``src_local`` indices
  into the part's OWN ``[vpad]`` state shard.
- Each source part gathers only from its own shard (< 64 MB/part at
  any scale with enough parts) and reduces its messages into
  per-destination-tile partials ``[G, W]`` — its contribution to
  EVERY destination part.
- Contributions combine across source parts: on one chip a
  ``lax.scan`` accumulates them (measured 7.8-9.1 ns/elem vs 14.7 for
  both the flat AND the vmapped-batched gather — the scan is what
  makes the emitter see the small table, PERF_NOTES.md round 3);
  on a mesh they ride a ``psum_scatter`` (sum) or ``all_to_all`` +
  local combine (min/max) over ICI, replacing the per-iteration
  all_gather entirely.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from lux_tpu.ops.tiled import STREAM_MSG_BYTES


def _ceil_div(a, b):
    return -(-a // b)


@dataclasses.dataclass
class OwnerLayout:
    """Host-side src-part-major chunk plan (stacked over src parts).

    Attribute names n_chunks/E/W/needs_scan match TiledLayout so the
    shared device helpers (streamed_chunk_partials, combine_chunks)
    accept either.

    Array leading dim R = MATERIALIZED src-part rows: all num_parts on
    a full build, this process's local parts on a multi-host build
    (row i is sg.part_ids()[i], not global part i)."""

    W: int                      # vertices per destination tile
    E: int                      # edges per chunk
    n_tiles: int                # dst tiles per PART = ceil(vpad / W)
    G: int                      # global dst tiles = num_parts * n_tiles
    n_chunks: int               # padded per-src-part chunk count C
    needs_scan: bool
    src_local: np.ndarray | None  # int32 [R, C, E] into own shard;
    #                               pad->0.  None in packed mode
    rel_dst: np.ndarray | None  # int8 [R, C, E] in [0, W); -1 = pad.
    #                             None in packed mode
    weight: np.ndarray | None   # float32 [R, C, E]
    chunk_start: np.ndarray     # bool [R, C] True at each tile's 1st chunk
    last_chunk: np.ndarray      # int32 [R, G]; -1 for edge-less tiles
    stats: dict
    # PACKED slot encoding (billion-edge fit, round 5): ONE uint32
    # carries src_local << 7 | rel (W=128 => rel is exactly 7 bits;
    # usable whenever vpad <= 2^25), and pad lanes are recovered from
    # a per-chunk live-lane count instead of rel == -1 — chunks fill
    # contiguously, so a count replaces the whole int8 rel array
    # (2.66 GB at RMAT27; the difference between fitting one chip and
    # OOMing it by 1.3 GB, PERF_NOTES round 5)
    src_rel: np.ndarray | None = None   # uint32 [R, C, E]; pad->0
    n_valid: np.ndarray | None = None   # uint16 [R, C] live lanes

    @property
    def packed(self) -> bool:
        return self.src_rel is not None

    # vpad bound for the 25-bit src_local field of the packed encoding
    PACK_VPAD_MAX = 1 << 25

    @classmethod
    def build(cls, sg, E: int = 256,
              packed: bool | None = None) -> "OwnerLayout":
        """Re-lay a ShardedGraph's edges src-part-major (host, once).

        Chunks bind to one global dst tile each, so per-(src-part,
        dst-tile) edge counts round up to E — smaller E wastes fewer
        padded gather slots when parts spread a tile's in-edges
        thinly (the inflation is reported in ``stats``).

        Multi-host local-parts builds (sg.local_parts set): the
        materialized rows are keyed by DESTINATION part, but the owner
        layout needs edges keyed by SOURCE part — a planning-time
        edge exchange streams every dst part's row across the process
        group (``_local_src_edges``) and each process keeps only the
        edges its own source parts emit; chunk geometry (C,
        needs_scan) is then agreed with a host allreduce, exactly how
        ``plan_sharded_pairs`` agrees on the depth profile.  The
        result's leading dim is the LOCAL row count (the analogue of
        the reference's per-node region instances,
        reference push_model.inl:8-51)."""
        from lux_tpu.ops.tiled import warn_sub128_tile
        warn_sub128_tile(E)
        P, vpad, W = sg.num_parts, sg.vpad, 128
        if packed is None:
            # auto: pack whenever the 25-bit src_local field fits AND
            # the uint16 live-lane count can hold a full chunk
            packed = (vpad <= cls.PACK_VPAD_MAX
                      and E <= np.iinfo(np.uint16).max)
        elif packed and vpad > cls.PACK_VPAD_MAX:
            raise ValueError(
                f"packed owner layout needs vpad <= {cls.PACK_VPAD_MAX}"
                f" (25-bit src_local), got vpad={vpad}")
        elif packed and E > np.iinfo(np.uint16).max:
            # n_valid is uint16 [R, C]; a bigger chunk would silently
            # wrap the live-lane count and corrupt the pad recovery
            # (the analogue of the vpad check)
            raise ValueError(
                f"packed owner layout needs E <= "
                f"{np.iinfo(np.uint16).max} (uint16 live-lane "
                f"counts), got E={E}; pass packed=False")
        n_tiles = max(1, _ceil_div(vpad, W))
        G = P * n_tiles
        local = sg.local_parts is not None
        own_rows = np.asarray(sg.part_ids(), np.int64)
        R = len(own_rows)

        if local:
            key, srcl, rel, wgt = _local_src_edges(sg, n_tiles, G)
        else:
            # per-edge (src part, src local, global dst tile, rel)
            # rows, then ONE stable sort by (src part, dst tile)
            key_l, srcl_l, rel_l, w_l = [], [], [], []
            for r in range(P):
                nep = int(sg.ne_part[r])
                slot = sg.src_slot[r, :nep].astype(np.int64)
                s = slot // vpad
                srcl_l.append((slot - s * vpad).astype(np.int32))
                dst = sg.dst_local[r, :nep].astype(np.int64)
                gt = r * n_tiles + (dst // W)
                key_l.append(s * G + gt)
                rel_l.append((dst % W).astype(np.int8))
                if sg.weighted:
                    w_l.append(sg.edge_weight[r, :nep])
            key = (np.concatenate(key_l) if key_l
                   else np.empty(0, np.int64))
            del key_l
            srcl = (np.concatenate(srcl_l) if srcl_l
                    else np.empty(0, np.int32))
            del srcl_l
            rel = (np.concatenate(rel_l) if rel_l
                   else np.empty(0, np.int8))
            del rel_l
            wgt = np.concatenate(w_l) if w_l else None
            del w_l
        from lux_tpu import native
        # fused radix sort: key + every edge payload move together —
        # no argsort permutation array and no post-sort gathers
        # (native.sort_kv; parallel on pod hosts, PERF_NOTES round 4)
        native.sort_kv(key, (srcl, rel) + (() if wgt is None
                                           else (wgt,)))
        s_of = key // G

        # chunk counts per OWNED src part (sizing pass); geometry is
        # program shape, so multi-host builds allreduce it global
        per_part = []
        for p in own_rows:
            lo, hi = (int(np.searchsorted(s_of, p)),
                      int(np.searchsorted(s_of, p + 1)))
            # key[lo:hi] is already sorted (the global argsort):
            # group boundaries by a diff pass — np.unique would
            # RE-SORT the slice (measured a large slice of the
            # big-graph build time, round 4)
            ks = key[lo:hi] - p * np.int64(G)
            if ks.size:
                newg = np.ones(len(ks), bool)
                newg[1:] = ks[1:] != ks[:-1]
                b = np.nonzero(newg)[0]
                uniq_g = ks[b]
                counts = np.diff(np.concatenate((b, [len(ks)])))
            else:
                uniq_g = np.empty(0, np.int64)
                counts = np.empty(0, np.int64)
            per_part.append((lo, uniq_g.astype(np.int64), counts))
        C = max(1, max((int(_ceil_div(c, E).sum())
                        for _, _, c in per_part), default=1))
        needs_scan = any((_ceil_div(c, E) > 1).any()
                         for _, _, c in per_part if c.size)
        if local:
            from lux_tpu.parallel.multihost import allreduce_host
            C = int(allreduce_host(np.int64(C), "max"))
            needs_scan = bool(allreduce_host(np.int64(needs_scan),
                                             "max"))
        C = _ceil_div(C, 8) * 8          # Pallas block granularity

        if packed:
            src_local = rel_dst = None
            src_rel = np.zeros((R, C, E), dtype=np.uint32)
            n_valid = np.zeros((R, C), dtype=np.uint16)
        else:
            src_rel = n_valid = None
            src_local = np.zeros((R, C, E), dtype=np.int32)
            rel_dst = np.full((R, C, E), -1, dtype=np.int8)
        weight = (np.zeros((R, C, E), dtype=np.float32)
                  if sg.weighted else None)
        chunk_start = np.ones((R, C), dtype=bool)   # pad chunks isolated
        last_chunk = np.full((R, G), -1, dtype=np.int32)

        lanes = np.arange(E, dtype=np.int64)
        used = 0
        for s, (lo, uniq_g, counts) in enumerate(per_part):
            if not counts.size:
                continue
            n_ch = _ceil_div(counts, E)
            nc = int(n_ch.sum())
            used += nc
            # chunk -> position in this part's sorted edge slice
            ci = np.repeat(np.arange(len(uniq_g)), n_ch)  # chunk->tile idx
            tile_lo = lo + np.concatenate(([0], np.cumsum(counts)[:-1]))
            tile_hi = tile_lo + counts
            tile_first = np.concatenate(([0], np.cumsum(n_ch)[:-1]))
            cj = np.arange(nc, dtype=np.int64) - tile_first[ci]
            start = tile_lo[ci] + cj * E
            idx = start[:, None] + lanes[None, :]          # [nc, E]
            valid = idx < tile_hi[ci][:, None]
            idx = np.where(valid, idx, lo)
            if packed:
                sr = (srcl[idx].astype(np.uint32) << np.uint32(7)
                      | rel[idx].astype(np.uint32))
                src_rel[s, :nc] = np.where(valid, sr, 0)
                n_valid[s, :nc] = np.minimum(
                    tile_hi[ci] - start, E).astype(np.uint16)
            else:
                src_local[s, :nc] = np.where(valid, srcl[idx], 0)
                rel_dst[s, :nc] = np.where(valid, rel[idx], -1)
            if weight is not None:
                weight[s, :nc] = np.where(valid, wgt[idx], 0)
            chunk_start[s, :nc] = cj == 0
            last_chunk[s, uniq_g] = (tile_first + n_ch - 1).astype(
                np.int32)

        # on local-parts builds the slot/used counts cover only this
        # process's rows; ne is global, so the ratios are per-process
        # estimates there (each process owns P/nproc of both)
        stats = dict(slots=R * C * E, used_chunks=used,
                     inflation=round(P * C * E / max(1, sg.ne), 3),
                     chunk_inflation=round(
                         (P // max(1, R)) * used * E / max(1, sg.ne),
                         3),
                     packed=packed)
        return cls(W=W, E=E, n_tiles=n_tiles, G=G, n_chunks=C,
                   needs_scan=needs_scan, src_local=src_local,
                   rel_dst=rel_dst, weight=weight,
                   chunk_start=chunk_start, last_chunk=last_chunk,
                   stats=stats, src_rel=src_rel, n_valid=n_valid)

    def streams(self) -> bool:
        """Stream gather+partials in lax.map blocks once one src
        part's [C, E] f32 message temporary passes the shared budget
        (same rule the dst-major engines use)."""
        return self.n_chunks * self.E * 4 > STREAM_MSG_BYTES

    def extract_plan(self):
        """Per-src-part extraction indices for the FUSED streamed
        combine (ops/tiled.streamed_chunk_combined) — avoids the two
        [C, W] temporaries that push billion-edge owner programs past
        HBM (PERF_NOTES round 4).  Returns (extr_pos [R, nB, L],
        extr_tile [R, nB, L]) numpy.

        The extraction width L is program shape: on multi-process
        runs it is allreduced across the group, exactly like C."""
        import jax

        from lux_tpu.ops.tiled import (build_extract_plan,
                                       extract_plan_width)
        L = extract_plan_width(self.last_chunk, self.n_chunks)
        if jax.process_count() > 1:
            from lux_tpu.parallel.multihost import allreduce_host
            L = int(allreduce_host(np.int64(L), "max"))
        return build_extract_plan(self.last_chunk, self.n_chunks, L=L)


def _local_src_edges(sg, n_tiles: int, G: int):
    """Planning-time edge exchange for multi-host owner builds: stream
    every destination part's edge row across the process group and
    keep only the edges whose SOURCE part this process owns.

    Returns (key, srcl, rel, wgt) in the same per-edge encoding the
    single-host build produces (key = src_part * G + global dst tile).
    Per-row ``process_allgather`` shapes come from the GLOBAL
    ``ne_part`` metadata, so every process participates with identical
    shapes.  Peak memory is O(nproc x one part's edges); total traffic
    is O(ne x nproc) — a one-shot planning cost, the analogue of the
    reference building its whole-graph CSR on every node
    (reference pull_model.inl:253-320)."""
    import jax

    P, vpad, W = sg.num_parts, sg.vpad, 128
    own = np.asarray(sg.local_parts, np.int64)
    own_mask = np.zeros(P, bool)
    own_mask[own] = True
    local_row = {int(p): i for i, p in enumerate(own)}
    nproc = jax.process_count()
    holders = np.full(P, -1, np.int64)
    if nproc > 1:
        from jax.experimental import multihost_utils
        # part -> holding process: allgather the row lists once.
        # process_allgather needs identical shapes, so every process
        # must hold the SAME NUMBER of parts (process_parts enforces
        # this; int32 — see the x64-truncation note below)
        lp = multihost_utils.process_allgather(
            own.astype(np.int32))                       # [nproc, R]
        for q in range(nproc):
            holders[np.asarray(lp[q], np.int64)] = q
    else:
        holders[own] = 0
    if (holders < 0).any():
        # an uncovered part's zero placeholder would otherwise be
        # mistaken for real (vertex-0 -> tile-0) edges of src part 0
        raise ValueError("local_parts rows do not cover every "
                         "partition across the process group")

    key_l, srcl_l, rel_l, w_l = [], [], [], []
    for r in range(P):
        nep = int(sg.ne_part[r])        # global metadata: same shape
        if nep == 0:                    # on every process
            continue
        rows = 3 if sg.weighted else 2
        if r in local_row:
            i = local_row[r]
            # [rows, nep] int32 — NOT a packed int64: jax collectives
            # truncate int64 to int32 unless jax_enable_x64 is on.
            # Weights ride along bit-cast to int32: one collective
            # per part instead of two
            both = np.empty((rows, nep), np.int32)
            both[0] = sg.src_slot[i, :nep]
            both[1] = sg.dst_local[i, :nep]
            if sg.weighted:
                both[2] = np.asarray(sg.edge_weight[i, :nep],
                                     np.float32).view(np.int32)
        else:
            both = np.zeros((rows, nep), np.int32)
        if nproc > 1:
            from jax.experimental import multihost_utils
            q = int(holders[r])
            both = np.asarray(
                multihost_utils.process_allgather(both)[q])
        wrow = both[2].view(np.float32) if sg.weighted else None
        slot = both[0].astype(np.int64)
        dst = both[1].astype(np.int64)
        s = slot // vpad
        keep = own_mask[s]
        if not keep.any():
            continue
        s = s[keep]
        slot = slot[keep]
        dst = dst[keep]
        key_l.append(s * G + (r * n_tiles + dst // W))
        srcl_l.append((slot - s * vpad).astype(np.int32))
        rel_l.append((dst % W).astype(np.int8))
        if wrow is not None:
            w_l.append(wrow[keep])
    key = np.concatenate(key_l) if key_l else np.empty(0, np.int64)
    srcl = np.concatenate(srcl_l) if srcl_l else np.empty(0, np.int32)
    rel = np.concatenate(rel_l) if rel_l else np.empty(0, np.int8)
    wgt = (np.concatenate(w_l) if w_l
           else (np.empty(0, np.float32) if sg.weighted else None))
    return key, srcl, rel, wgt


# graph-array dict keys holding the owner scan inputs (all leading-
# dim local src rows); own_w only on weighted graphs, own_ep/own_et
# only when the layout streams (the fused-combine extraction plan)
OWNER_SCAN_KEYS = ("own_src", "own_rel", "own_cs", "own_lc", "own_w",
                   "own_ep", "own_et", "own_sr", "own_nv")


def owner_contribs(lay: OwnerLayout, state_rows, g: dict,
                   kind: str, msg_fn, msg_dtype, num_parts: int,
                   reduce_method: str, use_mxu: bool = False):
    """lax.scan over the locally-held SOURCE parts: each step gathers
    from ONE [vpad, ...] state shard (the scan is what makes the XLA
    emitter see the small table — a vmapped batched gather still pays
    the big-table rate, PERF_NOTES.md round 3) and folds its
    [G, W] tile partials into the accumulated contribution
    ``[num_parts, n_tiles*W, ...]`` to every destination part.

    g: graph-array dict; the OWNER_SCAN_KEYS present in it ride the
    scan with the local-row leading dim."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.segment import identity_for
    from lux_tpu.ops.tiled import combine_op
    from lux_tpu.parallel.mesh import vary_like

    ntw = lay.n_tiles * lay.W
    comb = combine_op(kind)
    xs = {k: g[k] for k in OWNER_SCAN_KEYS if k in g}

    def step(acc, x):
        st_s, d = x
        tiles = owner_part_tiles(
            lay, st_s, d.get("own_sr", d.get("own_src")),
            d.get("own_rel"), d.get("own_w"),
            d["own_cs"], d["own_lc"], kind, msg_fn, reduce_method,
            use_mxu=use_mxu, extr_pos=d.get("own_ep"),
            extr_tile=d.get("own_et"), nvalid=d.get("own_nv"))
        contrib = tiles.reshape((num_parts, ntw) + tiles.shape[2:])
        return comb(acc, contrib), None

    acc0 = jnp.full((num_parts, ntw) + state_rows.shape[2:],
                    identity_for(kind, msg_dtype), msg_dtype)
    acc, _ = jax.lax.scan(step, vary_like(acc0, state_rows, xs),
                          (state_rows, xs))
    return acc


def owner_exchange(acc, kind: str, axis=None, ndev: int = 1,
                   minmax_fused: bool = False):
    """Route accumulated contributions [P, ntw, ...] to their
    destination parts.  axis=None (single device): identity — every
    dst row is already local.  On a mesh: reduce_scatter over ICI —
    ``psum_scatter`` for sum, ``all_to_all`` + local combine for
    min/max (the TPU-native replacement for the whole-region
    all_gather, reference pull_model.inl:454-461).

    minmax_fused=True routes min/max through the psum_scatter-style
    RING reduce-scatter (``ring_reduce_scatter``) instead: the combine
    happens en route, so the receive working set per step is ONE
    device's row chunk [P/ndev, ntw] instead of the all_to_all's full
    [P, ntw] landing buffer + ndev-way local reduction (round-5
    pointer #5).  Opt-in until measured on a real mesh; oracle-equal
    to the all_to_all path (tests/test_owner.py)."""
    import jax
    import jax.numpy as jnp

    if axis is None:
        return acc
    if kind == "sum":
        return jax.lax.psum_scatter(acc, axis, scatter_dimension=0,
                                    tiled=True)
    if minmax_fused:
        return ring_reduce_scatter(acc, kind, axis, ndev)
    recv = jax.lax.all_to_all(acc, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    rows = acc.shape[0] // ndev
    red = recv.reshape((ndev, rows) + recv.shape[1:])
    return {"min": jnp.min, "max": jnp.max}[kind](red, axis=0)


def ring_reduce_scatter(acc, kind: str, axis, ndev: int):
    """Ring reduce-scatter for any combine kind (shard_map body).

    acc [P, ...] per device; returns [P/ndev, ...] — device d ends
    with the fully-combined rows of ITS chunk d (the same contract as
    ``psum_scatter(..., scatter_dimension=0, tiled=True)``).  Chunk c
    starts at device c+1 and travels the ring c+1 -> c+2 -> ... -> c,
    each hop folding the visiting device's local contribution, so the
    partial being combined is always one chunk — ndev-1 ppermute hops
    of [P/ndev, ...] each, combine fused per hop."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.tiled import combine_op

    comb = combine_op(kind)
    rows = acc.shape[0] // ndev
    chunks = acc.reshape((ndev, rows) + acc.shape[1:])
    idx = jax.lax.axis_index(axis)
    perm = [(j, (j + 1) % ndev) for j in range(ndev)]
    # device i launches its contribution to chunk i-1
    cur = jnp.take(chunks, (idx - 1) % ndev, axis=0)
    for s in range(ndev - 1):
        cur = jax.lax.ppermute(cur, axis, perm)
        # after hop s, device i holds chunk (i - 2 - s) mod ndev and
        # folds its own contribution; the last fold (s = ndev - 2)
        # lands chunk i fully combined at device i
        cur = comb(cur, jnp.take(chunks, (idx - 2 - s) % ndev, axis=0))
    return cur


def owner_part_tiles(lay: OwnerLayout, state_s, src, rel, weight, cs,
                     lc, kind: str, msg_fn, reduce_method: str,
                     use_mxu: bool = False, extr_pos=None,
                     extr_tile=None, nvalid=None):
    """One source part's contribution: gather from its OWN shard
    ``state_s [vpad, ...]``, message, chunk-reduce, and combine into
    per-global-tile results ``[G, W, ...]`` (identity where the part
    contributes nothing).

    extr_pos/extr_tile (this part's rows of OwnerLayout.extract_plan):
    run the FUSED streamed combine, which never materializes the
    [C, W] running values."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.tiled import (chunk_partials, combine_chunks,
                                   method_args,
                                   streamed_chunk_combined,
                                   streamed_chunk_partials)

    if extr_pos is not None:
        return streamed_chunk_combined(
            state_s, src, rel, weight, lay, kind, msg_fn,
            reduce_method, cs, extr_pos, extr_tile, lc,
            use_mxu=use_mxu, nvalid=nvalid)           # [G, W, ...]
    if lay.streams():
        partials = streamed_chunk_partials(
            state_s, src, rel, weight, lay, kind, msg_fn, reduce_method,
            use_mxu=use_mxu, nvalid=nvalid)
    else:
        if nvalid is not None:
            from lux_tpu.ops.tiled import unpack_src_rel
            src, rel = unpack_src_rel(src, nvalid)
        vals = jnp.take(state_s, src, axis=0)
        msgs = msg_fn(vals, weight)
        if reduce_method.startswith("pallas") and msgs.ndim == 2:
            from lux_tpu.ops.pallas_reduce import chunk_partials_pallas
            partials = chunk_partials_pallas(
                msgs, rel, lay.W, kind,
                interpret=reduce_method == "pallas-interpret")
        else:
            # keep the (serial, expensive) gather out of the W-wide
            # broadcast consumer (see PullEngine._part_msgs)
            msgs = jax.lax.optimization_barrier(msgs)
            partials = chunk_partials(msgs, rel, lay.W, kind,
                                      use_mxu=use_mxu)
    return combine_chunks(partials, lay, cs, lc, kind, use_mxu=use_mxu,
                          **method_args(reduce_method))    # [G, W, ...]
