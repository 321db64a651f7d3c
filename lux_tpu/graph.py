"""Host graph representation and the padded device layout.

``Graph`` is the host-side CSC graph (what the reference keeps in
zero-copy memory after its load tasks, reference pull_model.inl:253-320).

``ShardedGraph`` is the TPU-native analogue of the reference's
per-partition device build (init_kernel CSC construction,
reference pagerank_gpu.cu:153-180): all index translation is done ONCE on
the host so that the per-iteration device code is nothing but
static-shape gathers and sorted segmented reductions:

- Partitions are edge-balanced contiguous vertex ranges (partition.py).
- Every per-part array is padded to the max across parts (vertex dim to
  ``vpad``, edge dim to ``epad``) so arrays stack into rectangular
  ``[num_parts, ...]`` tensors that shard cleanly over a mesh axis.
- Vertex state lives in *padded part-major order*: global slot of vertex
  v is ``part(v) * vpad + (v - starts[part(v)])``.  Edge sources are
  pre-translated into these slots (``src_slot``), so the gather of
  source state after an all-gather needs no arithmetic on device.
- Edge destinations are pre-translated to part-local indices
  (``dst_local``); padding edges point at a trash segment ``vpad`` and
  their sources at slot 0.

This replaces the reference's NodeStruct/EdgeStruct FB arrays and its
atomicAdd scatter with a layout where XLA/Pallas see dst-sorted segments
(SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

import dataclasses

import numpy as np

from lux_tpu import format as luxfmt
from lux_tpu import prepstore, telemetry
from lux_tpu.partition import edge_balanced_bounds, part_edge_counts


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _check_partition_starts(starts: np.ndarray, num_parts: int,
                            nv: int) -> None:
    """Partition cut-point invariants (ShardedGraph.build): length
    num_parts+1, 0 .. nv, monotone non-decreasing.  A bad ``starts``
    (hand-rolled, or derived from a corrupt file) would otherwise
    build negative-size parts whose gathers silently clamp."""
    if starts.shape[0] != num_parts + 1:
        raise luxfmt.GraphFormatError(
            "starts", "partition_starts",
            f"{starts.shape[0]} cut points for {num_parts} parts "
            f"(need num_parts + 1)")
    if int(starts[0]) != 0 or int(starts[-1]) != nv:
        raise luxfmt.GraphFormatError(
            "starts", "partition_starts",
            f"cut points must span [0, {nv}], got "
            f"[{int(starts[0])}, {int(starts[-1])}]")
    d = np.diff(starts)
    if (d < 0).any():
        at = int(np.argmax(d < 0))
        raise luxfmt.GraphFormatError(
            "starts", "partition_starts",
            f"cut points decrease at part {at} "
            f"({int(starts[at])} -> {int(starts[at + 1])})")


@dataclasses.dataclass
class Graph:
    """Host CSC graph: row_ptrs are END offsets (see format.py)."""

    nv: int
    ne: int
    row_ptrs: np.ndarray          # uint64 [nv], end offsets
    col_idx: np.ndarray           # uint32 [ne], edge sources, dst-sorted
    weights: np.ndarray | None    # [ne] or None
    out_degrees: np.ndarray       # uint32 [nv]
    # (the arrays a key was taken from, the key): content_key()
    _key_cache: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def _arrays(self) -> tuple:
        return (self.row_ptrs, self.col_idx, self.weights,
                self.out_degrees)

    def content_key(self) -> str:
        """Key of this graph's CONTENT for the preparation store
        (lux_tpu/prepstore.py): a digest of its arrays, taken once
        and kept while the fields hold the same array objects (a
        field that is reassigned gets a new key; the arrays
        themselves are never written in place).  Path, size and mtime
        of the file it came from play no part."""
        arrays = self._arrays()
        kept = self._key_cache
        if kept is None or any(a is not b
                               for a, b in zip(kept[0], arrays)):
            kept = (arrays, prepstore.digest("graph", self.nv, self.ne,
                                             *arrays))
            self._key_cache = kept
        return kept[1]

    def _keyed(self, key: str) -> "Graph":
        """This graph under ``key``: a product of the store is known
        by the key it was derived under, not by a second digest."""
        self._key_cache = (self._arrays(), key)
        return self

    @classmethod
    def from_file(cls, path: str, weighted: bool | None = None,
                  weight_dtype=np.int32, use_native: bool = False,
                  validate: bool = False,
                  reorder: bool | str = False) -> "Graph":
        """Load a .lux file.  use_native=True routes the bulk reads
        through the C++ pthread-pread loader (lux_tpu.native), the
        analogue of the reference's native per-partition load tasks
        (reference pull_model.inl:253-320); falls back to mmap when
        the native library is unavailable.

        validate=True runs format.validate_graph on the loaded arrays
        (both load paths) — a malformed file raises a typed
        format.GraphFormatError instead of producing wrong results
        through XLA's clamping gathers (the apps' -validate flag and
        scripts/fsck_lux.py surface this).

        reorder: apply the page-aware ``.perm`` sidecar written by
        the reorder pass (lux_tpu/reorder.py; format.py sidecar
        section) at load — True requires the sidecar (typed
        GraphFormatError when absent), "auto" applies it only when
        present.  The sidecar is validated (length, bijection) either
        way; the returned graph is relabeled with perm[new] = old."""
        if reorder not in (False, True, "auto"):
            raise ValueError(f"reorder={reorder!r} must be False, "
                             f"True or 'auto'")
        g = None
        if use_native:
            from lux_tpu import native
            if native.available():
                hdr = luxfmt.peek_lux(path, weighted, weight_dtype)
                row_ptrs, col_idx, weights, _ = native.load_partition(
                    path, hdr.nv, hdr.ne, 0, hdr.nv,
                    weighted=hdr.has_weights, weight_dtype=weight_dtype)
                # degrees: col_idx is already in RAM, so count there
                # rather than re-reading 4*ne bytes from disk
                if validate:
                    luxfmt.validate_graph(hdr.nv, hdr.ne, row_ptrs,
                                          col_idx, path=path)
                degrees = np.bincount(col_idx,
                                      minlength=hdr.nv).astype(np.uint32)
                g = cls(nv=hdr.nv, ne=hdr.ne, row_ptrs=row_ptrs,
                        col_idx=col_idx, weights=weights,
                        out_degrees=degrees)
        if g is None:
            hdr, row_ptrs, col_idx, weights, degrees = luxfmt.read_lux(
                path, weighted, weight_dtype, validate=validate)
            if degrees is None:
                # The reference recomputes out-degrees at load time
                # anyway (PullScanTask, reference
                # pull_model.inl:322-345).
                degrees = np.bincount(
                    col_idx, minlength=hdr.nv).astype(np.uint32)
            g = cls(nv=hdr.nv, ne=hdr.ne, row_ptrs=row_ptrs,
                    col_idx=col_idx, weights=weights,
                    out_degrees=degrees)
        if reorder:
            import os as _os
            sidecar = luxfmt.perm_sidecar_path(path)
            if not _os.path.exists(sidecar):
                if reorder == "auto":
                    return g
                raise luxfmt.GraphFormatError(
                    sidecar, "perm_header",
                    "reorder=True but no .perm sidecar exists "
                    "(write one with lux_tpu.reorder / "
                    "format.write_perm_sidecar, or pass "
                    "reorder='auto')")
            perm = luxfmt.read_perm_sidecar(path, nv=g.nv)
            from lux_tpu.reorder import apply_perm
            return apply_perm(g, perm)
        return g

    @classmethod
    def from_edges(cls, src, dst, nv: int, weights=None) -> "Graph":
        from lux_tpu.convert import edges_to_csc
        row_ptrs, col_idx, w_sorted, deg = edges_to_csc(src, dst, nv, weights)
        return cls(nv=nv, ne=int(col_idx.shape[0]), row_ptrs=row_ptrs,
                   col_idx=col_idx, weights=w_sorted, out_degrees=deg)

    def with_edges(self, src, dst, weights=None) -> "Graph":
        """New Graph = this graph's edge multiset plus (src, dst[,
        weights]) — the live-graph compaction fold (lux_tpu/
        livegraph.py): the canonical (dst, src) CSC rebuild through
        ``convert.edges_to_csc`` is deterministic, so two processes
        folding the same delta into the same base produce
        byte-identical arrays (the WAL-replay bitwise contract).
        Weighted graphs require weights for the new edges and vice
        versa — a silently zero-weighted append would corrupt
        shortest paths instead of erroring."""
        src = np.asarray(src)
        dst = np.asarray(dst)
        if (self.weights is None) != (weights is None):
            raise ValueError(
                f"with_edges weights mismatch: graph is "
                f"{'weighted' if self.weights is not None else 'unweighted'}"
                f" but new edges are "
                f"{'weighted' if weights is not None else 'unweighted'}")
        base_src, base_dst = self.edge_arrays()
        w = None
        if self.weights is not None:
            w = np.concatenate([np.asarray(self.weights),
                                np.asarray(weights)])
        return Graph.from_edges(
            np.concatenate([base_src, src.astype(np.int64)]),
            np.concatenate([base_dst, dst.astype(np.int64)]),
            self.nv, weights=w)

    def in_degrees(self) -> np.ndarray:
        return np.diff(self.row_ptrs.astype(np.int64), prepend=0)

    def edge_arrays(self):
        """(src, dst) int64 arrays in file (dst-sorted) order."""
        src = self.col_idx.astype(np.int64)
        dst = np.repeat(np.arange(self.nv, dtype=np.int64),
                        self.in_degrees())
        return src, dst


def degree_relabel(g: Graph):
    """Relabel vertices by descending total degree — concentrates hubs
    into shared 128-vertex tiles so pair-lane delivery (PullEngine /
    PushEngine ``pair_threshold``; ops/pairs.py) finds dense tile
    pairs.  Returns (relabeled graph, perm) with perm[new] = old."""
    src, dst = g.edge_arrays()
    deg = (np.bincount(src, minlength=g.nv)
           + np.bincount(dst, minlength=g.nv))
    perm = np.argsort(-deg, kind="stable")
    rank = np.empty(g.nv, np.int64)
    rank[perm] = np.arange(g.nv)
    g2 = Graph.from_edges(rank[src], rank[dst], g.nv, weights=g.weights)
    return g2, perm


def pair_relabel(g: Graph, num_parts: int = 1,
                 pair_threshold: int = 16, gather_cost: float = 9.0,
                 pair_cost: float = 2.5, vpad_cap: float = 1.2,
                 verbose: bool = False):
    """Degree-sort, then DEAL whole 128-vertex tiles to parts by
    greedy cost balancing (LPT over degree-ordered tiles).

    For multi-part pair-lane delivery (ops/pairs.py) a plain degree
    sort is hostile twice over: contiguous partitions make the hub
    part's depth profile few-deep-tiles and the tail part's
    many-shallow-tiles — and the common padded class structure parts
    must share (shard_map runs ONE program) inflates to the
    elementwise max (measured 2.9x row padding at RMAT21/np=4) — and
    the tail parts keep nearly all the residual gather-served edges
    (measured 0.8M..5.9M skew).  Dealing tiles in descending degree
    order to the currently-cheapest part gives every part a similar
    depth profile AND balanced estimated cost.  Tile contents are
    unchanged by dealing, so pair coverage is identical to the plain
    degree sort.

    Per-tile cost uses the exact global pair histogram (parts are
    tile-aligned, so part-local pair structure equals the global
    tiling): an in-edge in a dense (src-tile, dst-tile) pair costs
    ``pair_cost`` ns, any other ``gather_cost`` ns (PERF_NOTES.md).

    ``vpad_cap`` bounds each part's TILE COUNT at ceil(cap * mean)
    during the dealing: pure cost-LPT measured a 2.5x vpad blowup at
    RMAT25/np=4 (state padding, exchange bytes and the owner-side
    gather's per-shard table size all scale with the WORST part, and
    a shard past ~64 MB re-enters the big-table gather tax —
    PERF_NOTES round-3 #3); the cap trades a sliver of cost balance
    for 2x+ smaller padding.

    Returns (relabeled graph, perm, starts) with perm[new] = old and
    ``starts`` the partition cut points to pass to ShardedGraph.build
    (tile-aligned; a partial trailing tile is placed last).

    Leaves a ``relabel`` span with one child per stage
    (``relabel.degree_sort``, ``.pair_histogram`` when num_parts > 1,
    ``.deal``, ``.rebuild_csc``); ``verbose`` prints one line per
    stage from those records.  A graph the preparation store engages
    on (lux_tpu/prepstore.py) is looked up first, under the key of
    its content and these parameters: a hit loads the result and has
    no stage to show.
    """
    if vpad_cap < 1:
        # cap * P must cover every full tile, or the LPT's all-capped
        # argmin would dump the remainder on part 0 uncapped AND
        # unbalanced
        raise ValueError(f"vpad_cap={vpad_cap} must be >= 1")
    with telemetry.span("relabel") as sp:
        out = _stored_relabel(g, num_parts, pair_threshold,
                              gather_cost, pair_cost, vpad_cap)
    if verbose:
        for rec in telemetry.spans():
            if (rec["parent"] == sp.id
                    and rec["name"].startswith("relabel.")):
                print(f"# pair_relabel/{rec['name'][len('relabel.'):]}: "
                      f"{rec['t1'] - rec['t0']:.1f}s", flush=True)
    return out


def _stored_relabel(g, *params):
    """``_pair_relabel`` through the preparation store.  Hit or miss,
    the relabelled graph goes on under a key DERIVED from the lookup's
    (``Graph._keyed``), so what is prepared from it next finds its
    entry without a digest of the relabelled arrays."""
    if not prepstore.engages(g.ne):
        return _pair_relabel(g, *params)
    key = prepstore.derive(g.content_key(), "relabel", *params)
    g2, perm, starts = prepstore.through(
        "relabel", key, lambda: _pair_relabel(g, *params),
        _relabel_pack, _relabel_unpack)
    return g2._keyed(key), perm, starts


def _relabel_pack(product):
    g2, perm, starts = product
    return dict(row_ptrs=g2.row_ptrs, col_idx=g2.col_idx,
                weights=g2.weights, out_degrees=g2.out_degrees,
                perm=perm, starts=starts), {}


def _relabel_unpack(a, _meta):
    g2 = Graph(nv=len(a["row_ptrs"]), ne=len(a["col_idx"]),
               row_ptrs=a["row_ptrs"], col_idx=a["col_idx"],
               weights=a.get("weights"), out_degrees=a["out_degrees"])
    return g2, a["perm"], a.get("starts")


def _pair_relabel(g, num_parts, pair_threshold, gather_cost, pair_cost,
                  vpad_cap):
    with telemetry.span("relabel.degree_sort"):
        src, dst = g.edge_arrays()
        # uint32 endpoint arrays: the whole pipeline below is
        # billion-edge host prep, and every avoided int64 temporary is
        # 8 GB at RMAT26
        src = src.astype(np.uint32)
        dst = dst.astype(np.uint32)
        deg = (np.bincount(src, minlength=g.nv)
               + np.bincount(dst, minlength=g.nv))
        by_deg = np.argsort(-deg, kind="stable")  # degree position -> old
        del deg
    Wt = 128
    n_tiles = -(-g.nv // Wt)
    full = n_tiles - 1 if g.nv % Wt else n_tiles
    P = max(1, num_parts)
    if P > 1 and full < P:
        # graph too small for whole-tile dealing; plain degree sort,
        # default (cost-balanced) cuts
        with telemetry.span("relabel.rebuild_csc"):
            rank = np.empty(g.nv, np.int64)
            rank[by_deg] = np.arange(g.nv)
            g2 = Graph.from_edges(rank[src], rank[dst], g.nv,
                                  weights=g.weights)
        return g2, by_deg, None

    tile_cost = None
    if P > 1 and full:
        with telemetry.span("relabel.pair_histogram"):
            tile_cost = _tile_costs(g.nv, src, dst, by_deg, n_tiles,
                                    pair_threshold, gather_cost,
                                    pair_cost)
    with telemetry.span("relabel.deal"):
        if tile_cost is not None:
            cap = max(1, int(np.ceil(vpad_cap * full / P)))
            load = np.zeros(P)
            tiles_held = np.zeros(P, np.int64)
            owner = np.empty(full, np.int64)
            for t in range(full):                 # capped LPT greedy
                masked = np.where(tiles_held < cap, load, np.inf)
                p = int(np.argmin(masked))
                owner[t] = p
                load[p] += tile_cost[t]
                tiles_held[p] += 1
            part_tiles = [np.nonzero(owner == p)[0] for p in range(P)]
        else:
            part_tiles = [np.arange(p, full, P) for p in range(P)]

        counts_v = [len(t) * Wt for t in part_tiles]
        if g.nv % Wt:
            part_tiles[-1] = np.concatenate(
                [part_tiles[-1], [full]]).astype(np.int64)
            counts_v[-1] += g.nv % Wt
        starts = np.concatenate(
            ([0], np.cumsum(counts_v))).astype(np.int64)
        tile_seq = np.concatenate(part_tiles)
        vert_order = (tile_seq[:, None] * Wt +
                      np.arange(Wt)[None, :]).reshape(-1)
        vert_order = vert_order[vert_order < g.nv]  # clip partial tile
        perm = by_deg[vert_order]                   # new -> old
        rank = np.empty(g.nv, np.uint32)
        rank[perm] = np.arange(g.nv, dtype=np.uint32)
    with telemetry.span("relabel.rebuild_csc"):
        ns = rank[src]
        del src
        nd = rank[dst]
        del dst, rank
        g2 = Graph.from_edges(ns, nd, g.nv, weights=g.weights)
    return g2, perm, starts


def _tile_costs(nv, src, dst, by_deg, n_tiles, pair_threshold,
                gather_cost, pair_cost):
    """Estimated per-tile in-edge cost in the DEGREE-SORTED tiling
    (pair_relabel's cost model)."""
    Wt = 128
    rank0 = np.empty(nv, np.uint32)
    rank0[by_deg] = np.arange(nv, dtype=np.uint32)
    s2t = (rank0[src] // Wt).astype(np.int64)     # src tile
    d2t = (rank0[dst] // Wt).astype(np.int32)     # dst tile
    key = s2t * np.int64(n_tiles)
    key += d2t
    del s2t
    # per-edge pair multiplicity without np.unique's inverse
    # machinery: one FUSED radix sort carrying the edge index as
    # payload (sequential passes, no argsort random reads and no
    # key/index gathers — native.sort_kv, PERF_NOTES round 4),
    # then group boundaries on the sorted keys
    from lux_tpu import native
    idx = np.arange(len(key),
                    dtype=np.uint32 if len(key) < 2**32
                    else np.int64)
    native.sort_kv(key, (idx,))
    newg = np.ones(len(key), bool)
    newg[1:] = key[1:] != key[:-1]
    del key
    gid = (np.cumsum(newg) - 1).astype(np.int32)
    cnt = np.bincount(gid)
    is_pair = np.empty(len(gid), bool)            # per-edge dense?
    is_pair[idx] = cnt[gid] >= pair_threshold
    del idx, newg, gid, cnt
    # per-tile cost without a float64 per-edge array: count the
    # pair-served edges per dst tile, price the two classes
    pair_by_tile = np.bincount(d2t[is_pair], minlength=n_tiles)
    all_by_tile = np.bincount(d2t, minlength=n_tiles)
    return (pair_cost * pair_by_tile
            + gather_cost * (all_by_tile - pair_by_tile))


def _src_sorted_pack(raw):
    """The raw src-sorted view (per-part lists) as flat arrays and
    the lengths that split them again."""
    ids_l, off_l, dst_l, w_l, max_deg = raw
    arrays = dict(ids=np.concatenate(ids_l), off=np.concatenate(off_l),
                  dst=np.concatenate(dst_l),
                  w=(np.concatenate(w_l) if w_l[0] is not None
                     else None))
    return arrays, dict(n_ids=[len(u) for u in ids_l],
                        n_dst=[len(d) for d in dst_l],
                        max_deg=int(max_deg))


def _src_sorted_unpack(arrays, meta):
    n_ids, n_dst = meta["n_ids"], meta["n_dst"]

    def split(a, lens):
        return np.split(a, np.cumsum(lens)[:-1])

    w = arrays.get("w")
    return (split(arrays["ids"], n_ids),
            split(arrays["off"], [n + 1 for n in n_ids]),
            split(arrays["dst"], n_dst),
            split(w, n_dst) if w is not None else [None] * len(n_dst),
            meta["max_deg"])


@dataclasses.dataclass
class ShardedGraph:
    """Padded part-major device layout (all arrays are host numpy;
    engines move them on device with the right sharding)."""

    nv: int
    ne: int
    num_parts: int
    starts: np.ndarray        # int64 [num_parts+1] partition cut points
    vpad: int                 # padded vertices per part
    epad: int                 # padded edges per part
    nv_part: np.ndarray       # int32 [num_parts] real vertices per part
    ne_part: np.ndarray       # int64 [num_parts] real edges per part
    src_slot: np.ndarray      # int32 [num_parts, epad] padded global src slot
    dst_local: np.ndarray     # int32 [num_parts, epad] local dst, pad -> vpad
    edge_weight: np.ndarray | None  # float32 [num_parts, epad]
    row_ptr_local: np.ndarray  # int32 [num_parts, vpad+1] local END offsets
    vmask: np.ndarray         # bool [num_parts, vpad] valid-vertex mask
    deg_padded: np.ndarray    # int32 [num_parts, vpad] out-degrees, padded

    weighted: bool = False
    # Multi-host builds (parallel/multihost.py): only these parts' rows
    # are materialized in the part-major arrays (None = all parts).
    # Global metadata (nv, starts, vpad, epad, nv_part, ne_part) stays
    # global so every process compiles the SAME program shapes — the
    # analogue of the reference's identical Graph ctor on every node
    # with per-node load tasks (reference pull_model.inl:29-191,253-320).
    local_parts: np.ndarray | None = None
    # Global row_ptrs (END offsets), kept on local builds so chunk
    # geometry (ops/tiled.py) can be sized over ALL parts.
    row_ptr_global: np.ndarray | None = None
    # Max out-degree over the WHOLE graph (push edge budgets must be
    # process-independent static shapes).
    max_out_degree: int = 0
    # Key of this layout's content for the preparation store
    # (lux_tpu/prepstore.py): derived in build() from the graph's key
    # and what shaped the layout.  None where the store stays away: a
    # graph under its size, a local-parts build, a layout assembled
    # by hand.
    content_key: str | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def compatible_mesh_sizes(self, available: int) -> list[int]:
        """Device counts this padded layout can run on UNCHANGED,
        descending: the divisors of num_parts no larger than
        ``available``.  Parts P are fixed across an elastic mesh
        shrink (resilience.py round 11) — every program shape, the
        pair plan, and the checkpointed global ``[P, vpad, ...]``
        view depend only on P, so re-placement onto any of these
        sizes is pure device re-mapping, no host rebuild."""
        cap = min(int(self.num_parts), int(available))
        return [d for d in range(cap, 0, -1)
                if self.num_parts % d == 0]

    def part_ids(self) -> np.ndarray:
        """Global part id of each materialized array row."""
        if self.local_parts is None:
            return np.arange(self.num_parts, dtype=np.int64)
        return np.asarray(self.local_parts, dtype=np.int64)

    @classmethod
    def build(cls, g: Graph, num_parts: int, vpad_align: int = 8,
              epad_align: int = 128, starts: np.ndarray | None = None,
              pair_threshold: int | None = None,
              parts=None) -> "ShardedGraph":
        """pair_threshold: build FOR pair-lane delivery — forces the
        128-aligned vertex padding the delivery needs and (for
        num_parts > 1) cuts partitions balancing ESTIMATED cost under
        the pair/gather split (ops/pairs.cost_balanced_starts) rather
        than raw edge counts.  ``starts`` overrides the cut points.

        parts: materialize only these parts' array rows (multi-host:
        each process builds its own parts, engines assemble the global
        sharded arrays with jax.make_array_from_process_local_data).

        Leaves one ``layout.shard`` span."""
        with telemetry.span("layout.shard"):
            return cls._build(g, num_parts, vpad_align, epad_align,
                              starts, pair_threshold, parts)

    @classmethod
    def _build(cls, g, num_parts, vpad_align, epad_align, starts,
               pair_threshold, parts) -> "ShardedGraph":
        if pair_threshold is not None:
            vpad_align = max(vpad_align, 128)
            if starts is None and num_parts > 1:
                from lux_tpu.ops.pairs import cost_balanced_starts
                starts = cost_balanced_starts(g, num_parts,
                                              pair_threshold)
        if starts is None:
            starts = edge_balanced_bounds(g.row_ptrs, num_parts)
        starts = np.asarray(starts, np.int64)
        _check_partition_starts(starts, num_parts, g.nv)
        nv_part = (starts[1:] - starts[:-1]).astype(np.int32)
        ne_part = part_edge_counts(g.row_ptrs, starts).astype(np.int64)
        vpad = _round_up(max(1, int(nv_part.max())), vpad_align)
        epad = _round_up(max(1, int(ne_part.max())), epad_align)
        if epad >= np.iinfo(np.int32).max:
            raise ValueError(
                f"per-part edge count {epad} overflows int32; "
                f"use more partitions")
        if num_parts * vpad >= np.iinfo(np.int32).max:
            raise ValueError(
                f"padded vertex-slot space {num_parts * vpad} overflows "
                f"int32 src_slot indices")

        rp = g.row_ptrs.astype(np.int64)
        col = g.col_idx
        # part id of every vertex, for the src -> padded-slot translation
        v_part = np.searchsorted(starts, np.arange(g.nv, dtype=np.int64),
                                 side="right") - 1
        v_slot = (v_part * vpad +
                  (np.arange(g.nv, dtype=np.int64) - starts[v_part]))
        v_slot = v_slot.astype(np.int64)

        local = None if parts is None else np.asarray(list(parts), np.int64)
        content_key = None
        if local is None and prepstore.engages(g.ne):
            # pair_threshold acts through vpad_align and starts only
            content_key = prepstore.derive(
                g.content_key(), "shard", num_parts, vpad_align,
                epad_align, starts)
        rows = np.arange(num_parts) if local is None else local
        R = len(rows)
        src_slot = np.zeros((R, epad), dtype=np.int32)
        dst_local = np.full((R, epad), vpad, dtype=np.int32)
        edge_weight = None
        if g.weights is not None:
            edge_weight = np.zeros((R, epad), dtype=np.float32)
        row_ptr_local = np.zeros((R, vpad + 1), dtype=np.int32)
        vmask = np.zeros((R, vpad), dtype=bool)
        deg_padded = np.zeros((R, vpad), dtype=np.int32)

        for r, p in enumerate(rows):
            v0, v1 = int(starts[p]), int(starts[p + 1])
            nep = int(ne_part[p])
            ebegin = int(rp[v0 - 1]) if v0 else 0
            eend = ebegin + nep
            # shard-boundary invariants (the same checks
            # format.validate_graph runs on the whole file, asserted
            # here on each part's slice so an unvalidated malformed
            # graph still errors instead of building garbage gathers)
            local_ends = (rp[v0:v1] - ebegin).astype(np.int64)
            in_deg = np.diff(np.concatenate(([0], local_ends)))
            if nep < 0 or (in_deg < 0).any() or (
                    v1 > v0 and int(local_ends[-1]) != nep):
                raise luxfmt.GraphFormatError(
                    f"part {p}", "partition_edges",
                    f"row_ptrs not monotone within vertices "
                    f"[{v0}, {v1}) or edge count {nep} inconsistent "
                    f"with the part's end offsets")
            srcs = col[ebegin:eend].astype(np.int64)
            if srcs.size and (int(srcs.min()) < 0
                              or int(srcs.max()) >= g.nv):
                bad = int(srcs.max()) if int(srcs.max()) >= g.nv \
                    else int(srcs.min())
                raise luxfmt.GraphFormatError(
                    f"part {p}", "col_idx_range",
                    f"edge source {bad} outside [0, {g.nv})")
            src_slot[r, :nep] = v_slot[srcs]
            # local dst of each edge: expand per-vertex in-degree runs
            dst_local[r, :nep] = np.repeat(
                np.arange(v1 - v0, dtype=np.int32), in_deg)
            if edge_weight is not None:
                edge_weight[r, :nep] = np.asarray(
                    g.weights[ebegin:eend], dtype=np.float32)
            row_ptr_local[r, 1:v1 - v0 + 1] = local_ends
            row_ptr_local[r, v1 - v0 + 1:] = nep
            vmask[r, :v1 - v0] = True
            deg_padded[r, :v1 - v0] = g.out_degrees[v0:v1]

        return cls(nv=g.nv, ne=g.ne, num_parts=num_parts, starts=starts,
                   vpad=vpad, epad=epad, nv_part=nv_part, ne_part=ne_part,
                   src_slot=src_slot, dst_local=dst_local,
                   edge_weight=edge_weight, row_ptr_local=row_ptr_local,
                   vmask=vmask, deg_padded=deg_padded,
                   weighted=g.weights is not None,
                   local_parts=local,
                   row_ptr_global=(g.row_ptrs if local is not None
                                   else None),
                   max_out_degree=int(g.out_degrees.max(initial=0)),
                   content_key=content_key)

    @classmethod
    def build_from_file(cls, path: str, num_parts: int, parts=None,
                        vpad_align: int = 8, epad_align: int = 128,
                        starts: np.ndarray | None = None,
                        weighted: bool | None = None,
                        weight_dtype=np.int32) -> "ShardedGraph":
        """Per-host sharded load: read only ``parts``' edge slices from
        a .lux file through the native pthread-pread loader
        (lux_tpu.native.load_partition; mmap fallback) — the TPU-native
        analogue of the reference's per-partition CPU load tasks
        (reference pull_model.inl:253-320) running one process per
        node.  Only the (small) row_ptr/degree sections are read in
        full, for globally-consistent partition cuts and paddings.

        Typical multi-host use (same code on every host):

            multihost.initialize()
            mesh = multihost.global_mesh()
            sg = ShardedGraph.build_from_file(
                path, P, parts=multihost.process_parts(P))
            eng = PullEngine(sg, program, mesh=mesh)
        """
        from lux_tpu import native

        hdr = luxfmt.peek_lux(path, weighted, weight_dtype)
        # row_ptrs + degrees: small sections, read whole (mmap)
        _, row_ptrs, col_mm, w_mm, degrees = luxfmt.read_lux(
            path, weighted, weight_dtype)
        row_ptrs = np.asarray(row_ptrs)
        if degrees is not None:
            out_deg = np.asarray(degrees).astype(np.uint32)
        elif native.available():
            out_deg = native.count_degrees(path, hdr.nv, hdr.ne)
        else:
            out_deg = np.bincount(np.asarray(col_mm),
                                  minlength=hdr.nv).astype(np.uint32)

        if parts is None:
            parts = range(num_parts)
        parts = np.asarray(list(parts), np.int64)
        if starts is None:
            starts = edge_balanced_bounds(row_ptrs, num_parts)

        use_native = native.available()

        class _LazyCols:
            """Graph.col_idx stand-in that serves per-part slices from
            the native loader (falls back to the mmap view)."""

            def __getitem__(self, sl):
                lo, hi = sl.start or 0, sl.stop
                if hi <= lo:
                    return np.empty(0, np.uint32)
                if not use_native:
                    return np.asarray(col_mm[sl])
                # vertex range covering this edge slice: parts are
                # vertex-contiguous, so invert via searchsorted
                v0 = int(np.searchsorted(row_ptrs, lo, side="right"))
                v1 = min(hdr.nv, 1 + int(
                    np.searchsorted(row_ptrs, hi, side="left")))
                # weights are served from the mmap view; don't read
                # (and immediately discard) the weight bytes here
                _, cols, _w, e_lo = native.load_partition(
                    path, hdr.nv, hdr.ne, v0, v1, weighted=False)
                return cols[lo - e_lo:hi - e_lo]

        weights = None
        if hdr.has_weights:
            weights = w_mm      # mmap: sliced lazily per part
        g = Graph(nv=hdr.nv, ne=hdr.ne, row_ptrs=row_ptrs,
                  col_idx=_LazyCols(), weights=weights,
                  out_degrees=out_deg)
        return cls.build(g, num_parts, vpad_align=vpad_align,
                         epad_align=epad_align, starts=starts,
                         parts=parts)

    def sizing_row_ptr(self) -> np.ndarray:
        """row_ptr_local for ALL parts — chunk geometry (ops/tiled.py)
        must be identical on every process even when only local parts
        are materialized."""
        if self.local_parts is None:
            return self.row_ptr_local
        rp = np.asarray(self.row_ptr_global).astype(np.int64)
        out = np.zeros((self.num_parts, self.vpad + 1), np.int64)
        for p in range(self.num_parts):
            v0, v1 = int(self.starts[p]), int(self.starts[p + 1])
            ebegin = int(rp[v0 - 1]) if v0 else 0
            out[p, 1:v1 - v0 + 1] = rp[v0:v1] - ebegin
            out[p, v1 - v0 + 1:] = out[p, v1 - v0]
        return out

    # ---- push-model (src-sorted) edge view ---------------------------

    _src_sorted_cache: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def _src_sorted_raw(self):
        """Per-part src-sort + unique-source compression (host, once;
        through the preparation store where this layout has a
        ``content_key`` — the stored view is this raw one, so one
        entry serves every ``s_pad`` and ``memory_report`` prices a
        loaded view as a built one)."""
        if self._src_sorted_cache is None:
            key = None
            if self.content_key is not None and self.local_parts is None:
                key = prepstore.derive(self.content_key, "src_sorted")
            self._src_sorted_cache = prepstore.through(
                "src_sorted", key, self._src_sort, _src_sorted_pack,
                _src_sorted_unpack)
        return self._src_sorted_cache

    def _global_src(self, r: int, nep: int) -> np.ndarray:
        """Global source id of row ``r``'s first ``nep`` edges:
        src_slot is the part-major slot; invert the translation."""
        of_slot = (self.starts[:-1, None]
                   + np.arange(self.vpad, dtype=np.int64)).reshape(-1)
        return of_slot[self.src_slot[r, :nep]]

    def _src_sort(self):
        ids_l, off_l, dst_l, w_l = [], [], [], []
        max_deg = 0
        for r, p in enumerate(self.part_ids()):
            nep = int(self.ne_part[p])
            # the compressed index narrows edge offsets to int32
            # (src_off, and the cumsum'd off in expand_frontier);
            # safe because nep <= epad and build() rejects epad >=
            # int32 max (the ValueError guard in ShardedGraph.build)
            src = self._global_src(r, nep)
            order = np.argsort(src, kind="stable")
            uniq, counts = np.unique(src[order], return_counts=True)
            if counts.size:
                max_deg = max(max_deg, int(counts.max()))
            ids_l.append(uniq.astype(np.int32))
            off_l.append(np.concatenate(
                ([0], np.cumsum(counts))).astype(np.int32))
            dst_l.append(self.dst_local[r, :nep][order])
            w_l.append(self.edge_weight[r, :nep][order]
                       if self.weighted else None)
        return ids_l, off_l, dst_l, w_l, max_deg

    # ---- is the src-sorted view also every vertex's IN-edge list? ----

    _symmetric_cache: bool | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def edges_symmetric(self) -> bool:
        """Whether the stored edge multiset equals its own transpose
        (``(src, dst[, weight])`` against ``(dst, src[, weight])``,
        duplicates and self-loops counted): then each part's
        src-sorted view is also the in-edge list of the sources it
        names, which is what the push engine's bottom-up step walks
        (engine/push.py).  Exact, decided on the host once: a vertex
        whose in- and out-degree differ settles it in O(nv); a graph
        that passes that is compared key for key, and the bit is kept
        in the preparation store under a key of its own (the stored
        view does not change).  False on a local-parts build, where no
        process sees every edge."""
        if self._symmetric_cache is None:
            self._symmetric_cache = self._edges_symmetric()
        return self._symmetric_cache

    def _edges_symmetric(self) -> bool:
        if self.local_parts is not None:
            return False
        in_deg = np.diff(self.row_ptr_local, axis=1)
        if not np.array_equal(in_deg, self.deg_padded):
            return False
        key = None
        if self.content_key is not None:
            key = prepstore.derive(self.content_key, "symmetric")
        return bool(prepstore.through(
            "symmetric", key, self._compare_with_transpose,
            lambda bit: (dict(bit=np.asarray([bit], np.uint8)), {}),
            lambda arrays, _meta: arrays["bit"][0]))

    def _compare_with_transpose(self) -> bool:
        """Every edge as the key ``src * nv + dst`` and as its
        transpose's, both sorted: the multisets are equal where the
        sorted keys are (weighted: keys and weights under one
        lexicographic order).  The keys are written in place, part by
        part: fresh arrays of this size cost more to fault in than to
        fill."""
        ends = np.concatenate(([0], np.cumsum(self.ne_part)))
        fwd = np.empty(int(ends[-1]), np.int64)
        rev = np.empty_like(fwd)
        for p in range(self.num_parts):
            nep, at = int(self.ne_part[p]), slice(ends[p], ends[p + 1])
            src = self._global_src(p, nep)
            dst = self.dst_local[p, :nep].astype(np.int64)
            dst += self.starts[p]
            np.multiply(src, self.nv, out=fwd[at])
            fwd[at] += dst
            np.multiply(dst, self.nv, out=rev[at])
            rev[at] += src
        if not self.weighted:
            fwd.sort()
            rev.sort()
            return np.array_equal(fwd, rev)
        w = np.concatenate([self.edge_weight[p, :int(n)]
                            for p, n in enumerate(self.ne_part)])
        of, orv = np.lexsort((w, fwd)), np.lexsort((w, rev))
        return (np.array_equal(fwd[of], rev[orv])
                and np.array_equal(w[of], w[orv]))

    def src_unique_max(self) -> int:
        """Max unique-source count over the materialized parts (the
        compressed source index's natural pad size)."""
        return max((len(u) for u in self._src_sorted_raw()[0]),
                   default=1) or 1

    def max_in_deg(self) -> int:
        """Max edges of one source within a part (cheap: reads the
        cached raw src-sort, no padded-array rebuild)."""
        return self._src_sorted_raw()[4]

    def src_sorted(self, s_pad: int | None = None):
        """Per-part edges re-sorted by GLOBAL source id — the dual CSR
        view the reference's push init builds on device with atomic
        degree counting (reference sssp_gpu.cu:550-607) — with a
        COMPRESSED source index: only sources with >=1 edge in the
        part are stored (sorted ids + END offsets), searched at
        frontier-expansion time (engine/frontier.expand_frontier).
        This replaces the reference's nv-wide per-part row pointers
        (reference push_model.inl:321-324) — O(nv) rows per part,
        ~1.1 GB/part int64 at RMAT27 — with O(present sources) rows.

        s_pad pads the source-index dim; multi-host runs must pass a
        process-independent value >= every part's unique-source count
        (PushEngine all-gathers the max).  Default: the local max.

        Returns dict of numpy arrays:
          src_ids   int32 [R, S]    present-source GLOBAL ids, pad=nv
          src_off   int32 [R, S+1]  END offsets into the part's
                                    src-sorted edge list (pad repeats)
          ss_dst    int32 [R, epad] part-local dst, pad->vpad
          ss_weight float32 [R, epad] or None
          max_in_deg int            max edges of one source in a part
        """
        ids_l, off_l, dst_l, w_l, max_deg = self._src_sorted_raw()
        R = len(ids_l)
        need = max((len(u) for u in ids_l), default=0)
        S = max(1, need if s_pad is None else int(s_pad))
        if S < need:
            raise ValueError(f"s_pad={s_pad} < max unique sources {need}")
        src_ids = np.full((R, S), self.nv, dtype=np.int32)
        src_off = np.zeros((R, S + 1), dtype=np.int32)
        ss_dst = np.full((R, self.epad), self.vpad, dtype=np.int32)
        ss_weight = (np.zeros((R, self.epad), dtype=np.float32)
                     if self.weighted else None)
        for r in range(R):
            u, off = ids_l[r], off_l[r]
            src_ids[r, :len(u)] = u
            src_off[r, :len(u) + 1] = off
            src_off[r, len(u) + 1:] = off[-1]
            nep = len(dst_l[r])
            ss_dst[r, :nep] = dst_l[r]
            if ss_weight is not None:
                ss_weight[r, :nep] = w_l[r]
        return dict(src_ids=src_ids, src_off=src_off, ss_dst=ss_dst,
                    ss_weight=ss_weight, max_in_deg=max_deg)

    # ---- state layout conversion -------------------------------------

    def to_padded(self, x: np.ndarray) -> np.ndarray:
        """[nv, ...] user order -> [rows, vpad, ...] padded layout
        (rows = materialized parts; all of them on a full build)."""
        x = np.asarray(x)
        ids = self.part_ids()
        out = np.zeros((len(ids), self.vpad) + x.shape[1:], x.dtype)
        for r, p in enumerate(ids):
            v0, v1 = int(self.starts[p]), int(self.starts[p + 1])
            out[r, :v1 - v0] = x[v0:v1]
        return out

    def from_padded(self, x: np.ndarray) -> np.ndarray:
        """[num_parts, vpad, ...] padded layout -> [nv, ...] user order.

        Requires ALL parts' rows: on a multi-host run fetch the global
        state first (parallel.multihost.fetch_global)."""
        x = np.asarray(x)
        if x.shape[0] != self.num_parts:
            raise ValueError(
                f"from_padded needs all {self.num_parts} part rows, got "
                f"{x.shape[0]} (multi-host: fetch_global the state first)")
        out = np.empty((self.nv,) + x.shape[2:], x.dtype)
        for p in range(self.num_parts):
            v0, v1 = int(self.starts[p]), int(self.starts[p + 1])
            out[v0:v1] = x[p, :v1 - v0]
        return out

    def memory_report(self, *, exchange: str = "gather",
                      owner_slots_per_part: int | None = None,
                      owner_packed: bool | None = None,
                      push_sparse: bool = False,
                      pairs=None, pair_kdim: int = 1,
                      pair_stream: bool | None = None,
                      page_plan=None,
                      query_batch: int = 1,
                      use_mxu: bool = False,
                      mxu_tile_e: int = 512) -> dict:
        """HBM bytes for the engine edge layouts per part — the
        analogue of the reference's startup memory advisor (reference
        pagerank.cc:60-85).  (The flat oracle layout ships int32
        dst_local instead of int8 rel, +3 B/edge.)

        exchange='owner' prices the owner-side layout instead of the
        tiled one: one packed uint32 per slot (the default whenever
        vpad <= 2^25, ops/owner.OwnerLayout) or int32 src + int8 rel
        (+ f32 weight either way); owner_packed=None infers from the
        vpad bound.  owner_slots_per_part defaults to epad — a LOWER
        bound; the real count includes per-(src-part, dst-tile) chunk
        padding and lives in OwnerLayout.stats after the build
        (measured 1.15-1.5x, PERF_NOTES).

        pairs (a StackedPairPlan, typically ``engine.pairs`` — pass
        the RESIDUAL graph's report the same plan the engine holds)
        prices the pair-lane delivery: the materialized row arrays
        (rowbind + int8 rel + f32 weights + tile_pos, + row_tile for
        K-dim/SDDMM plans, ``pair_kdim`` > 1) AND the delivery
        temporaries — at the STREAMED per-block bound when streaming
        engages (the default; ops/pairs.resolve_pair_stream /
        resolve_pair_dot_stream with ``pair_stream`` forwarded), NOT
        the monolithic [Rp, 128, K] tensor that is only real when
        streaming is forced off (67.7 GB at the NetFlix shape,
        PERF_NOTES round 5/8).

        push_sparse adds the push engine's src-sorted frontier view
        (graph.src_sorted): ss_dst int32 over epad AGAIN (+ f32
        weights again) plus the compressed source index — the arrays
        that roughly DOUBLE edge memory and must be priced before any
        big-scale push run (round-4 VERDICT).  The source-index pad S
        uses the cached src-sort when available, else the min(nv-ish,
        epad) upper bound.

        use_mxu prices the MXU one-hot reduce's live intermediate
        (round 23, ops/tiled.chunk_partials): unlike the fused VPU
        masked reduce, the contraction MATERIALIZES the [C, E, W]
        int8 lane-membership matrix — one byte per (edge, lane) over
        W = 128 lanes, bounded by the streamed block
        (ops/tiled.STREAM_BLOCK_CHUNKS x ``mxu_tile_e`` edges) when
        block streaming engages.  Reported as ``mxu_temp`` and
        subtracted by the ledger-drift audit like the other
        per-iteration temporaries (audit.priced_argument_bytes) —
        the term exists so a use_mxu=True build's ledger stays
        honest, per the round-22 rule that every resident consumer
        is named.

        query_batch prices the QUERY-BATCHED state table (ROADMAP
        item 2, engine/program.py ``batch``): B > 1 makes the vertex
        term ``vpad * (5 B + 4)`` — a 4-byte label/rank plus the
        1-byte active mask per (vertex, query), plus the shared int32
        degrees (at B = 1 the legacy ``vpad * 8`` pricing is kept so
        historical reports stay comparable; pull engines carry no
        mask, so the 5 B term over-prices them by B/(4B+4) — inside
        the ledger-drift tolerance).  The owner exchange's per-
        iteration contribution accumulator also widens to ``vpad * 4
        * B`` per part — reported as ``owner_msg_bytes_per_part`` but
        NOT folded into ``total_bytes``, which prices resident
        ARGUMENT arrays (the quantity the ledger-drift audit check
        compares against XLA memory_analysis)."""
        if query_batch < 1:
            raise ValueError(f"query_batch must be >= 1, got "
                             f"{query_batch}")
        w = 4 if self.weighted else 0
        page_buf = page_temp = 0
        if page_plan is not None:
            # paged gather (ops/pagegather.py): the plan arrays
            # REPLACE the tiled/owner edge layout entirely — price
            # their actual bytes (slot_lane uint32 + rel int8 +
            # weights + row_tile + tile_pos + page_ids), plus the
            # per-iteration temporaries: the deduplicated page buffer
            # [n_pages, 128 (, K, B)] f32 AND the delivered rows —
            # vals + per-row partials, f32 [Rp, 128 (, K, B)] each
            # (the same 2x-Rp-rows term the pair path prices as
            # pair_temp; there is no streamed paged variant yet, so
            # the monolithic bound is what a big build must fit).
            # Both fold into the total like the pair temporaries; the
            # ledger-drift audit compares ARGUMENT arrays only and
            # subtracts the temp fields (audit.check_ledger).
            pp = page_plan
            resident = (pp.slot_lane.nbytes + pp.rel_dst.nbytes
                        + pp.row_tile.nbytes + pp.tile_pos.nbytes
                        + pp.page_ids.nbytes
                        + (pp.weight.nbytes
                           if pp.weight is not None else 0)
                        + (pp.vrow_src.nbytes
                           if getattr(pp, "vrow_src", None)
                           is not None else 0))
            # plan arrays lead with the part (owner: src-part) count
            plan_parts = max(1, pp.slot_lane.shape[0])
            edge_bytes = resident // plan_parts
            wide = max(1, pair_kdim) * query_batch
            page_buf = pp.n_pages * 128 * 4 * wide
            # page-major plans additionally hold the delivered
            # gather-row value buffer [Rg, 128] the virtual rows
            # take from (mode="pagemajor"; Rg = 0 on paged plans)
            page_temp = (2 * pp.Rp + getattr(pp, "Rg", 0)) \
                * 128 * 4 * wide
        elif exchange == "owner":
            slots = (self.epad if owner_slots_per_part is None
                     else int(owner_slots_per_part))
            if owner_packed is None:
                from lux_tpu.ops.owner import OwnerLayout
                owner_packed = self.vpad <= OwnerLayout.PACK_VPAD_MAX
            edge_bytes = slots * ((4 if owner_packed else 5) + w)
        else:
            # src_slot int32 + rel_dst int8 (+ f32 weights)
            edge_bytes = self.epad * (4 + 1 + w)
        sparse_bytes = 0
        if push_sparse:
            if self._src_sorted_cache is not None:
                S = self.src_unique_max()
            else:
                # a part's unique sources are bounded by min(nv, ne):
                # sources come from ANY part (nv ~ num_parts * vpad),
                # not just this one's vpad — the old min(vpad, epad)
                # under-priced exactly the multi-part big-scale fits
                # this advisor gates (~200 MB/part at RMAT25 np=4)
                S = min(self.num_parts * self.vpad, self.epad)
            # src_ids + src_off int32 + ss_dst int32 (+ f32 ss_weight)
            sparse_bytes = 4 * (2 * S + 1) + self.epad * (4 + w)
        pair_bytes = pair_temp = 0
        if pairs is not None:
            from lux_tpu.ops.pairs import (PAIR_DOT_BLOCK_BYTES,
                                           PAIR_STREAM_BLOCK_BYTES,
                                           resolve_pair_dot_stream,
                                           resolve_pair_stream)
            from lux_tpu.ops.pairs import W as _PW
            Rp = int(pairs.Rp)
            wlane = _PW * 4 if pairs.weight is not None else 0
            # rowbind int32 + rel int8[128] (+ f32 weights) + tile_pos
            pair_bytes = Rp * (4 + _PW + wlane) + pairs.tile_pos.shape[1] * 4
            rows = len(self.part_ids())
            if pair_kdim > 1:
                pair_bytes += Rp * 4                       # row_tile
                streamed = resolve_pair_dot_stream(
                    pair_stream, pairs, rows, pair_kdim)
                # streamed: one slot-block of tiles/dots/partials;
                # monolithic: the lax.map-stacked per-row partials
                # PLUS the delivered tile values (XLA materializes
                # both — measured 2x the partials tensor alone,
                # PERF_NOTES round-8 memory_analysis table)
                pair_temp = (PAIR_DOT_BLOCK_BYTES if streamed
                             else 2 * Rp * _PW * pair_kdim * 4)
            else:
                streamed = resolve_pair_stream(pair_stream, pairs)
                # monolithic: delivered f32 value rows + row partials
                pair_temp = (PAIR_STREAM_BLOCK_BYTES if streamed
                             else 2 * Rp * _PW * 4)
        # state f32 + deg int32 (vmask derives from a scalar on
        # device); batched: 4-byte state + 1-byte active per column
        if query_batch == 1:
            vert_bytes = self.vpad * (4 + 4)
        else:
            vert_bytes = self.vpad * (5 * query_batch + 4)
        owner_msg = (self.vpad * 4 * query_batch
                     if exchange == "owner" else 0)
        mxu_temp = 0
        if use_mxu and page_plan is None:
            from lux_tpu.ops.tiled import STREAM_BLOCK_CHUNKS
            # [C, E, 128] int8 one-hot, one byte per (edge, lane);
            # the streamed block bound caps the live chunks
            live_edges = min(self.epad,
                             STREAM_BLOCK_CHUNKS * int(mxu_tile_e))
            mxu_temp = live_edges * 128
        # named per-part decomposition (round 22, lux_tpu/memwatch.py):
        # the unified runtime byte ledger folds these terms alongside
        # the serving/live consumers, and its NumPy oracle re-derives
        # each term independently — total_bytes IS num_parts x the
        # bitwise sum of terms, never a separately-maintained number
        terms = {
            "edge": edge_bytes,
            "push_sparse": sparse_bytes,
            "pair": pair_bytes,
            "pair_temp": pair_temp,
            "page_buffer": page_buf,
            "page_temp": page_temp,
            "mxu_temp": mxu_temp,
            "vertex": vert_bytes,
        }
        per_part = sum(terms.values())
        return {
            "num_parts": self.num_parts,
            "query_batch": query_batch,
            "edge_bytes_per_part": edge_bytes,
            "push_sparse_bytes_per_part": sparse_bytes,
            "pair_bytes_per_part": pair_bytes,
            "pair_temp_bytes_per_part": pair_temp,
            "page_buffer_bytes_per_part": page_buf,
            "page_temp_bytes_per_part": page_temp,
            "mxu_temp_bytes_per_part": mxu_temp,
            "vertex_bytes_per_part": vert_bytes,
            "owner_msg_bytes_per_part": owner_msg,
            "terms_per_part": terms,
            "total_bytes": self.num_parts * per_part,
        }

    def telemetry_header(self, **memory_kwargs) -> dict:
        """Graph shape + the startup memory advisor's per-part HBM
        estimate, as one JSON-serializable dict — the payload of the
        event log's ``header`` event (lux_tpu/telemetry.py), so every
        events JSONL is self-describing.  ``memory_kwargs`` forward to
        ``memory_report`` (exchange=, push_sparse=, ...)."""
        return {
            "nv": int(self.nv), "ne": int(self.ne),
            "weighted": bool(self.weighted),
            "num_parts": int(self.num_parts),
            "vpad": int(self.vpad), "epad": int(self.epad),
            "memory": {k: int(v) for k, v in
                       self.memory_report(**memory_kwargs).items()
                       if not isinstance(v, dict)},
        }
