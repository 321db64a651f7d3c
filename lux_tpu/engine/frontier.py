"""Sparse-frontier machinery for the push engine.

The reference keeps per-partition frontier queues with a
dense-bitmap / sparse-queue state machine and switches representation
on occupancy (reference graph.h:100-106, sssp_gpu.cu:408-491,
SURVEY.md §3.4).  On TPU, variable-size queues fight XLA's static
shapes, so the design is:

- The CANONICAL frontier is always the dense bool mask (shape-stable,
  trivially all-gatherable).  The sparse path is an *execution
  strategy*, not a distinct representation: when the active count is
  small, the step compacts the mask into a capacity-bounded padded
  queue of (vertex slot, label) pairs and relaxes ONLY the frontier's
  out-edges — a fixed edge budget ``EB`` of work instead of a full
  pass over every edge.
- Queue capacity mirrors the reference's sizing rule
  (``part_nv/SPARSE_THRESHOLD + 100``, push_model.inl:393-397).  The
  caller (engine/push.py) takes the dense step (``lax.cond``) only
  when the frontier's COUNT overflows the queue or passes nv/16.  A
  frontier whose out-edges overflow the edge budget is NOT
  re-densified the way the reference does (sssp_gpu.cu:485-490): the
  iteration is TRUNCATED — the queue prefix whose edges fit is
  relaxed and cleared, the rest stays active for the next iteration
  (``expand_extents`` masks past the budget, the caller's ``done``
  prefix does the clearing).
- Both static shapes are the TOP of a short ladder (``rungs``): every
  gather, scatter and scan below costs per SLOT, real or not, so a
  root with five out-edges on a 4 M-slot budget pays for 4 M.  The
  work is therefore split where the sizes split: ``mask_ranks`` is
  [vpad]-sized (the ranks and their search tree) and the same on
  every rung; ``pick_queue`` and ``frontier_extents`` are queue-sized
  and end in the frontier's real out-edge ``total``;
  ``expand_extents`` is budget-sized.  The caller
  picks the smallest queue rung that holds the frontier's count, then
  the smallest budget rung that holds ``total`` (``rung_index``,
  scalars chosen outside the per-part vmap so each branch stays a
  branch).  A lower rung never truncates, so the ladder changes the
  shapes an iteration runs on and nothing else.  Rungs are few: each
  is one more compiled copy of its stage, and compiled code lives in
  device memory.
- Labels ride along with vertex ids in the queue (the reference
  gathers them from the all-parts dist region instead), so multi-chip
  sparse iterations exchange O(queue) bytes over ICI, not O(nv).
- On the queue stage a slot's two searches (its vertex among the
  mask's ranks, ``pick_queue``; its id in the part's compressed
  source index, ``frontier_extents``) are ROW searches
  (``table_search``): every level of a 128-ary tree over the table is
  rows of 128 sorted splitters, the table itself the leaves, all
  stacked in one array (``row_table``: the ranks' built once a trip,
  the source index's once a graph), and a step fetches one ROW and
  counts the splitters under the query.  Three fetched rows a slot on
  a 2 M-entry table where a binary search fetched 21 scalars; with
  the label and the three scalars of the extent, 4 + 6 fetches a slot
  where there were 22 + 24.
- On the budget stage a slot does not go back to the queue for its
  item's data (the CSR-expand trick, ``expand_extents``): each item
  drops, at its first slot, the step from its predecessor's value,
  and a running sum along the slots telescopes to the owning item's
  value.  The chip fetches a row in 6-7 ns whatever the order of the
  indices, and a slot of a running sum in a fraction of that.

Everything here is per-part, static-shape, and built from sorted
cumsum/gather primitives — no data-dependent shapes anywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lux_tpu.parallel.mesh import vary_like

# Block length for the MXU cumsum-as-matmul in expand_extents: one
# int8 lower-triangular [B, B] matrix (64 KB) contracted per block,
# same sizing rationale as ops/tiled.MXU_SCAN_BLOCK.
FRONTIER_MXU_BLOCK = 256

# Stride of a channel in ``_along_slots``' vector: every channel
# starts on a whole (8, 128) int32 tile, so taking one out is a slice
# and not a shift of lanes.
SLOT_ALIGN = 1024

# jnp.searchsorted's method where a binary search is left: the ONE
# query a part of the budget stage makes (how many queue items its
# budget held, push.py ``relax_part``); the queue stage's, one a slot,
# are row searches (``table_search``).  The rolled loop: unrolled
# ("scan_unrolled") the queue stage's ran in the same time and
# compiled to 25 MB more code on a 2 M-vertex part, and compiled code
# is device memory (PERF.md, PR 29).
SEARCH = "scan"

# The row search's fan-out (``table_search``): how many sorted
# splitters one fetched row holds.  128 is the lane count of the
# chip's (8, 128) int32 tile: a [n, 128] view of a table IS the table,
# where a narrower row is padded to 128 lanes in device memory (a
# [n, 16] level of 1.9 M entries is 60 MB, the entries 7.6) and
# fetched no faster (PERF.md, PR 48: the probe's table).
ROW_FANOUT = 128


def row_plan(length: int):
    """The static shape of ``row_table``'s tree over ``length``
    entries: ((first row, rows) of every level, the one-row top level
    first and the leaves last)."""
    sizes, n = [], int(length)
    while True:
        n = -(-n // ROW_FANOUT)
        sizes.append(n)
        if n == 1:
            break
    firsts = np.cumsum([0] + sizes[:-1])
    return tuple(zip(firsts.tolist()[::-1], sizes[::-1]))


def row_table(table):
    """A non-decreasing int32 ``table`` [N] (NumPy's or JAX's, the
    result is the same kind) -> its search tree int32 [T, ROW_FANOUT],
    all levels stacked in ONE array of rows: the table itself first (so
    ``rows.reshape(-1)[:N]`` is the table), then level on level the
    LAST element of every row below (the row's greatest), up to a
    level of one row.  Short rows are filled with the dtype's greatest
    value, which no query is above."""
    xp = np if isinstance(table, np.ndarray) else jnp
    levels, flat = [], table
    while True:
        n = -(-flat.shape[0] // ROW_FANOUT)
        if n * ROW_FANOUT != flat.shape[0]:
            flat = xp.concatenate([flat, xp.full(
                (n * ROW_FANOUT - flat.shape[0],),
                np.iinfo(flat.dtype).max, flat.dtype)])
        levels.append(flat.reshape(n, ROW_FANOUT))
        if n == 1:
            return xp.concatenate(levels, axis=0)
        flat = levels[-1][:, -1]


def table_search(rows, length: int, queries):
    """Positions int32 [Q] of ``queries`` in the table of ``length``
    entries behind ``rows`` (its ``row_table``): what
    ``jnp.searchsorted(table, queries, side="left")`` returns, bit for
    bit, by a descent that fetches one ROW of splitters a level where
    the binary search fetches one scalar a step (7 steps a level at
    128 lanes; the chip fetches a row for about the price of a
    scalar).  A strict ``<`` counts the splitters under the query, so
    a node is the first row whose greatest element reaches it and the
    leaf the first such position: equal runs resolve as
    ``side="left"`` does.  The levels are one rolled loop over the
    stacked rows, ONE gather and one count in the program however
    deep the tree: compiled code is device memory."""
    plan = np.asarray(row_plan(length), np.int32)
    first, count = jnp.asarray(plan[:, 0]), jnp.asarray(plan[:, 1])
    q = queries.astype(rows.dtype)[:, None]

    def level(k, node):
        # (a query over every splitter counts one row too many)
        node = jnp.minimum(node, count[k] - 1)
        row = rows.at[first[k] + node].get(mode="promise_in_bounds")
        return node * ROW_FANOUT + jnp.sum(row < q, axis=1,
                                           dtype=jnp.int32)

    node = jax.lax.fori_loop(
        0, len(plan), level,
        vary_like(jnp.zeros(queries.shape, jnp.int32), rows, queries))
    return jnp.minimum(node, length)


def _cumsum_matmul(x, block: int = FRONTIER_MXU_BLOCK):
    """Inclusive cumsum of an int32 [N] vector as blocked lower-
    triangular matmuls (the tiled scan-as-matmul recurrence with one
    global segment): per block ``T @ x_b + carry`` where T[i, j] =
    (i >= j) is built on device from iota.  Bitwise-equal to
    jnp.cumsum for int32 (integer matmul is exact)."""
    N = x.shape[0]
    nB = -(-N // block)
    Np = nB * block
    if Np != N:
        x = jnp.concatenate(
            [x, jnp.zeros((Np - N,), x.dtype)], axis=0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    T = (ii >= jj).astype(jnp.int8)

    def step(carry, x_b):
        inner = jnp.einsum("ij,j->i", T, x_b,
                           preferred_element_type=x.dtype)
        out = inner + carry
        return out[-1], out

    _, blocks = jax.lax.scan(
        step, vary_like(jnp.zeros((), x.dtype), x),
        x.reshape(nB, block))
    return blocks.reshape(Np)[:N]


def mask_ranks(mask):
    """Dense bool mask [vpad] -> (rows int32 [T, ROW_FANOUT], count
    int32): the 1-based running count of set bits as its search tree
    (``row_table``: the ranks themselves are its first vpad entries)
    and the count's total: the [vpad]-sized half of ``compact_mask``,
    the same on every queue rung."""
    with jax.named_scope("lux_sparse_compact"):
        ranks = jnp.cumsum(mask.astype(jnp.int32))      # 1-based
        return row_table(ranks), ranks[-1]


def pick_queue(rows, labels, capacity: int):
    """The queue-sized half: (ids int32 [capacity], vals [capacity])
    of the first ``capacity`` set bits behind ``mask_ranks``' rows.
    ids[i] for i >= count is vpad (an invalid slot)."""
    with jax.named_scope("lux_sparse_compact"):
        vpad = labels.shape[0]
        # i-th set bit = first position whose running count reaches
        # i+1; a row search over the monotone ranks.
        want = jnp.arange(capacity, dtype=jnp.int32) + 1
        ids = table_search(rows, vpad, want)
        ids = jnp.where(want <= rows.reshape(-1)[vpad - 1], ids, vpad)
        vals = jnp.take(labels, jnp.minimum(ids, vpad - 1), axis=0)
        return ids, vals


def compact_mask(mask, labels, capacity: int):
    """Dense bool mask [vpad] -> padded queue.

    Returns (ids int32 [capacity], vals [capacity], count int32).
    ids[i] for i >= count is vpad (an invalid slot); callers mask on
    position < count.  If count > capacity the queue is truncated —
    callers must branch to the dense path in that case.
    """
    rows, count = mask_ranks(mask)
    ids, vals = pick_queue(rows, labels, capacity)
    return ids, vals, count


def rungs(top: int, divisors=()) -> tuple:
    """The ladder of one static shape: ``top`` and its fixed fractions
    ``top // d``, ascending, distinct, none under 1.  The last rung is
    ``top`` itself."""
    return tuple(sorted({int(top)} | {max(1, int(top) // int(d))
                                      for d in divisors}))


def rung_index(need, ladder):
    """int32 index of the smallest rung of the ascending ``ladder``
    that holds ``need``; the top rung when none does."""
    idx = jnp.int32(0)
    for r in ladder[:-1]:
        idx = idx + (need > r).astype(jnp.int32)
    return idx


def rung_size(idx, ladder):
    """int32 size of rung ``idx`` (a ``rung_index``) of the ascending
    ``ladder``, by selects: a scalar stays a scalar (no table
    lookup)."""
    size = jnp.int32(ladder[0])
    for i, r in enumerate(ladder[1:], 1):
        size = jnp.where(idx >= i, jnp.int32(r), size)
    return size


def wide_add(low, high, x):
    """The count kept in the uint32 words (``low``, ``high``) plus
    ``x`` uint32, the carry going into the high word -> (low, high): a
    count that may pass 2^32 inside one loop, kept exact without
    64-bit types.  Elementwise; on scalars nothing but scalar
    arithmetic."""
    new = low + x
    return new, high + (new < x).astype(jnp.uint32)


class Folded:
    """Row ``index`` of ``wide_add`` words stacked [N, 2] (low, high),
    still on the device, read as ONE number: ``item()`` folds the two words into a
    Python int.  The surface ``telemetry``'s ring settles a count by
    (``is_ready`` / ``item``), so marking one fetches nothing."""

    __slots__ = ("words", "index")

    def __init__(self, words, index: int):
        self.words, self.index = words, index

    def is_ready(self) -> bool:
        return self.words.is_ready()

    def item(self) -> int:
        low, high = jax.device_get(self.words)[self.index]
        return (int(high) << 32) | int(low)


def frontier_extents(ids, src_ids, src_off, nv: int):
    """The queue-sized half of the expansion: where each queue item's
    out-edges lie in this part.

    ids     int32 [Q]   vertex GLOBAL ids (graph numbering), nv=invalid
    src_ids int32 [T, ROW_FANOUT]  the ``row_table`` of this part's
                        present-source ids [S] (sorted, pad=nv), built
                        ahead (the engine's, once a graph)
    src_off int32 [S+1] END offsets into the part's src-sorted edge
                        arrays (ShardedGraph.src_sorted — the
                        compressed replacement for the reference's
                        nv-wide row pointers, push_model.inl:321-324)
    Returns (begin int32 [Q], off int32 [Q], total int32): begin is
    each item's first slot in the part's src-sorted edge arrays, off
    the running END offset of its extent among the frontier's
    out-edges (so its degree is ``diff(off)``), and ``total ==
    off[-1]`` the real number of frontier out-edges here — what the
    caller sizes the budget stage by.
    """
    with jax.named_scope("lux_sparse_expand"):
        S = src_off.shape[0] - 1
        # row-search each queue id in the compressed source index
        posc = jnp.minimum(table_search(src_ids, S, ids), S - 1)
        present = (jnp.take(src_ids.reshape(-1), posc, axis=0) == ids) \
            & (ids < nv)
        begin = jnp.where(present, jnp.take(src_off, posc, axis=0), 0)
        end = jnp.where(present, jnp.take(src_off, posc + 1, axis=0), 0)
        off = jnp.cumsum((end - begin).astype(jnp.int32))
        return begin, off, off[-1]


def _along_slots(channels, start, edge_budget: int,
                 running_sum=jnp.cumsum):
    """Queue channels (int32 [Q] each) laid along the budget's slots
    -> one int32 [EB] each: at slot s, the channel's value of the LAST
    item whose first slot ``start`` is <= s, which is the slot's
    owner.  Every item drops its step ``x_i - x_(i-1)`` (``x_(-1) =
    0``) at its start and a running sum telescopes them: items are in
    queue order and ``start`` never decreases, a zero-degree item
    shares its start with the next and cancels, and the items past
    the budget collide in slot EB, which no slot reads.  Integer
    addition wraps, so the sum is exact for any 32-bit pattern, and
    zeros under a scatter-ADD are the identity init.

    The channels lie end to end in ONE vector (``SLOT_ALIGN``-aligned
    strides): one scatter and one running sum whatever their number,
    since each is one more compiled copy per rung and compiled code is
    device memory; a channel's sum is the vector's less what ran up
    before its first slot.  The indices ascend, and saying so spares
    the scatter its sort of the queue."""
    stride = -(-(edge_budget + 1) // SLOT_ALIGN) * SLOT_ALIGN
    at = jnp.minimum(start, edge_budget)
    zero = jnp.zeros((1,), jnp.int32)
    marks = jnp.zeros((len(channels) * stride,), jnp.int32).at[
        jnp.concatenate([at + k * stride
                         for k in range(len(channels))])].add(
        jnp.concatenate([jnp.diff(x, prepend=zero) for x in channels]),
        indices_are_sorted=True)
    run = running_sum(marks)
    return [run[k * stride:k * stride + edge_budget]
            - (run[k * stride - 1] if k else 0)
            for k in range(len(channels))]


def expand_extents(vals, begin, off, edge_budget: int,
                   use_mxu: bool = False):
    """The budget-sized half: one slot per frontier out-edge, up to
    ``edge_budget`` of them.

    vals [Q] are the queue items' labels (None where the caller reads
    no label off the slots: src_val is then None, and nothing is
    computed for it); begin, off are ``frontier_extents``'.  Returns
    (edge_idx int32 [EB], src_val [EB], in_range bool [EB], owner
    int32 [EB]): edge_idx indexes the part's src-sorted edge arrays,
    owner is the queue index of the item a slot belongs to and
    src_val that item's label, and slots past ``min(off[-1], EB)``
    are masked by in_range (with more out-edges than slots the
    expansion is a prefix: the caller keeps the un-expanded queue
    suffix active).

    No slot fetches from the queue (a fetched row is 6-7 ns on the
    chip, sorted or not; PERF.md, PR 46): what a slot needs of its
    item runs along the slots as prefix sums (``_along_slots``), one
    channel each for the item's queue index, for ``begin - start``
    (the slot number plus it is the edge's index) and for the bits of
    a 32-bit label; a label of another width is fetched by the owner.
    ``use_mxu`` takes the owner's running sum, a count of starts, as
    blocked triangular matmuls; the other channels' steps are full
    32-bit patterns and run as ``jnp.cumsum`` either way.
    """
    with jax.named_scope("lux_sparse_expand"):
        total = off[-1]
        deg = jnp.diff(off, prepend=jnp.zeros((1,), off.dtype))
        start = off - deg                       # first slot per item
        rides = vals is not None and vals.dtype.itemsize == 4
        channels = [jnp.arange(1, begin.shape[0] + 1, dtype=jnp.int32),
                    (begin - start).astype(jnp.int32)]
        if rides:
            channels.append(
                jax.lax.bitcast_convert_type(vals, jnp.int32))
        if use_mxu:
            sums = _along_slots(channels[:1], start, edge_budget,
                                _cumsum_matmul) \
                + _along_slots(channels[1:], start, edge_budget)
        else:
            sums = _along_slots(channels, start, edge_budget)
        owner = sums[0] - 1
        slot = jnp.arange(edge_budget, dtype=off.dtype)
        in_range = slot < jnp.minimum(total, edge_budget)
        edge_idx = jnp.where(in_range, slot + sums[1], 0)
        if rides:
            src_val = jax.lax.bitcast_convert_type(sums[2], vals.dtype)
        else:
            src_val = None if vals is None \
                else jnp.take(vals, owner, axis=0)
        return edge_idx, src_val, in_range, owner


def expand_frontier(ids, vals, src_ids, src_off, nv: int,
                    edge_budget: int, use_mxu: bool = False):
    """Map a gathered queue to its out-edge slots in this part:
    ``frontier_extents`` (``src_ids`` int32 [S] are the sorted ids
    themselves: their tree is built here) then ``expand_extents`` on
    one budget.  Returns (edge_idx int32 [EB], src_val [EB], in_range bool [EB],
    total int32, off int32 [Q]); ``total`` may exceed EB (the
    expansion is then a prefix, see ``expand_extents``).
    """
    begin, off, total = frontier_extents(ids, row_table(src_ids),
                                         src_off, nv)
    edge_idx, src_val, in_range, _owner = expand_extents(
        vals, begin, off, edge_budget, use_mxu=use_mxu)
    return edge_idx, src_val, in_range, total, off


def scatter_reduce(labels, dst_local, cand, kind: str):
    """Scatter-combine candidates into per-part labels.

    dst_local indexes [0, vpad); out-of-frontier lanes should carry the
    reduction identity so they are no-ops.  Unsorted scatter — only used
    on the bounded sparse edge budget, never on full edge arrays.
    """
    with jax.named_scope("lux_sparse_scatter"):
        vpad = labels.shape[0]
        safe = jnp.minimum(dst_local, vpad - 1)
        if kind == "min":
            return labels.at[safe].min(cand, mode="drop")
        if kind == "max":
            return labels.at[safe].max(cand, mode="drop")
    raise ValueError(f"unsupported sparse reduce {kind!r}")
