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
  (``part_nv/SPARSE_THRESHOLD + 100``, push_model.inl:393-397); the
  caller falls back to the dense step (lax.cond) when the frontier
  overflows either the queue or the edge budget, which is exactly the
  reference's sparse->dense overflow transition (sssp_gpu.cu:485-490).
- Labels ride along with vertex ids in the queue (the reference
  gathers them from the all-parts dist region instead), so multi-chip
  sparse iterations exchange O(queue) bytes over ICI, not O(nv).

Everything here is per-part, static-shape, and built from sorted
cumsum/gather primitives — no data-dependent shapes anywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from lux_tpu.parallel.mesh import vary_like

# Block length for the MXU cumsum-as-matmul in expand_frontier: one
# int8 lower-triangular [B, B] matrix (64 KB) contracted per block,
# same sizing rationale as ops/tiled.MXU_SCAN_BLOCK.
FRONTIER_MXU_BLOCK = 256


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


def compact_mask(mask, labels, capacity: int):
    """Dense bool mask [vpad] -> padded queue.

    Returns (ids int32 [capacity], vals [capacity], count int32).
    ids[i] for i >= count is vpad (an invalid slot); callers mask on
    position < count.  If count > capacity the queue is truncated —
    callers must branch to the dense path in that case.
    """
    with jax.named_scope("lux_sparse_compact"):
        vpad = mask.shape[0]
        ranks = jnp.cumsum(mask.astype(jnp.int32))      # 1-based
        count = ranks[-1]
        # i-th set bit = first position whose running count reaches
        # i+1; vectorized binary search over the monotone ranks array.
        want = jnp.arange(capacity, dtype=jnp.int32) + 1
        ids = jnp.searchsorted(ranks, want, side="left",
                               method="scan_unrolled").astype(jnp.int32)
        valid = want <= count
        ids = jnp.where(valid, ids, vpad)
        vals = jnp.take(labels, jnp.minimum(ids, vpad - 1), axis=0)
        return ids, vals, count


def expand_frontier(ids, vals, src_ids, src_off, nv: int,
                    edge_budget: int, use_mxu: bool = False):
    """Map a gathered queue to its out-edge slots in this part.

    ids     int32 [Q]   vertex GLOBAL ids (graph numbering), nv=invalid
    vals    [Q]         the queue vertices' labels
    src_ids int32 [S]   this part's present-source ids, sorted, pad=nv
    src_off int32 [S+1] END offsets into the part's src-sorted edge
                        arrays (ShardedGraph.src_sorted — the
                        compressed replacement for the reference's
                        nv-wide row pointers, push_model.inl:321-324)
    Returns (edge_idx int32 [EB], src_val [EB], in_range bool [EB],
             total int32, off int32 [Q]) where edge_idx indexes the
    part's src-sorted edge arrays, src_val is the owning queue item's
    label, off is the running END offset of each queue item's out-edge
    extent (off[-1] == total), and total is the real number of
    frontier out-edges here (may exceed EB — callers must then use the
    dense path; entries past ``total`` are masked by in_range).
    """
    with jax.named_scope("lux_sparse_expand"):
        Q = ids.shape[0]
        S = src_ids.shape[0]
        # binary-search each queue id in the compressed source index
        pos = jnp.searchsorted(src_ids, ids, side="left",
                               method="scan_unrolled")
        posc = jnp.minimum(pos, S - 1).astype(jnp.int32)
        present = (jnp.take(src_ids, posc, axis=0) == ids) & (ids < nv)
        begin = jnp.where(present, jnp.take(src_off, posc, axis=0), 0)
        end = jnp.where(present, jnp.take(src_off, posc + 1, axis=0), 0)
        deg = (end - begin).astype(jnp.int32)
        off = jnp.cumsum(deg)                   # END offsets per item
        total = off[-1]
        start = off - deg                       # begin offset per item
        # Owner of each edge slot via the CSR-expand trick: drop each
        # item's 1-based queue index at its first slot, then a running
        # max spreads it across the item's extent.  (Items with
        # deg > 0 have distinct starts, so the scatter-max never
        # collides.)
        marks = jnp.zeros((edge_budget + 1,), jnp.int32)
        qidx = jnp.arange(Q, dtype=jnp.int32) + 1
        if use_mxu:
            # MXU form: because deg > 0 items have strictly increasing
            # starts AND increasing qidx, the running max of scattered
            # qidx equals the running SUM of scattered qidx-DELTAS
            # (delta = qidx - previous deg>0 item's qidx telescopes,
            # so every prefix sum lands exactly on the most recent
            # item's qidx — including the clamped edge_budget slot,
            # where colliding overflow deltas telescope to the last
            # overflow qidx).  Scatter-ADD into a zero-filled buffer
            # IS the identity init (0 = sum identity), so the
            # identity-init audit passes this path without a pragma;
            # the cumsum then runs as blocked triangular matmuls.
            qm = jnp.where(deg > 0, qidx, 0)
            run = jax.lax.cummax(qm)                 # cheap [Q] op
            prev = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), run[:-1]], axis=0)
            delta = jnp.where(deg > 0, qidx - prev, 0)
            marks = marks.at[jnp.minimum(start, edge_budget)].add(delta)
            owner = _cumsum_matmul(marks[:edge_budget]) - 1  # [EB]
        else:
            # audit: allow(identity-init) — 0 deliberately marks "no
            # item starts here": values are 1-based queue indices
            # >= 1, and the cummax - 1 below maps an untouched 0 back
            # to no-owner (an int32-min init would overflow that - 1).
            marks = marks.at[jnp.minimum(start, edge_budget)].max(
                jnp.where(deg > 0, qidx, 0))
            owner = jax.lax.cummax(marks[:edge_budget]) - 1  # [EB]
        owner = jnp.maximum(owner, 0)
        slot = jnp.arange(edge_budget, dtype=off.dtype)
        in_range = slot < jnp.minimum(total, edge_budget)
        within = slot - jnp.take(start, owner, axis=0)
        edge_idx = (jnp.take(begin, owner, axis=0)
                    + within).astype(jnp.int32)
        edge_idx = jnp.where(in_range, edge_idx, 0)
        src_val = jnp.take(vals, owner, axis=0)
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
