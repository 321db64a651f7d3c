"""Pallas TPU kernel for the segmented combine of chunk partials.

``ops/tiled.combine_chunks`` folds the per-chunk partials ``[C, ...,
W]`` of one 128-vertex tile together along the chunk axis: an
inclusive combine that restarts wherever ``chunk_start`` is set.  The
portable form is ``jax.lax.associative_scan`` (``ops/tiled._segscan``),
a tree that passes over the whole operand, a same-shaped flag operand
and their interleaves (``lax.pad`` + ``lax.add`` per level, on strided
slices) some ``2 log2 C`` times — measured as the ``copy`` / ``pad`` /
``add`` ops of the serving cells, 13.7% of their device time (PERF.md,
PR 38).  Here it is the textbook accumulator: ONE sequential pass with
a carry, read once, written once in place — the role of the carried
block aggregate in the reference's CUB BlockScan CTA loop (reference
pagerank_gpu.cu:49-102; SURVEY.md §3.3), whose atomic scatter the
tiled layout replaced.

- The grid walks blocks of ``block_c`` chunks in order (the one grid
  dimension is ``"arbitrary"``); a VMEM scratch holds the running
  value across grid steps.  The input is aliased to the output, so no
  second ``[C, ...]`` buffer lives.
- The flags ride as ``int32`` in SMEM, one block at a time: never
  broadcast to the payload's shape.
- Vector payloads ``[C, R, 128]`` (the lane-dense order
  ``chunk_partials(..., lane_minor=True)`` returns): a row loop,
  ``acc = where(start[i], x[i], comb(acc, x[i]))``, each row ``R / 8``
  vregs.
- Scalar payloads ``[C, 128]``: one chunk is an eighth of a vreg, so
  eight chunks fold into one ``[8, 128]`` tile.  The tile's own
  segmented combine is three shift-and-select steps along the
  sublanes (1, 2, 4), steered by the tile's eight flags packed into
  one ``int32`` (bit ``r`` = chunk ``r`` starts a segment); the carry
  enters the rows before the tile's first flag.  Only that last step
  depends on the previous tile.

``sum`` folds left to right within a tile where the tree folded in
tree order: float32 sums differ in the last bits, ``min`` / ``max``
and integer sums are bitwise the same.  Runs in interpret mode off
the TPU (tests/test_pallas_combine.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lux_tpu.ops.tiled import combine_op
from lux_tpu.parallel.mesh import vma_of

# chunks per grid step.  Rows: [256, 16, 128] f32 is 2 MB, in + out
# double-buffered 8 MB of the 16 MB scoped VMEM; the block shrinks for
# taller rows (row_block) down to the row loop's unroll of 8, which
# bounds R.  Tiles: [2048, 128] f32 is 1 MB with 256 flag words.
ROW_BLOCK_BYTES = 2 << 20
ROW_UNROLL = 8
TILE_UNROLL = 4                     # tiles of 8 chunks a loop trip
MAX_ROWS = ROW_BLOCK_BYTES // (ROW_UNROLL * 128 * 4)       # R <= 512
TILE_BLOCK_CHUNKS = 2048


def kernel_takes(shape, dtype) -> bool:
    """Whether the kernel lays this payload out: 4-byte, the 128
    lanes minor, ``[C, 128]`` or ``[C, R, 128]`` with R <= MAX_ROWS."""
    return (len(shape) in (2, 3) and shape[-1] == 128
            and jnp.dtype(dtype).itemsize == 4
            and (len(shape) == 2 or shape[1] <= MAX_ROWS))


def row_block(R: int) -> int:
    """Chunks per grid step of the row kernel: the largest multiple
    of ROW_UNROLL whose ``[bc, R, 128]`` 4-byte block stays within
    ROW_BLOCK_BYTES."""
    rows = -(-R // 8) * 8                  # sublane padding in VMEM
    return (ROW_BLOCK_BYTES // (rows * 128 * 4)
            // ROW_UNROLL * ROW_UNROLL)


def _unrolled(n: int, unroll: int, body, init):
    """``fori_loop(0, n, body, init)`` with ``unroll`` steps a trip
    (Mosaic's fori_loop takes only unroll=1 or the whole loop)."""
    def trip(g, acc):
        for u in range(unroll):
            acc = body(g * unroll + u, acc)
        return acc

    return jax.lax.fori_loop(0, n // unroll, trip, init)


def _row_kernel(start_ref, x_ref, o_ref, acc_ref, *, kind: str):
    comb = combine_op(kind)

    def body(i, acc):
        x = x_ref[i]                                   # [R, 128]
        acc = jnp.where(start_ref[0, i] != 0, x, comb(acc, x))
        o_ref[i] = acc
        return acc

    acc_ref[...] = _unrolled(x_ref.shape[0], ROW_UNROLL, body,
                             acc_ref[...])


def _tile_kernel(bits_ref, x_ref, o_ref, acc_ref, *, kind: str):
    comb = combine_op(kind)

    row = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    # bits r-d+1 .. r of a tile's flag word, clipped at bit 0: a flag
    # there stops row r from taking the value d rows above it
    upto = (2 << row) - 1                              # bits 0 .. r
    windows = [(d, upto & ~((1 << jnp.maximum(row - d + 1, 0)) - 1))
               for d in (1, 2, 4)]

    def body(t, acc):
        m = bits_ref[0, t]                                # 8 flags
        lo = pl.multiple_of(t * 8, 8)
        v = x_ref[pl.ds(lo, 8), :]                     # [8, 128]
        # the tile's own combine: row 0 counts as a start, so no row
        # takes a value that the rotation wrapped around
        inner = m | 1
        for d, win in windows:
            up = pltpu.roll(v, d, 0)                   # row r <- r-d
            v = jnp.where((inner & win) != 0, v, comb(up, v))
        # rows before the tile's first flag continue the carry
        v = jnp.where((m & upto) == 0, comb(acc, v), v)
        o_ref[pl.ds(lo, 8), :] = v
        return jnp.broadcast_to(v[7:8, :], (8, 128))

    acc_ref[...] = _unrolled(x_ref.shape[0] // 8, TILE_UNROLL, body,
                             acc_ref[...])


@functools.partial(jax.jit, static_argnames=("kind", "block_c",
                                             "interpret"))
def segmented_combine_pallas(partials, chunk_start, kind: str,
                             block_c: int | None = None,
                             interpret: bool = False):
    """Inclusive flag-reset combine along axis 0, same shape.

    partials ``[C, 128]`` or ``[C, R, 128]``, 4-byte dtype;
    chunk_start ``[C]`` bool (True = the chunk starts a segment;
    chunk 0 always does).  block_c: chunks per grid step, a multiple
    of 8 (rows) or 32 (tiles); default by shape.

    Only the flags are padded to whole blocks.  The payload's last
    block may hang over the end of the chunk axis: what is read there
    is never flagged into a row that is kept, and what is written
    there is dropped."""
    C = partials.shape[0]
    if not kernel_takes(partials.shape, partials.dtype):
        raise ValueError(
            f"segmented_combine_pallas takes 4-byte [C, 128] or "
            f"[C, R <= {MAX_ROWS}, 128] payloads, got "
            f"{partials.dtype}{list(partials.shape)}")
    tiles = partials.ndim == 2
    granule = 8 * TILE_UNROLL if tiles else ROW_UNROLL   # a loop trip
    if block_c is None:
        block_c = (TILE_BLOCK_CHUNKS if tiles
                   else row_block(partials.shape[1]))
        # a short chunk axis is one block
        block_c = min(block_c, -(-C // granule) * granule)
    if block_c % granule:
        raise ValueError(f"block_c={block_c} is not a multiple of "
                         f"{granule}")
    nb = -(-C // block_c)
    # chunk 0 starts a segment whatever its flag says: the scratch
    # holds nothing yet (and, vmapped over parts, the last part's end)
    start = jnp.pad(chunk_start.astype(jnp.int32).at[0].set(1),
                    (0, nb * block_c - C), constant_values=1)
    if tiles:
        # eight flags a word, bit r = chunk 8t + r
        flags = jnp.sum(start.reshape(-1, 8) << jnp.arange(8),
                        axis=1, dtype=jnp.int32)
        kern, fblock = _tile_kernel, block_c // 8
        xspec = pl.BlockSpec((block_c, 128), lambda b: (b, 0),
                             memory_space=pltpu.VMEM)
        acc = pltpu.VMEM((8, 128), partials.dtype)
    else:
        R = partials.shape[1]
        flags, kern, fblock = start, _row_kernel, block_c
        xspec = pl.BlockSpec((block_c, R, 128), lambda b: (b, 0, 0),
                             memory_space=pltpu.VMEM)
        acc = pltpu.VMEM((R, 128), partials.dtype)
    return pl.pallas_call(
        functools.partial(kern, kind=kind),
        grid=(nb,),
        in_specs=[
            # one SMEM row a grid step, whatever its length (a 1-D
            # block would have to be 1024 words, XLA's tile for a
            # 1-D int32 array)
            pl.BlockSpec((None, 1, fblock), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            xspec,
        ],
        out_specs=xspec,
        scratch_shapes=[acc],
        # the running values replace the partials in place
        input_output_aliases={1: 0},
        # under shard_map the result varies over whatever mesh axes
        # its inputs vary over (see chunk_partials_pallas)
        out_shape=jax.ShapeDtypeStruct(
            partials.shape, partials.dtype,
            vma=vma_of(partials, chunk_start)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(flags.reshape(nb, 1, fblock), partials)
