"""Pallas TPU kernel for the per-chunk segment partial reduction.

This is the hot loop of every vertex program — the TPU replacement for
the reference's CUB BlockScan + atomic scatter CTA pattern
(reference pagerank_gpu.cu:49-102, sssp_gpu.cu:148-244; SURVEY.md
§3.3).  It consumes the tiled chunk layout of ops/tiled.py: edge
messages ``vals [C, E]`` with relative destinations ``rel_dst [C, E]``
in ``[0, W)`` (negative = padding lane) and produces per-chunk partials
``[C, W]``, which ops/tiled.combine_chunks folds into vertex tiles: on
the ``pallas`` reduce method with the one-pass kernel beside this one
(ops/pallas_combine.py, scalar and lane-minor vector payloads alike),
on ``xla`` with the segmented ``associative_scan`` (ops/tiled._segscan).

Why a kernel instead of the XLA broadcast-compare reduction
(ops/tiled.chunk_partials):

- The ``[C, E, W]`` one-hot intermediate stays in VMEM one grid block
  at a time instead of spilling W× the edge data to HBM.
- ``pallas_call`` is an opaque custom call, so XLA cannot fuse the
  (serial, expensive) source-state gather that produces ``vals`` into
  the W-wide broadcast — re-executing the gather per output lane —
  which it measurably does to the pure-XLA formulation on TPU v5e.

The kernel is shape-generic over the reduction kind (sum/min/max) and
runs in interpret mode off-TPU so the same code path is testable on
CPU (tests/test_pallas_reduce.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lux_tpu.ops.segment import identity_for
from lux_tpu.parallel.mesh import vma_of


def _partial_kernel(vals_ref, rel_ref, out_ref, *, W: int, kind: str):
    vals = vals_ref[:]                                   # [B, E]
    rel = rel_ref[:]                                     # [B, E]
    B, E = vals.shape
    ident = identity_for(kind, vals.dtype)
    # compare in int32: rel rides HBM as int8 (valid lanes 0..W-1,
    # pad -1 — matches nothing); Mosaic's iota is 32-bit and its
    # minor-dim broadcast insertion only supports 32-bit types, so
    # widen BEFORE the reshape
    rel32 = rel.astype(jnp.int32)                        # [B, E]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (B, E, W), 2)
    match = rel32[:, :, None] == lanes
    masked = jnp.where(match, vals[:, :, None], ident)   # [B, E, W]
    if kind == "sum":
        out_ref[:] = jnp.sum(masked, axis=1)
    elif kind == "min":
        out_ref[:] = jnp.min(masked, axis=1)
    elif kind == "max":
        out_ref[:] = jnp.max(masked, axis=1)
    else:
        raise ValueError(f"unknown reduce kind {kind!r}")


@functools.partial(jax.jit, static_argnames=("W", "kind", "block_c",
                                             "interpret"))
def chunk_partials_pallas(vals, rel_dst, W: int, kind: str,
                          block_c: int = 8, interpret: bool = False):
    """Per-chunk partial reduction [C, E] -> [C, W] on the TPU.

    C must be a multiple of block_c (TiledLayout pads to this).
    Scalar payloads only.  Vector payloads take the XLA path
    (ops/tiled.chunk_partials), and pay for it: at the serving cells'
    [36864, 512, 16] messages its [C, E, K, W] compare-reduce and the
    relayout copy that feeds it (16 lanes padded to 128, 9.66 GB
    written) were 98 of a dense iteration's 224.6 ms (PERF.md section
    5, PR 38).  Query-batched programs therefore run on lane-aligned
    chunks, which need no compare (ops/tiled.aligned_partials, PR 40),
    and only their hub tiles' chunks still take that path; colfilter's
    K-vectors go through the dot path's matmuls instead.
    """
    C, E = vals.shape
    if C % block_c:
        raise ValueError(f"C={C} not a multiple of block_c={block_c}")
    kern = functools.partial(_partial_kernel, W=W, kind=kind)
    return pl.pallas_call(
        kern,
        grid=(C // block_c,),
        in_specs=[
            pl.BlockSpec((block_c, E), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_c, E), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_c, W), lambda b: (b, 0),
                               memory_space=pltpu.VMEM),
        # inside shard_map the kernel's result varies over whatever
        # mesh axes its inputs vary over; the VMA check needs that
        # stated on the out_shape (empty outside shard_map)
        out_shape=jax.ShapeDtypeStruct((C, W), vals.dtype,
                                       vma=vma_of(vals, rel_dst)),
        interpret=interpret,
    )(vals, rel_dst)
