"""Device mesh construction and part-axis sharding helpers.

The reference's placement layer is its Legion mapper: slice_task
round-robins partition tasks over GPUs and pins regions to framebuffer
vs zero-copy memory (reference lux_mapper.cc:97-165).  On TPU the same
role is played declaratively: a 1-D ``Mesh`` over the ``parts`` axis
plus ``NamedSharding`` annotations on the part-major arrays; XLA's SPMD
partitioner then inserts the ICI collectives that Legion/GASNet
performed implicitly (SURVEY.md §2.3).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

PARTS_AXIS = "parts"


def make_mesh(num_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the ``parts`` axis."""
    if devices is None:
        devices = jax.devices()
        if num_devices is not None:
            if num_devices > len(devices):
                raise ValueError(
                    f"requested {num_devices} devices, have {len(devices)}")
            devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (PARTS_AXIS,))


def vma_of(*refs) -> frozenset:
    """The manual mesh axes any leaf of ``refs`` varies over — the
    varying-manual-axes (VMA) type of whatever is computed from them.
    Empty outside ``shard_map``."""
    return frozenset().union(
        *(jax.typeof(r).vma for r in jax.tree.leaves(refs)))


def vary_like(init, *refs):
    """Type a constant loop carry for a loop whose body folds ``refs``
    into it.  Inside ``shard_map`` a scan/while carry must have the
    same VMA type going in as coming out; a fresh
    ``jnp.zeros``/``jnp.full`` is device-invariant while a body that
    mixes in sharded ``refs`` yields a device-varying value.  Casts
    every leaf of ``init`` to vary over ``vma_of(refs)``; the identity
    outside shard_map."""
    want = vma_of(refs)

    def cast(x):
        axes = tuple(want - jax.typeof(x).vma)
        return jax.lax.pcast(x, axes, to="varying") if axes else x

    return jax.tree.map(cast, init)


def parts_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(PARTS_AXIS))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def local_part_rows(mesh: Mesh, num_parts: int) -> list[int]:
    """The global leading-axis rows this PROCESS's devices hold under
    the parts sharding (sorted).  Single-process: all rows."""
    sharding = parts_spec(mesh)
    idx_map = sharding.addressable_devices_indices_map((num_parts,))
    rows = set()
    for idx in idx_map.values():
        rows.update(range(*idx[0].indices(num_parts)))
    return sorted(rows)


def shard_over_parts(mesh: Mesh, tree, num_parts: int | None = None):
    """Place every array in ``tree`` sharded on its leading (parts)
    axis.  Leading dims must be divisible by the mesh size.

    Multi-process (jax.distributed): ``num_parts`` gives the global
    leading dim.  Arrays carrying all ``num_parts`` rows are split into
    per-local-device shards; arrays carrying only this process's rows
    (ShardedGraph built with ``parts=``) are assembled with
    ``jax.make_array_from_process_local_data`` — the analogue of the
    reference's per-node region instances that Legion stitches into one
    logical region (reference push_model.inl:8-51).
    """
    sharding = parts_spec(mesh)
    multiproc = jax.process_count() > 1

    def place(x):
        if x is None:
            return None
        if not multiproc:
            return jax.device_put(x, sharding)
        if num_parts is None or x.shape[0] == num_parts:
            # full array present on every process: hand each local
            # device its slice
            idx_map = sharding.addressable_devices_indices_map(x.shape)
            shards = [jax.device_put(np.asarray(x[idx]), d)
                      for d, idx in idx_map.items()]
            return jax.make_array_from_single_device_arrays(
                x.shape, sharding, shards)
        gshape = (num_parts,) + tuple(x.shape[1:])
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(x), gshape)

    return jax.tree.map(place, tree)
