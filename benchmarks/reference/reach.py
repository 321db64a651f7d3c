"""Seeded reachability, from the reference's frontier search: the label
of a vertex is the seed where the seed reaches it over out-edges (the
seed itself included) and -1 elsewhere — what max-label propagation
from one seeded vertex converges to (``lux_tpu/apps/components.py``,
batched form)."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import bfs


def labels_from_levels(levels, seed: int):
    """The reachability labels that the hop levels of a search from
    ``seed`` imply (-1 = not reached)."""
    levels = np.asarray(levels)
    return np.where(levels >= 0, np.int32(seed),
                    np.int32(-1)).astype(np.int32)


def reach_labels(offsets, neighbours, seed: int):
    return labels_from_levels(
        bfs.bfs_levels(offsets, neighbours, seed), seed)
