"""lux_tpu — a TPU-native distributed graph-processing framework.

A from-scratch reimplementation of the capabilities of Lux (Jia et al.,
"A Distributed Multi-GPU System for Fast Graph Processing", PVLDB 11(3),
2017; reference tree at /root/reference) designed for TPU hardware:

- compute path: JAX/XLA (gathers + segmented reductions on the VPU/MXU),
  with optional Pallas kernels for the hot edge loops;
- distribution: ``jax.sharding.Mesh`` + ``shard_map`` over a ``parts``
  axis, with the per-iteration vertex-state exchange expressed as
  ``lax.all_gather`` over ICI (the reference's Legion/GASNet region
  all-gather, see reference core/pull_model.inl:454-469);
- convergence-driven apps compile the *entire* run into one XLA program
  (``lax.while_loop`` + ``psum`` halt detection), replacing the
  reference's SLIDING_WINDOW=4 host-pipelining trick
  (reference sssp/sssp.cc:111-129) with zero host round-trips;
- host-side native tooling (graph converter, partition-slice file
  loader) implemented in C++ (lux_tpu/native/).

Layout:
  format.py     .lux binary CSC file format (read/write/inspect)
  convert.py    edge-list <-> .lux conversion + synthetic generators (RMAT)
  partition.py  edge-balanced contiguous vertex partitioner
  graph.py      host Graph + padded device-resident ShardedGraph layout
  ops/          segmented reductions (XLA + Pallas fast paths)
  engine/       pull (dense gather-apply) and push (frontier) engines
  parallel/     mesh construction and sharding helpers
  apps/         PageRank, SSSP/BFS, ConnectedComponents, CollabFilter
  check.py      fixed-point correctness audits (the reference's -check)
  audit.py      compile-time program auditor (jaxpr invariant checks;
                repo-wide: python -m lux_tpu.audit)
  observe.py    session-calibration probe, link calibration and
                bench.py's ledger
  livegraph.py  live graphs: CRC-chained mutation WAL, snapshot-
                isolated epochs, incremental revalidation, chaos-
                drilled compaction (round 20, ROADMAP item 4)
  native/       C++ converter CLI and partition-slice loader
"""

__version__ = "0.1.0"

from lux_tpu.format import LuxFileHeader, read_lux, write_lux, peek_lux
from lux_tpu.graph import Graph, ShardedGraph
from lux_tpu.partition import edge_balanced_bounds

# round-9 guarded-execution typed errors, re-exported for callers
# that catch rather than build (see ARCHITECTURE.md "Data integrity
# & guarded execution")
from lux_tpu.checkpoint import CorruptCheckpointError
from lux_tpu.format import GraphFormatError
from lux_tpu.health import HealthError

# round-10 static-guarantee typed error (ARCHITECTURE.md "Static
# guarantees"); the check-specific subclasses live in lux_tpu.audit.
# Lazy (module __getattr__): an eager import here would pre-load
# lux_tpu.audit into sys.modules and make ``python -m lux_tpu.audit``
# execute the module twice (runpy RuntimeWarning + duplicate class
# objects that break isinstance across the copies).


def __getattr__(name):
    if name == "AuditError":
        from lux_tpu.audit import AuditError
        return AuditError
    # round-20 live-graph typed errors: lazy for the same
    # python -m double-import reason as AuditError
    if name in ("LiveGraphError", "MutationLogError",
                "DeltaFullError"):
        from lux_tpu import livegraph
        return getattr(livegraph, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
