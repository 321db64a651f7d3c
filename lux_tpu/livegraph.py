"""Live graphs: crash-consistent mutation log, snapshot-isolated
epochs, incremental revalidation, and chaos-drilled compaction.

The reference is a static-graph batch system (its graphs are loaded
once and never mutate, reference pull_model.inl:253-320); the serving
tier built in rounds 14-18 answers live traffic against that frozen
snapshot.  This module makes the graph MUTABLE underneath the queries
with a robustness-first correctness story (ROADMAP item 4):

1. **Durable mutation log** (:class:`MutationLog`): every edge append
   is journaled to a CRC-CHAINED append-only WAL before it is
   visible — record i's CRC32 seeds from record i-1's
   (checkpoint.chained_crc32, the same per-leaf CRC discipline the
   checkpoints carry), so a torn mid-append write (power loss, the
   injected ``faults.WAL_TORN``) breaks the chain at the exact tear
   point.  Replay truncates a torn TAIL and recovers the precise
   pre-append state (bitwise — tests/test_livegraph.py); a broken
   chain FOLLOWED by further whole records cannot be a torn append
   and raises a typed :class:`MutationLogError` instead of replaying
   garbage.  The on-disk header format lives with the other formats
   (format.py ``read_wal_header``: magic/version/nv/capacity — a log
   from a DIFFERENT graph errors instead of replaying foreign
   mutations).

2. **Fixed-capacity delta blocks, snapshot-isolated epochs**:
   published mutations land in fixed-capacity host arrays
   (src/dst/weight/epoch) that are passed to the engines' delta-relax
   step as jit ARGUMENTS — no pair/page plan rebuild, no recompile,
   per append (the Ragged-Paged-Attention idiom from PAPERS.md:
   ragged growth through fixed-shape blocks).  Isolation is BY
   CONSTRUCTION: a published slot is never rewritten (compaction
   swaps in FRESH arrays rather than zeroing), the base generation's
   arrays are never mutated in place, unwritten slots carry an
   i32-max epoch sentinel written LAST — so a reader pinned to epoch
   e sees exactly the edges with ``d_epoch <= e`` no matter how the
   writer thread interleaves, and a torn read is impossible rather
   than merely unlikely.  ``epoch`` is a monotone counter advanced
   once per published append batch; scripts/events_summary.py FAILS
   any trail whose answers were computed at a different epoch than
   their admission pinned (the torn-epoch audit).

3. **Incremental revalidation** (:meth:`LiveGraph.revalidate`):
   frontier-seeded re-convergence — the delta-relax step gathers the
   delta sources from the state table (ONE state-table gather,
   machine-checked against the same audit gather budget as the dense
   iterations: lux_tpu/audit.py matrix configs ``*_live_delta``),
   relaxes the delta edges, epoch-masks per query column, scatters
   min/max into the table, and activates improved destinations; the
   push engine then re-converges only the reachable-from-touched
   region.  NumPy incremental oracles came FIRST per convention
   (apps/sssp.reference_sssp_incremental,
   components.reference_components_incremental) and the device path
   is proved equal to full recompute at the same epoch, bitwise for
   the integer apps.  Measured on CPU it beats full recompute across
   the touched-fraction sweep (scripts/sweep_live.py; PERF_NOTES
   round 20).  Round 21 extends the algebra past monotone appends:
   edge DELETIONS (:meth:`LiveGraph.delete_edges`) and WEIGHT
   UPDATES (:meth:`LiveGraph.reweight_edges`) journal as v2 WAL
   record kinds and publish TOMBSTONE/OVERWRITE delta slots (masked
   to the reduce identity by the delta relax — a monotone step
   cannot express them); revalidation past such an op dispatches to
   the ANTI-MONOTONE RE-SEED — compute the affected cone (forward
   reachability from the touched destinations, capped by
   ``cone_cap`` with a full-recompute fallback), re-seed it from the
   program's init labels, and re-converge over ``graph_at(epoch)``
   — proved equal to full recompute against the decremental oracles
   (apps/sssp.reference_sssp_decremental,
   components.reference_components_decremental), bitwise for the
   integer apps.

4. **Scheduled compaction** (:meth:`LiveGraph.compact`,
   :class:`CompactionScheduler`): the delta folds into the base
   layout via the shared deterministic ``_apply_ops`` construction
   (origin + full op history — the same rule graph_at and recover
   use, so live, oracle, and recovered bases are bitwise-identical)
   and the generation swaps ATOMICALLY under the lock: readers see
   the old (base, delta) pair or the new one, never a mixture.  The
   WAL brackets the fold with COMPACT_START/COMPACT_DONE markers; an
   injected crash between them (``faults.COMPACT_CRASH``) leaves a
   START without a DONE, and recovery comes up on the SURVIVING
   generation (origin base + full replay) — compaction is a LAYOUT
   transition, never a durability transition, so a half-built
   generation can always be discarded.  WHEN to fold is the
   scheduler's call (round 21): :meth:`compact_economics` prices the
   standing delta drag (MEASURED per-boundary samples from the serve
   runners when available, the scalemodel term otherwise) and the
   :class:`CompactionScheduler` weighs it against admission load,
   pending anti-monotone ops, and the fleet's SLO burn gauge —
   picking fold windows under live traffic instead of the old
   compact-between-drains heuristic.  Serving-tier backpressure:
   when ingest outruns compaction the delta blocks fill and
   mutations raise a typed :class:`DeltaFullError`, which the
   fleet's admission sheds as ``AdmissionError(reason="delta_full")``
   (lux_tpu/fleet.py).

Epoch visibility per engine family: the PUSH kinds (sssp /
components) see base + published delta at the latest epoch — their
monotone min/max programs absorb delta APPENDS exactly through the
delta-relax step.  The PULL kinds (pagerank) absorb appends through
the base-generation + degree-correction step (serve.py
PullBatchRunner, round 21; device programs since PR 27), so both
families' admissions advance
with published epochs WITHOUT waiting for a fold.  The one cap is
anti-monotone: while a deletion/reweight is pending (not yet folded),
``view_epoch`` holds BOTH families at (earliest pending anti epoch -
1) — neither mechanism can express the op, so the op costs admission
FRESHNESS, never correctness.  Every pinning is recorded at
admission and audited at answer time (serve.py /
scripts/events_summary.py).

Durability scope: the WAL journals MUTATIONS; the base graph is the
caller's (a .lux file or a deterministic generator spec), so recovery
is ``LiveGraph.recover(origin_graph, wal_path)`` — replay the full
log onto the origin and re-fold any completed compactions
(deterministic, hence bitwise).  ``graph_at(epoch)`` materializes the
host Graph as of any epoch — the NumPy-oracle surface every
live-serving answer is checked against (O(total mutations) host
memory; a diagnostic/test surface, documented as such).
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
import weakref

import numpy as np

from lux_tpu import format as luxfmt
from lux_tpu.checkpoint import chained_crc32
from lux_tpu.graph import Graph

# WAL record kinds (format.py owns the header; the 24-byte record
# layout is [epoch u32, kind u32, a u32, b u32, c u32, crc u32] with
# crc = chained_crc32(first 20 bytes, prev record's crc; the chain
# seeds from the header's CRC so a re-headered log cannot re-validate)
REC_EDGE = 1           # a=src, b=dst, c=float32 weight bits
REC_COMPACT_START = 2  # a=delta count folded, b=new generation
REC_COMPACT_DONE = 3   # a=new generation, b=base epoch after fold
# v2 record kinds (round 21, the full mutation algebra).  The record
# LAYOUT is unchanged, so a v1 log replays bitwise under this reader;
# a v2 kind inside a v1-headered log is typed record_kind corruption
# (the kind set is part of the header version's contract).
REC_DELETE = 4         # a=src, b=dst, c=0
REC_REWEIGHT = 5       # a=src, b=dst, c=new float32 weight bits

_V1_KINDS = frozenset((REC_EDGE, REC_COMPACT_START, REC_COMPACT_DONE))
_V2_KINDS = _V1_KINDS | {REC_DELETE, REC_REWEIGHT}
_KINDS_BY_VERSION = {1: _V1_KINDS, 2: _V2_KINDS}

# delta-slot kinds (the d_kind column).  A published DELETE/REWEIGHT
# slot is a TOMBSTONE/OVERWRITE marker: it consumes a delta slot (so
# occupancy prices it and DeltaFullError backpressure covers it) but
# the monotone delta-relax step masks it to the reduce identity — its
# effect reaches answers only through the anti-monotone admission cap
# (view_epoch) + re-seed / compaction fold, never through a monotone
# relax that cannot express it.
DK_APPEND = 0
DK_DELETE = 1
DK_REWEIGHT = 2

_REC_BY_OP = {"append": REC_EDGE, "delete": REC_DELETE,
              "reweight": REC_REWEIGHT}
_DK_BY_OP = {"append": DK_APPEND, "delete": DK_DELETE,
             "reweight": DK_REWEIGHT}
_OP_BY_REC = {REC_EDGE: "append", REC_DELETE: "delete",
              REC_REWEIGHT: "reweight"}

# unwritten delta slots carry this epoch sentinel (written LAST in a
# slot publish) so a concurrent reader's epoch mask can never see a
# half-written slot — the torn-read-free-by-construction invariant
EPOCH_SENTINEL = np.int32(np.iinfo(np.int32).max)

# nominal host prices for the pointer-structured live consumers
# (memory_terms, round 22): CPython has no portable exact size for a
# list-of-tuples or a Counter entry, so the unified ledger prices the
# DOCUMENTED nominal per entry — a 5-tuple history op (~tuple header
# + 5 boxed fields + list slot) and a Counter entry (~dict slot +
# key 2-tuple + two boxed ints).  What matters observably is the
# O(count) growth these make visible, not malloc jitter; the NumPy
# oracle re-derives the same formula bitwise.
HISTORY_ENTRY_BYTES = 112
MULTISET_ENTRY_BYTES = 96


class LiveGraphError(RuntimeError):
    """Base of the live-graph subsystem's typed failures."""


class MutationLogError(LiveGraphError):
    """The mutation log failed verification.  Carries ``path``,
    ``check`` (torn_tail / crc_chain / epoch_order / record_kind /
    compact_pair / capacity_overflow / wal_exists) and ``detail`` —
    the same typed-diagnosis shape as
    format.GraphFormatError, consumed by scripts/fsck_lux.py (exit
    2).  ``torn_tail`` is the RECOVERABLE class: replay truncates it;
    every other check is hard corruption that must never replay."""

    def __init__(self, path: str, check: str, detail: str):
        super().__init__(f"{path}: mutation log [{check}] — {detail}")
        self.path = path
        self.check = check
        self.detail = detail


class DeltaFullError(LiveGraphError):
    """The fixed-capacity delta blocks are full: ingest has outrun
    compaction.  The serving tier's admission converts this into the
    typed ``AdmissionError(reason="delta_full")`` backpressure shed
    (lux_tpu/fleet.py) instead of blocking or silently dropping."""

    def __init__(self, capacity: int):
        super().__init__(
            f"delta blocks full ({capacity} slots): compact before "
            f"appending more mutations")
        self.capacity = capacity


class CompactPinnedError(LiveGraphError):
    """compact() was called while queries still pin the current
    generation — swapping under them would un-mask base edges newer
    than their admission epochs (a torn read by another name).  The
    serving layer compacts between drains, when nothing is
    resident."""


def _emit(kind: str, **fields):
    from lux_tpu import telemetry
    telemetry.current().emit(kind, **fields)


@dataclasses.dataclass(frozen=True)
class WalRecord:
    epoch: int
    kind: int
    a: int
    b: int
    c: int


def _pack_record(epoch: int, kind: int, a: int, b: int, c: int,
                 prev_crc: int) -> bytes:
    body = np.array([epoch, kind, a, b, c],
                    luxfmt.V_DTYPE).tobytes()
    crc = chained_crc32(body, prev_crc)
    return body + np.array([crc], luxfmt.V_DTYPE).tobytes()


class MutationLog:
    """The CRC-chained append-only WAL (module docstring pillar 1).

    One instance owns an open append handle; each ``append_*`` writes
    one 24-byte record and fsyncs — durability is per record, so a
    crash between two records of a batch replays the durable prefix
    (the documented half-batch semantics).  ``replay`` is a
    classmethod: verify the chain, truncate a torn tail (emitting a
    ``wal_truncate`` telemetry event), raise typed MutationLogError
    on anything that cannot be a torn append."""

    def __init__(self, path: str, nv: int, capacity: int,
                 version: int = luxfmt.WAL_VERSION,
                 _resume: tuple | None = None):
        self.path = path
        self.nv = int(nv)
        self.capacity = int(capacity)
        self.version = int(version)
        self.records = 0        # records appended THROUGH this handle
        if _resume is None:
            header = luxfmt.pack_wal_header(self.nv, self.capacity,
                                            version=self.version)
            try:
                fd = os.open(path,
                             os.O_WRONLY | os.O_CREAT | os.O_EXCL,
                             0o644)
            except FileExistsError:
                # the restart-after-crash path is the very situation
                # the WAL exists for — refuse typed, pointing at the
                # recovery entry, never an opaque builtin traceback
                raise MutationLogError(
                    path, "wal_exists",
                    "a mutation log already exists at this path — "
                    "a fresh log would orphan its durable history; "
                    "use LiveGraph.recover(g, path) to replay it, "
                    "or remove the file to start over") from None
            self._f = os.fdopen(fd, "wb")
            self._f.write(header)
            self._f.flush()
            os.fsync(self._f.fileno())
            self._crc = chained_crc32(header)
        else:
            size, crc = _resume
            self._f = open(path, "r+b")
            self._f.seek(size)
            self._crc = crc

    # -- append side ---------------------------------------------------

    def _append(self, record: bytes) -> None:
        self._f.write(record)
        self._f.flush()
        os.fsync(self._f.fileno())
        self._crc = int(np.frombuffer(record, luxfmt.V_DTYPE)[5])
        self.records += 1

    def buffer_bytes(self) -> int:
        """Bytes the open append handle accounts for in the unified
        byte ledger (lux_tpu/memwatch.py, round 22): the header plus
        every record appended through THIS handle — the page-cache /
        stream-buffer footprint of the append path.  Per-record fsync
        keeps the userspace buffer empty, so this is an upper bound
        on dirty bytes and exact on what the handle wrote."""
        return (luxfmt.WAL_HEADER_SIZE
                + self.records * luxfmt.WAL_RECORD_SIZE)

    def pack_edge(self, epoch: int, src: int, dst: int,
                  wbits: int) -> bytes:
        return _pack_record(epoch, REC_EDGE, src, dst, wbits,
                            self._crc)

    def pack_mutation(self, epoch: int, op: str, src: int, dst: int,
                      wbits: int) -> bytes:
        """Pack one mutation record of any op (append / delete /
        reweight) against the CURRENT chain position — the
        fault-injection hook (WAL_TORN) needs the exact bytes the
        append would write."""
        kind = _REC_BY_OP[op]
        if kind not in _KINDS_BY_VERSION[self.version]:
            raise MutationLogError(
                self.path, "record_kind",
                f"op {op!r} (record kind {kind}) is not in the "
                f"v{self.version} header's kind set — recover into a "
                f"fresh v{luxfmt.WAL_VERSION} log to use the full "
                f"mutation algebra")
        return _pack_record(epoch, kind, src, dst, wbits, self._crc)

    def append_edge(self, epoch: int, src: int, dst: int,
                    wbits: int) -> None:
        self._append(self.pack_edge(epoch, src, dst, wbits))

    def append_mutation(self, epoch: int, op: str, src: int,
                        dst: int, wbits: int) -> None:
        self._append(self.pack_mutation(epoch, op, src, dst, wbits))

    def append_marker(self, epoch: int, kind: int, a: int,
                      b: int) -> None:
        self._append(_pack_record(epoch, kind, a, b, 0, self._crc))

    def write_torn(self, record: bytes) -> None:
        """Fault-injection hook (faults.MutationFaultPlan WAL_TORN):
        persist a STRICT PREFIX of ``record`` — what a power loss
        mid-append leaves on disk — and fsync it so the tear is
        really there for the replay to diagnose."""
        self._f.write(record[:len(record) // 2])
        self._f.flush()
        os.fsync(self._f.fileno())

    def close(self) -> None:
        try:
            self._f.close()
        except OSError:
            pass

    # -- replay / verify side ------------------------------------------

    @classmethod
    def scan(cls, path: str, nv: int | None = None):
        """Verify the whole log WITHOUT modifying it.  Returns
        (records, header_nv, capacity, torn_bytes): ``torn_bytes`` is
        the length of a recoverable torn tail (0 = clean); hard
        corruption raises MutationLogError.  scripts/fsck_lux.py's
        WAL leg and ``replay`` both run through here so the checker
        and the recovery path can never disagree on validity."""
        recs, hnv, cap, tail, _crc, _ver = cls._scan(path, nv=nv)
        return recs, hnv, cap, tail

    @classmethod
    def _scan(cls, path: str, nv: int | None = None):
        """scan + the final chain CRC (the resume seed), so replay
        never re-reads the file to recompute a chain the scan just
        walked."""
        with open(path, "rb") as f:
            blob = f.read()
        head = blob[:luxfmt.WAL_HEADER_SIZE]
        hnv, cap, ver = luxfmt.read_wal_header(path, nv=nv, head=head)
        known = _KINDS_BY_VERSION[ver]
        crc = chained_crc32(head)
        recs: list[WalRecord] = []
        off = luxfmt.WAL_HEADER_SIZE
        R = luxfmt.WAL_RECORD_SIZE
        last_epoch = 0
        bad_at = None
        while off + R <= len(blob):
            raw = blob[off:off + R]
            words = np.frombuffer(raw, luxfmt.V_DTYPE)
            want = chained_crc32(raw[:20], crc)
            if int(words[5]) != want:
                bad_at = off
                break
            epoch, kind = int(words[0]), int(words[1])
            if kind not in known:
                extra = (f" (a v2 mutation kind inside a v{ver} "
                         f"header — the kind set is part of the "
                         f"version contract)"
                         if kind in _V2_KINDS else
                         " — log written by a newer/foreign build")
                raise MutationLogError(
                    path, "record_kind",
                    f"record at byte {off} has kind {kind} outside "
                    f"the v{ver} kind set with a VALID chain CRC"
                    f"{extra}, refusing to replay")
            if epoch < last_epoch:
                raise MutationLogError(
                    path, "epoch_order",
                    f"record at byte {off} carries epoch {epoch} "
                    f"after epoch {last_epoch} — the monotone epoch "
                    f"counter never goes backwards; the log is "
                    f"corrupt or spliced")
            last_epoch = epoch
            recs.append(WalRecord(epoch, kind, int(words[2]),
                                  int(words[3]), int(words[4])))
            crc = int(words[5])
            off += R
        tail = len(blob) - off
        if bad_at is not None:
            # a torn append can only leave a STRICT PREFIX of the
            # record on disk (the writer's model: faults.WAL_TORN;
            # a complete record that landed carries its valid CRC) —
            # those never reach here (the loop stops short of a
            # partial record and reports them as ``tail``).  A
            # FULL-SIZE bad-CRC record is rot of a possibly-fsync-
            # acknowledged append, and one with further records
            # behind it is mid-file corruption — both must refuse,
            # never silently truncate an acknowledged mutation away
            behind = len(blob) - bad_at - R
            what = (f"with {behind} byte(s) of further records "
                    f"behind it — mid-file corruption"
                    if behind else
                    "at full record size — corruption of a "
                    "possibly-acknowledged final record")
            raise MutationLogError(
                path, "crc_chain",
                f"record at byte {bad_at} fails the CRC chain "
                f"{what}, not a torn append; refusing to replay")
        return recs, hnv, cap, tail, crc, ver

    @classmethod
    def replay(cls, path: str, nv: int | None = None):
        """Crash-recovery entry: scan, TRUNCATE a torn tail in place
        (the pre-append state is the correct durable state — the torn
        record was never acknowledged), and return (records,
        truncated_bytes, resumable MutationLog open at the end)."""
        recs, hnv, cap, torn, crc, ver = cls._scan(path, nv=nv)
        good = luxfmt.WAL_HEADER_SIZE + len(recs) * luxfmt.WAL_RECORD_SIZE
        if torn:
            with open(path, "r+b") as f:
                f.truncate(good)
                f.flush()
                os.fsync(f.fileno())
            _emit("wal_truncate", path=path, torn_bytes=int(torn),
                  records=len(recs))
        # the scan's final chain CRC IS the resume seed — no second
        # read of the file, no recomputed chain.  The resumed log
        # keeps the HEADER'S version: appends to a recovered v1 log
        # stay within the v1 kind set (pack_mutation refuses typed).
        log = cls(path, hnv, cap, version=ver, _resume=(good, crc))
        return recs, torn, log


# ---------------------------------------------------------------------
# the live graph


def _apply_ops(origin: Graph, ops, weighted: bool) -> Graph:
    """Deterministic host construction of origin + a mutation-op
    prefix ``[(op, src, dst, w, epoch), ...]`` — the ONE targeting
    rule every fold surface shares (graph_at, compact, recover), so
    the live view, the compacted base, and the recovered base are
    bitwise-identical by construction.

    Targeting: a delete/reweight of (s, d) hits the FIRST surviving
    base edge in dst-sorted ``edge_arrays`` order, else the first
    live appended edge (publish order).  The pure-append prefix
    reduces to exactly ``Graph.with_edges``'s construction (same
    concatenation into ``from_edges``), so pre-algebra logs fold
    bitwise-identically to the round-20 code."""
    if not ops:
        return origin
    base_src, base_dst = origin.edge_arrays()
    base_w = (np.asarray(origin.weights, np.float32).copy()
              if weighted else None)
    alive = np.ones(origin.ne, dtype=bool)
    app_src: list = []
    app_dst: list = []
    app_w: list = []
    app_alive: list = []
    base_ix: dict = {}
    app_ix: dict = {}
    if any(h[0] != "append" for h in ops):
        for i, sd in enumerate(zip(base_src.tolist(),
                                   base_dst.tolist())):
            base_ix.setdefault(sd, []).append(i)
    for h in ops:
        op, s, d, w = h[0], int(h[1]), int(h[2]), h[3]
        if op == "append":
            app_ix.setdefault((s, d), []).append(len(app_src))
            app_src.append(s)
            app_dst.append(d)
            app_w.append(np.float32(w))
            app_alive.append(True)
            continue
        tgt = next((i for i in base_ix.get((s, d), ())
                    if alive[i]), None)
        if op == "delete":
            if tgt is not None:
                alive[tgt] = False
            else:
                j = next(i for i in app_ix.get((s, d), ())
                         if app_alive[i])
                app_alive[j] = False
        else:  # reweight
            if tgt is not None:
                base_w[tgt] = np.float32(w)
            else:
                j = next(i for i in app_ix.get((s, d), ())
                         if app_alive[i])
                app_w[j] = np.float32(w)
    keep = [i for i, ok in enumerate(app_alive) if ok]
    src = np.concatenate([base_src[alive],
                          np.array([app_src[i] for i in keep],
                                   np.int64)])
    dst = np.concatenate([base_dst[alive],
                          np.array([app_dst[i] for i in keep],
                                   np.int64)])
    w_all = None
    if weighted:
        w_all = np.concatenate([base_w[alive],
                                np.array([app_w[i] for i in keep],
                                         np.float32)])
    return Graph.from_edges(src, dst, origin.nv, weights=w_all)


class LiveGraph:
    """Mutable graph = base generation + fixed-capacity delta blocks
    + monotone epochs (module docstring).  Thread contract: appends
    take the lock; readers snapshot ``(epoch, count)`` lock-free and
    epoch-mask — published slots are immutable and unwritten slots
    carry the EPOCH_SENTINEL, so a reader can never observe a torn
    slot regardless of interleaving."""

    def __init__(self, g: Graph, *, capacity: int = 1024,
                 wal_path: str | None = None,
                 fault=None, compact_threshold: float = 0.75,
                 cone_cap: float = 0.5,
                 _recovering: bool = False):
        if capacity < 1:
            raise ValueError(f"delta capacity {capacity} must be >= 1")
        if not 0.0 < compact_threshold <= 1.0:
            raise ValueError(f"compact_threshold {compact_threshold} "
                             f"must be in (0, 1]")
        if not 0.0 < cone_cap <= 1.0:
            raise ValueError(f"cone_cap {cone_cap} must be in (0, 1]")
        self.origin = g               # recovery/oracle anchor
        self.base = g                 # current generation's base
        self.base_epoch = 0           # epoch folded into the base
        self.generation = 0
        self.epoch = 0                # latest published epoch
        self.capacity = int(capacity)
        self.weighted = g.weights is not None
        self.compact_threshold = float(compact_threshold)
        self.cone_cap = float(cone_cap)
        self.fault = fault
        self._lock = threading.Lock()
        self._fresh_delta()
        self.count = 0                # published delta slots
        self.pins = 0                 # RESIDENT queries on this gen
        self.admitted = 0             # admitted-but-unretired queries
        self.mutations = 0            # mutations ever published
        self.deletions = 0            # deletion ops ever published
        self.reweights = 0            # reweight ops ever published
        self.reseeds = 0              # anti-monotone re-seeds run
        self.reseed_fallbacks = 0     # ... that fell back to full
        self.compactions = 0
        self.peak_count = 0
        # full publish history [(op, src, dst, w, epoch)] — the
        # graph_at/oracle surface (O(total mutations) host memory;
        # diagnostic/test scope, module docstring)
        self._history: list[tuple] = []
        # pending ANTI-MONOTONE ops [(epoch, op, src, dst)] not yet
        # folded into the base — while nonempty, view_epoch caps
        # admission at (min anti epoch - 1) for BOTH families: a
        # monotone delta relax cannot express a deletion/reweight, so
        # serving past it would answer BELOW/ABOVE the true fixed
        # point, the error class the torn-epoch audit is blind to.
        self._anti: list[tuple] = []
        # measured per-slot delta drag samples (ns), fed by the serve
        # runners (record_drag_sample) for the scheduler's economics
        # — bounded deque, newest-biased median
        self._drag_samples = collections.deque(maxlen=64)
        # live-edge multiset (src, dst) -> count, built LAZILY on the
        # first anti-monotone mutation (delete/reweight of an edge
        # that does not exist must refuse typed BEFORE journaling)
        self._edge_counts = None
        self._graph_cache: dict[int, Graph] = {}
        self._slot_cache: dict[int, tuple] = {}
        self._vslot_cache: dict[int, tuple] = {}  # geometry-keyed
        self._step_cache: dict[int, object] = {}
        self._wal: MutationLog | None = None
        if wal_path is not None and not _recovering:
            self._wal = MutationLog(wal_path, g.nv, capacity)

    def _fresh_delta(self) -> None:
        # FRESH arrays on every generation swap — a concurrent reader
        # holding the old arrays keeps a consistent published block
        # (immutable-once-published), never a zeroed-under-it one
        cap = self.capacity
        self.d_src = np.zeros(cap, np.int32)
        self.d_dst = np.zeros(cap, np.int32)
        self.d_w = np.zeros(cap, np.float32)
        self.d_kind = np.zeros(cap, np.int32)   # DK_APPEND default
        self.d_epoch = np.full(cap, EPOCH_SENTINEL, np.int32)

    # -- ingest --------------------------------------------------------

    def _check_pair(self, src, dst, what: str):
        """Shared shape/endpoint validation for every mutation op."""
        src = np.atleast_1d(np.asarray(src, np.int64))
        dst = np.atleast_1d(np.asarray(dst, np.int64))
        n = len(src)
        if len(dst) != n:
            raise ValueError(f"{what} src/dst length mismatch "
                             f"({n} vs {len(dst)})")
        nv = self.base.nv
        if src.size and (int(src.max()) >= nv or int(src.min()) < 0
                         or int(dst.max()) >= nv or int(dst.min()) < 0):
            raise ValueError(f"{what}: edge endpoint outside "
                             f"[0, {nv})")
        return src, dst, n

    def _live_edge_counts(self):
        """The (src, dst) -> live-multiplicity multiset, built LAZILY
        on the first anti-monotone mutation and maintained
        incrementally by ``_publish`` afterwards — a delete/reweight
        of an edge that does not exist must refuse typed BEFORE the
        WAL journals anything (a journaled phantom op would replay on
        every recovery)."""
        if self._edge_counts is None:
            src, dst = self.origin.edge_arrays()
            counts = collections.Counter(
                zip(src.tolist(), dst.tolist()))
            for h in self._history:
                if h[0] == "append":
                    counts[(h[1], h[2])] += 1
                elif h[0] == "delete":
                    counts[(h[1], h[2])] -= 1
            self._edge_counts = counts
        return self._edge_counts

    def _publish(self, op: str, src, dst, w) -> int:
        """Shared publish core for every mutation op (WAL journal ->
        delta slot -> epoch advance); callers validated shapes,
        weights, and endpoints.  The batch becomes ONE new epoch
        (visible the moment ``self.epoch`` advances, after every slot
        is fully written).  Anti-monotone existence validation runs
        HERE, under the same lock as the journal write — a check in
        the caller could race a concurrent delete of the same edge."""
        n = len(src)
        dk = _DK_BY_OP[op]
        with self._lock:
            if self.count + n > self.capacity:
                raise DeltaFullError(self.capacity)
            if dk != DK_APPEND:
                counts = self._live_edge_counts()
                need = collections.Counter(
                    zip(src.tolist(), dst.tolist()))
                for (s, d), k in need.items():
                    # deletions CONSUME multiplicity; reweights of the
                    # same edge restate it (last wins), needing one
                    required = k if dk == DK_DELETE else 1
                    have = counts[(s, d)]
                    if have < required:
                        raise ValueError(
                            f"{op} of edge ({s}, {d}): {have} live "
                            f"edge(s), batch needs {required} — "
                            f"refusing before journaling (mutations "
                            f"of phantom edges would replay on every "
                            f"recovery)")
            epoch = self.epoch + 1
            for i in range(n):
                s, d = int(src[i]), int(dst[i])
                wbits = int(np.float32(w[i]).view(np.uint32))
                if self.fault is not None:
                    record = (self._wal.pack_mutation(
                        epoch, op, s, d, wbits)
                        if self._wal is not None else b"")
                    self.fault.fire_append(self._wal, record, op=op)
                if self._wal is not None:
                    self._wal.append_mutation(epoch, op, s, d, wbits)
                slot = self.count
                self.d_src[slot] = s
                self.d_dst[slot] = d
                self.d_w[slot] = w[i]
                self.d_kind[slot] = dk
                # epoch LAST: a concurrent reader's epoch mask never
                # admits a half-written slot
                self.d_epoch[slot] = epoch
                self.count = slot + 1
                self._history.append((op, s, d, float(w[i]), epoch))
                if self._edge_counts is not None:
                    if op == "append":
                        self._edge_counts[(s, d)] += 1
                    elif op == "delete":
                        self._edge_counts[(s, d)] -= 1
                if dk != DK_APPEND:
                    self._anti.append((epoch, op, s, d))
            self.mutations += n
            if op == "delete":
                self.deletions += n
            elif op == "reweight":
                self.reweights += n
            self.peak_count = max(self.peak_count, self.count)
            self.epoch = epoch
        # the wal path keys the events_summary CROSS-process
        # replay-regression audit: a crash and its recovery are
        # different processes, so the publisher's epochs and the
        # recovering wal_replay pair on the log path, not the run
        wal_kw = ({"wal": self._wal.path}
                  if self._wal is not None else {})
        _emit("mutation", op=op, edges=int(n), epoch=int(epoch),
              delta_count=int(self.count),
              occupancy=round(self.count / self.capacity, 4),
              **wal_kw)
        _emit("epoch_advance", from_epoch=int(epoch - 1),
              to_epoch=int(epoch), **wal_kw)
        return epoch

    def append_edges(self, src, dst, weights=None) -> int:
        """Publish one edge-append batch: WAL-journal then
        delta-publish each edge; the batch becomes ONE new epoch.
        Returns the new epoch.  Raises DeltaFullError when the batch
        does not fit (the admission backpressure signal),
        MutationLogError/InjectedWorkerCrash from the fault plan's
        crash legs."""
        src, dst, n = self._check_pair(src, dst, "append_edges")
        if n == 0:
            return self.epoch
        if self.weighted:
            if weights is None:
                raise ValueError("weighted live graph needs weights "
                                 "for every appended edge")
            w = np.atleast_1d(np.asarray(weights, np.float32))
            if len(w) != n:
                raise ValueError(
                    f"append_edges src/weights length mismatch "
                    f"({n} vs {len(w)})")
        else:
            if weights is not None:
                # Graph.with_edges refuses this same mismatch typed —
                # silently zeroing the caller's weight data would
                # journal 0.0 bits and serve hop-count semantics with
                # no signal that the weights vanished
                raise ValueError(
                    "append_edges got weights for an UNWEIGHTED live "
                    "graph — build the LiveGraph over a weighted "
                    "base, or drop the weights")
            w = np.zeros(n, np.float32)
        return self._publish("append", src, dst, w)

    def delete_edges(self, src, dst) -> int:
        """Publish one edge-DELETION batch (round 21, the mutation
        algebra).  Each (src, dst) tombstones exactly ONE live edge —
        the first surviving base edge in dst-sorted order, else the
        first live appended edge (the deterministic targeting rule
        ``_apply_ops`` shares between graph_at, compaction, and
        recovery, so every surface folds the same edge away).
        Deleting an edge that does not exist raises ValueError BEFORE
        the WAL journals anything.  Deletions are ANTI-MONOTONE: the
        published tombstone slot consumes delta capacity but is
        masked to the reduce identity by the delta-relax step; its
        effect reaches answers through the ``view_epoch`` admission
        cap and the re-seed (:meth:`revalidate`) / compaction fold.
        NumPy oracles: apps/sssp.reference_sssp_decremental,
        apps/components.reference_components_decremental.  Returns
        the new epoch."""
        src, dst, n = self._check_pair(src, dst, "delete_edges")
        if n == 0:
            return self.epoch
        return self._publish("delete", src, dst,
                             np.zeros(n, np.float32))

    def reweight_edges(self, src, dst, weights) -> int:
        """Publish one edge WEIGHT-UPDATE batch (round 21).  Targets
        one live edge per (src, dst) under the same deterministic
        rule as :meth:`delete_edges`; reweighting a phantom edge or
        an UNWEIGHTED live graph refuses typed before journaling.
        Conservatively ANTI-MONOTONE for BOTH engine families: a
        weight increase can raise converged sssp distances (which a
        monotone min-relax can never repair), and rather than
        special-case the decrease-only direction the admission cap +
        re-seed path covers every reweight — the safe-over-clever
        choice the chaos drill can actually falsify.  Returns the new
        epoch."""
        if not self.weighted:
            raise ValueError(
                "reweight_edges on an UNWEIGHTED live graph — "
                "hop-count semantics have no weights to update; "
                "build the LiveGraph over a weighted base")
        src, dst, n = self._check_pair(src, dst, "reweight_edges")
        if n == 0:
            return self.epoch
        if weights is None:
            raise ValueError("reweight_edges needs the new weights")
        w = np.atleast_1d(np.asarray(weights, np.float32))
        if len(w) != n:
            raise ValueError(
                f"reweight_edges src/weights length mismatch "
                f"({n} vs {len(w)})")
        return self._publish("reweight", src, dst, w)

    def occupancy(self) -> float:
        return self.count / self.capacity

    def memory_terms(self) -> dict:
        """The live graph's host/device byte terms for the unified
        per-replica ledger (lux_tpu/memwatch.py, round 22) — the
        consumers rounds 20-21 built but never priced.  Every term is
        a deterministic integer so the ledger's NumPy oracle can
        re-derive it independently and match bitwise:

        - ``live_delta``: the five preallocated delta-block arrays
          (src/dst/w/kind/epoch, 20 B per capacity slot) — actual
          ``nbytes``, priced at construction not occupancy, because
          the allocation IS capacity-sized.
        - ``live_history``: the full publish history list, nominal
          HISTORY_ENTRY_BYTES per op (a 5-tuple + list slot; host
          pointer structures have no exact portable size, so the
          ledger prices the documented nominal — O(total mutations)
          growth is the thing to see, not malloc jitter).
        - ``live_multiset``: the lazily-built (src, dst) -> count
          Counter, nominal MULTISET_ENTRY_BYTES per distinct edge,
          ZERO until the first anti-monotone mutation builds it —
          the step change is visible in the trail.
        - ``live_wal``: the open append handle's written bytes
          (MutationLog.buffer_bytes), 0 without a WAL."""
        delta = (self.d_src.nbytes + self.d_dst.nbytes
                 + self.d_w.nbytes + self.d_kind.nbytes
                 + self.d_epoch.nbytes)
        return {
            "live_delta": int(delta),
            "live_history": len(self._history) * HISTORY_ENTRY_BYTES,
            "live_multiset": (0 if self._edge_counts is None
                              else len(self._edge_counts)
                              * MULTISET_ENTRY_BYTES),
            "live_wal": (0 if self._wal is None
                         else self._wal.buffer_bytes()),
        }

    # -- pins (snapshot isolation vs compaction) -----------------------

    def pin(self) -> None:
        with self._lock:
            self.pins += 1

    def unpin(self) -> None:
        with self._lock:
            self.pins = max(0, self.pins - 1)

    def admit(self, family: str | None = None) -> int | None:
        """Count one ADMITTED query and return the epoch it pins —
        ONE lock acquisition, so the stamp and the ledger entry are
        atomic (a mutate+compact between a separate read and a
        separate increment could fold the stamped view away before
        the ledger protected it).  Resident pins alone cannot
        protect a queued query: its epoch was pinned at admission,
        and a compaction before it reaches a column folds the delta
        out from under the OLD-base engines it will be served on — a
        wrong answer the torn-epoch audit is structurally blind to
        (answer_epoch == admission epoch both point at the vanished
        view).  The serving tier admits at submit and releases at
        exactly-once retirement/shed."""
        with self._lock:
            self.admitted += 1
            if family is None:
                return None
            return self.view_epoch(family)

    def release(self) -> None:
        with self._lock:
            self.admitted = max(0, self.admitted - 1)

    # -- epoch views ---------------------------------------------------

    def anti_pending(self) -> int:
        """Count of published anti-monotone ops (deletions/reweights)
        not yet folded into the base — while nonzero, ``view_epoch``
        caps admission below the earliest one."""
        return len(self._anti)

    def view_epoch(self, family: str = "push") -> int:
        """The epoch a newly admitted query of this engine family
        pins.  Both families now advance with published epochs — push
        kinds absorb appends through the delta-relax step, pull kinds
        through the degree/delta correction (serve.py
        PullBatchRunner, round 21) — EXCEPT past a pending
        anti-monotone op: a deletion/reweight cannot be expressed by
        either mechanism, so admission is capped at (earliest pending
        anti epoch - 1) until a re-seed-bearing fold publishes it.
        Answers stay exact at their admitted epoch; anti-monotone
        mutations cost admission FRESHNESS, never correctness."""
        # snapshot FIRST: checking self._anti and then iterating it
        # races compact()'s under-lock clear — a fold landing between
        # the truthiness gate and the min() raised ValueError on the
        # emptied list (found by lockcheck snapshot-iteration,
        # regression: tests/test_lockcheck.py)
        anti = list(self._anti)
        if anti:
            return min(t[0] for t in anti) - 1
        return self.epoch

    def graph_at(self, epoch: int) -> Graph:
        """Host Graph as of ``epoch`` — the NumPy-oracle surface
        (origin + every published mutation with epoch <= e, applied
        by ``_apply_ops``; cached)."""
        if not 0 <= epoch <= self.epoch:
            raise ValueError(f"epoch {epoch} outside [0, "
                             f"{self.epoch}]")
        if epoch not in self._graph_cache:
            # list() snapshot: _publish appends under the lock while
            # oracle threads replay history lock-free
            hist = [h for h in list(self._history) if h[4] <= epoch]
            self._graph_cache[epoch] = _apply_ops(
                self.origin, hist, self.weighted)
        return self._graph_cache[epoch]

    # -- delta relax (the device step; jit ARGUMENTS) ------------------

    @staticmethod
    def _evict_dead(cache: dict) -> None:
        """Drop entries whose weakref referent is gone.  The id()-
        keyed caches validate hits by weakref identity, but a dead
        geometry/engine's id may never be probed again (each
        refresh_live rebuilds engines at fresh addresses), so stale
        entries would accrete forever — O(nv) slot maps and compiled
        steps pinned per retired generation.  Run on every miss:
        the dicts hold a handful of live entries, so the sweep is
        O(live + newly dead)."""
        dead = [k for k, v in cache.items() if v[0]() is None]
        for k in dead:
            del cache[k]

    def _vertex_slots(self, sg) -> np.ndarray:
        """The O(nv) vertex -> padded-part-major-slot map for one
        shard geometry — depends only on the IMMUTABLE geometry
        (starts/vpad), never on the delta, so it is computed once per
        sg and survives every mutation batch and compaction —
        rebuilding it per batch would put O(nv) work (tens of MB of
        temporaries at RMAT25 scale) on the ingest hot path for a
        batch that touched a handful of slots."""
        key = id(sg)
        vs = self._vslot_cache.get(key)
        if vs is None or vs[0]() is not sg:
            self._evict_dead(self._vslot_cache)
            v = np.arange(sg.nv, dtype=np.int64)
            v_part = np.searchsorted(sg.starts, v, side="right") - 1
            v_slot = (v_part * sg.vpad
                      + (v - sg.starts[v_part])).astype(np.int32)
            vs = (weakref.ref(sg), v_slot)
            self._vslot_cache[key] = vs
        return vs[1]

    def delta_arrays(self, sg):
        """The fixed-capacity delta block TRANSLATED into ``sg``'s
        padded part-major slots, ready to pass as jit arguments:
        (src_slot i32 [cap], dst_slot i32 [cap], w f32 [cap],
        kind i32 [cap], epoch i32 [cap]).  Published slots are
        immutable; per miss only O(capacity) translation work runs
        (the O(nv) vertex map is geometry-cached in
        ``_vertex_slots``) and the returned arrays are fresh copies
        (never aliases of the mutable tail)."""
        # keyed by id() but VALIDATED by a weakref identity check:
        # a dict key alone holds no reference, and CPython reuses a
        # freed object's address — a stale hit would translate slots
        # for a different shard geometry
        key = id(sg)
        cached = self._slot_cache.get(key)
        n = self.count
        if cached is None or cached[0]() is not sg \
                or cached[1] is not self.d_src or cached[2] < n:
            self._evict_dead(self._slot_cache)
            v_slot = self._vertex_slots(sg)
            src_slot = np.zeros(self.capacity, np.int32)
            dst_slot = np.full(self.capacity,
                               sg.num_parts * sg.vpad, np.int32)
            src_slot[:n] = v_slot[self.d_src[:n]]
            dst_slot[:n] = v_slot[self.d_dst[:n]]
            cached = (weakref.ref(sg), self.d_src, n, src_slot,
                      dst_slot, self.d_w.copy(), self.d_kind.copy(),
                      self.d_epoch.copy())
            # lockcheck: allow(guarded-field) idempotent cache fill
            # (last-writer-wins over immutable published slots);
            # compact()'s under-lock clear targets a generation the
            # engines must refresh_live() past before serving anyway
            self._slot_cache[key] = cached
        return cached[3], cached[4], cached[5], cached[6], cached[7]

    def append_deltas(self):
        """Host view of the published APPEND slots — (src i64, dst
        i64, w f32, epoch i32) with tombstone/overwrite slots
        filtered out.  The pull runner counts a fresh column's
        effective out-degrees from it (serve.PullBatchRunner; the
        correction itself reads ``delta_arrays`` on the device):
        published slots are
        immutable and ``count`` is advanced after the slot's epoch
        lands, so a lock-free snapshot here is consistent by the same
        construction the device delta arrays rely on."""
        n = self.count
        m = self.d_kind[:n] == DK_APPEND
        return (self.d_src[:n][m].astype(np.int64),
                self.d_dst[:n][m].astype(np.int64),
                self.d_w[:n][m].copy(), self.d_epoch[:n][m].copy())

    def delta_step(self, eng):
        """The compiled delta-relax step for one push engine, CACHED
        per engine (keyed by id(), validated by weakref identity, dead
        entries evicted on miss) — every caller (revalidate, the serve
        runners' _apply_delta, register_audit) shares ONE compile per
        engine instead of re-inventing caching per site; a fresh
        jax.jit per call was the exact recompile-per-revalidate bug
        scripts/sweep_live.py found once already (PERF_NOTES round
        20)."""
        ent = self._step_cache.get(id(eng))
        if ent is None or ent[0]() is not eng:
            self._evict_dead(self._step_cache)
            step = self._build_delta_step(eng)
            self._step_cache[id(eng)] = (weakref.ref(eng), step)
        else:
            step = ent[1]
        return step

    def _build_delta_step(self, eng):
        """Delta-relax step for one push engine: (label
        [P, vpad(, B)], active, src_slot, dst_slot, w, kind, epoch,
        col_epoch) -> (label, active, improved count).  ONE
        state-table gather (the delta-source fetch), candidates
        epoch-masked PER QUERY COLUMN to the reduce identity, then a
        scatter-min/max into the flat table; improvements come from a
        whole-table compare (no second gather), so the audit's
        gather budget holds at the dense iterations' own bound
        (audit.matrix_configs ``*_live_delta``).  The delta arrays
        are jit ARGUMENTS — appends never recompile.  Tombstone and
        reweight slots (``kind != DK_APPEND``) are masked to the
        reduce identity: a monotone relax cannot express them, so
        they flow to answers only through the view_epoch admission
        cap + re-seed/fold (module docstring)."""
        import jax
        import jax.numpy as jnp

        prog = eng.program
        sg = eng.sg
        flat_n = sg.num_parts * sg.vpad
        reduce = prog.reduce
        if reduce not in ("min", "max"):
            raise ValueError(
                f"live delta relax requires a monotone min/max "
                f"program, got reduce={reduce!r} (pull kinds use the "
                f"degree correction instead — serve.py)")

        def step(label, active, src_slot, dst_slot, w, d_kind,
                 d_epoch, col_epoch):
            ident = jnp.asarray(prog.identity, label.dtype)
            flat = label.reshape((flat_n,) + label.shape[2:])
            # weights pass RAW [cap] — the program's relax owns the
            # query-axis broadcast, exactly as in the dense iteration
            # (batched relax does w[..., None] itself)
            src_l = jnp.take(flat, src_slot, axis=0)
            cand = prog.relax(src_l, w if self.weighted else None)
            cand = jnp.where(src_l == ident, ident,
                             cand.astype(label.dtype))
            # per-column epoch mask: a column pinned to epoch e must
            # never see an edge published after it — the snapshot-
            # isolation contract, enforced inside the step.  The kind
            # mask drops anti-monotone slots the same way.
            mask = d_epoch.reshape(d_epoch.shape
                                   + (1,) * (cand.ndim - 1)) \
                <= col_epoch
            mask = mask & (d_kind == DK_APPEND).reshape(
                d_kind.shape + (1,) * (cand.ndim - 1))
            cand = jnp.where(mask, cand, ident)
            at = flat.at[dst_slot]
            new_flat = at.min(cand, mode="drop") if reduce == "min" \
                else at.max(cand, mode="drop")
            improved = new_flat != flat
            new_label = new_flat.reshape(label.shape)
            new_active = active | improved.reshape(active.shape)
            return new_label, new_active, \
                jnp.sum(improved.astype(jnp.int32))

        return jax.jit(step)

    def register_audit(self, eng) -> None:
        """Expose the delta-relax step to the static program auditor
        as an engine variant (engine/auditable.py) so the repo-wide
        matrix machine-checks its single state-table gather with the
        engine's own ProgramSpec."""
        import jax

        jitted = self.delta_step(eng)
        cap = self.capacity

        def _thunk():
            lab_sds, act_sds = eng._audit_state_sds
            i32 = np.int32
            col = (jax.ShapeDtypeStruct((lab_sds.shape[2],), i32)
                   if len(lab_sds.shape) > 2
                   else jax.ShapeDtypeStruct((), i32))
            return (lab_sds, act_sds,
                    jax.ShapeDtypeStruct((cap,), i32),
                    jax.ShapeDtypeStruct((cap,), i32),
                    jax.ShapeDtypeStruct((cap,), np.float32),
                    jax.ShapeDtypeStruct((cap,), i32),
                    jax.ShapeDtypeStruct((cap,), i32), col)

        eng._register_variant("live_delta", jitted, _thunk)

    # -- incremental revalidation --------------------------------------

    def revalidate(self, eng, label, active, col_epoch=None):
        """Frontier-seeded incremental re-convergence of a converged
        state to this graph's published epoch (or per-column epochs):
        interleave the delta-relax step with the engine's compiled
        converge until the delta edges offer no further improvement —
        the fixed point of base + epoch-masked delta, reached by
        touching only the reachable-from-touched region (the
        incremental-vs-full sweep: scripts/sweep_live.py, PERF_NOTES
        round 20).  Returns (label, active, engine iterations).

        When a pending ANTI-MONOTONE op (deletion/reweight) falls at
        or before the target epoch, dispatches to the cone re-seed
        path instead (round 21): ``eng`` must then be built over
        ``graph_at(target)`` — the monotone delta relax cannot
        express the op against the old base — and ``col_epoch`` must
        be a scalar (per-column targets cannot cross an anti epoch;
        typed LiveGraphError).  NumPy oracles:
        apps/sssp.reference_sssp_decremental,
        apps/components.reference_components_decremental."""
        import jax
        import jax.numpy as jnp

        if col_epoch is None:
            col_epoch = self.epoch
        anti_min = min((t[0] for t in list(self._anti)), default=None)
        if np.ndim(col_epoch) == 0:
            if anti_min is not None and anti_min <= int(col_epoch):
                return self._revalidate_anti(eng, label, active,
                                             int(col_epoch))
        elif anti_min is not None \
                and anti_min <= int(np.max(col_epoch)):
            raise LiveGraphError(
                f"per-column revalidation cannot cross the pending "
                f"anti-monotone epoch {anti_min} — the re-seed needs "
                f"ONE target epoch; call revalidate with a scalar "
                f"col_epoch and an engine built over graph_at(epoch)")
        step = self.delta_step(eng)     # cached per engine
        args = self.delta_arrays(eng.sg)
        batched = getattr(eng.program, "batch", None)
        ce = (jnp.asarray(np.full(batched, col_epoch, np.int32))
              if batched is not None and np.ndim(col_epoch) == 0
              else jnp.asarray(np.asarray(col_epoch, np.int32)))
        total = 0
        while True:
            label, active, imp = step(label, active, *args, ce)
            if int(jax.device_get(imp)) == 0:
                break
            label, active, it = eng.converge(label, active)
            total += int(jax.device_get(it))
        return label, active, total

    def _revalidate_anti(self, eng, label, active, target: int):
        """The anti-monotone RE-SEED (round 21): compute the affected
        cone — forward reachability over ``graph_at(target)`` from
        every pending anti op's destination — re-seed those vertices
        to the program's init labels on the host, re-activate
        everything, and run the engine's compiled converge to the
        exact fixed point.  Correctness (mirrors the decremental
        oracles' argument): a vertex whose fixed point degrades is
        reachable in the new graph from some touched destination
        (the suffix of its stale witness path past the LAST mutated
        edge survives), so it is in the cone and restarts from init;
        every other vertex starts on the monotone side of its fixed
        point — the relax converges to full recompute's answer,
        bitwise for the integer apps (tests/test_livegraph.py).

        A cone larger than ``cone_cap * nv`` falls back to a full
        recompute from ``init_state`` (at that size the incremental
        path has no work left to skip — scripts/sweep_live.py round
        21 locates the crossover).  CONTRACT: ``eng`` is built over
        ``graph_at(target)``."""
        import jax
        import jax.numpy as jnp

        sg = eng.sg
        g_new = self.graph_at(target)
        if sg.nv != g_new.nv:
            raise LiveGraphError(
                f"re-seed engine geometry nv={sg.nv} does not match "
                f"graph_at({target}).nv={g_new.nv}")
        src, dst = g_new.edge_arrays()
        cone = np.zeros(g_new.nv, dtype=bool)
        touched = [d for (e, _op, _s, d) in list(self._anti)
                   if e <= target]
        cone[np.asarray(touched, np.int64)] = True
        while True:
            add = np.zeros(g_new.nv, dtype=bool)
            add[dst[cone[src]]] = True
            add &= ~cone
            if not add.any():
                break
            cone |= add
        cone_n = int(cone.sum())
        fallback = cone_n > self.cone_cap * g_new.nv
        batched = getattr(eng.program, "batch", None)
        if fallback:
            label, active = eng.init_state()
        else:
            init_lab, _ = eng.program.init(sg)
            lab_host = sg.from_padded(
                np.asarray(jax.device_get(label)))
            init_host = sg.from_padded(np.asarray(init_lab))
            cmask = cone if batched is None else cone[:, None]
            new_host = np.where(cmask, init_host, lab_host)
            # full-True active on the REAL vertices (to_padded zero-
            # fills the padding lanes, keeping them inactive): the
            # converge must also propagate append improvements into
            # the untouched region, not only repair the cone
            ones = np.ones((g_new.nv,) if batched is None
                           else (g_new.nv, batched), bool)
            label, active = eng.place(sg.to_padded(new_host),
                                      sg.to_padded(ones))
        if self.fault is not None:
            # RESEED_CRASH: die between the cone computation and the
            # converge — recovery must come up with the anti ops
            # still pending (admission stays capped; no answer was
            # produced from the half-re-seeded state)
            self.fault.fire_reseed()
        label, active, it = eng.converge(label, active)
        self.reseeds += 1
        if fallback:
            self.reseed_fallbacks += 1
        wal_kw = ({"wal": self._wal.path}
                  if self._wal is not None else {})
        _emit("reseed", epoch=int(target), cone=cone_n,
              cone_frac=round(cone_n / g_new.nv, 4),
              fallback=bool(fallback), anti=len(touched), **wal_kw)
        return label, active, int(jax.device_get(it))

    # -- compaction ----------------------------------------------------

    def record_drag_sample(self, seconds: float, count: int) -> None:
        """Feed one MEASURED delta-drag sample — a fenced timing of a
        delta-relax boundary over ``count`` published slots (the
        serve runners sample every Nth ``_apply_delta``).  The
        scheduler's economics prefer the measured median over the
        scalemodel term (``drag_source="measured"``): the modeled
        GATHER_SMALL_NS rate is a small-table calibration and the
        live table may sit past the 64-128 MB emitter step
        (PERF_NOTES)."""
        if count <= 0 or seconds <= 0:
            return
        self._drag_samples.append(seconds * 1e9 / count)

    def compact_economics(self) -> dict:
        """Price the standing delta drag against the one-time re-pack.
        Every dense boundary pays ~drag_ns per delta slot for the
        delta-source fetch — the scalemodel GATHER_SMALL_NS term
        until measured samples arrive (``record_drag_sample``), then
        the measured per-slot median (``drag_source``) — while the
        re-pack is a host CSC rebuild over base+delta.  The legacy
        trigger (``should_compact``) fires when occupancy crosses
        ``compact_threshold``; the round-21
        :class:`CompactionScheduler` folds in anti-monotone pressure,
        admission load, and SLO burn on top of these terms."""
        from lux_tpu import scalemodel

        occ = self.occupancy()
        modeled = self.count * scalemodel.GATHER_SMALL_NS
        if self._drag_samples:
            per_slot = float(np.median(np.fromiter(
                self._drag_samples, np.float64)))
            drag, source = per_slot * self.count, "measured"
        else:
            drag, source = modeled, "modeled"
        return {
            "occupancy": round(occ, 4),
            "threshold": self.compact_threshold,
            "should_compact": occ >= self.compact_threshold,
            "delta_count": int(self.count),
            "anti_pending": len(self._anti),
            "delta_drag_ns_per_boundary": round(drag, 1),
            "modeled_drag_ns_per_boundary": round(modeled, 1),
            "drag_source": source,
            "drag_samples": len(self._drag_samples),
            "repack_edges": int(self.base.ne + self.count),
        }

    def should_compact(self) -> bool:
        return self.compact_economics()["should_compact"]

    def compact(self, force: bool = False):
        """Fold the published delta into a NEW base generation and
        swap atomically (module docstring pillar 4).  Returns the new
        generation number, or None when there is nothing to fold (or
        occupancy is under threshold and ``force`` is False).  Raises
        CompactPinnedError while queries pin the current generation —
        the serving layer compacts between drains.

        Holds the mutation lock END TO END.  The fold is ~40 ms
        (PERF_NOTES round 20) and a concurrent append in a released
        window would be lost twice over: its published slot silently
        discarded by the fresh-delta swap (in neither the new base
        nor the delta — wrong answers the torn-epoch audit cannot
        see), and its epoch-e+1 WAL record landing BEFORE this
        compaction's epoch-e START marker — a log that fails its own
        epoch_order validation, turning acknowledged durable
        mutations unrecoverable.  Ingest simply blocks for the fold
        (the backpressure-friendly choice); pin() takes the same
        lock, so the pin check cannot race either."""
        with self._lock:
            if self.pins or self.admitted:
                raise CompactPinnedError(
                    f"{self.pins} resident / {self.admitted} "
                    f"admitted query(ies) pin generation "
                    f"{self.generation}; drain before compacting")
            n = self.count
            epoch = self.epoch
            if n == 0 or (not force and not self.should_compact()):
                return None
            new_gen = self.generation + 1
            if self._wal is not None:
                self._wal.append_marker(epoch, REC_COMPACT_START, n,
                                        new_gen)
            _emit("compact_start", epoch=int(epoch),
                  generation=new_gen, delta_count=int(n),
                  occupancy=round(n / self.capacity, 4))
            if self.fault is not None:
                # the injected COMPACT_CRASH leg: die between the
                # START marker and the swap — recovery must come up
                # on the SURVIVING generation (base + published
                # delta)
                self.fault.fire_compact()
            # fold from the ORIGIN through the full op history — the
            # same _apply_ops construction graph_at and recover use,
            # so live base, oracle surface, and recovered base are
            # bitwise-identical (for a pure-append history this is
            # exactly the old base.with_edges(delta) concatenation)
            new_base = _apply_ops(
                self.origin,
                [h for h in self._history if h[4] <= epoch],
                self.weighted)
            self.base = new_base
            self.base_epoch = epoch
            self.generation = new_gen
            self._fresh_delta()
            self.count = 0
            self.compactions += 1
            # every published anti op is <= epoch — the fold just
            # materialized them, so admission advances again
            self._anti = [t for t in self._anti if t[0] > epoch]
            self._slot_cache.clear()
            if self._wal is not None:
                self._wal.append_marker(epoch, REC_COMPACT_DONE,
                                        new_gen, epoch)
        _emit("compact_done", epoch=int(epoch), generation=new_gen,
              folded=int(n), ne=int(new_base.ne))
        return new_gen

    # -- recovery ------------------------------------------------------

    @classmethod
    def recover(cls, origin: Graph, wal_path: str, *,
                fault=None, compact_threshold: float = 0.75
                ) -> "LiveGraph":
        """Rebuild the live graph from the origin graph + the WAL:
        verify the chain (truncating a torn tail), replay every edge
        into the delta blocks, and re-fold every COMPLETED compaction
        (START..DONE pair) — deterministic CSC rebuilds, so the
        recovered generation is bitwise-identical to the pre-crash
        one.  A START without a DONE (COMPACT_CRASH) is ignored: the
        surviving generation is base + published delta, exactly what
        the log proves durable."""
        recs, torn, log = MutationLog.replay(wal_path, nv=origin.nv)
        lg = cls(origin, capacity=log.capacity, wal_path=wal_path,
                 fault=fault, compact_threshold=compact_threshold,
                 _recovering=True)
        lg._wal = log
        pending_start = None
        for rec in recs:
            if rec.kind in (REC_EDGE, REC_DELETE, REC_REWEIGHT):
                if lg.count >= lg.capacity:
                    raise MutationLogError(
                        wal_path, "capacity_overflow",
                        f"replay overflows the delta capacity "
                        f"{lg.capacity} with no compaction marker — "
                        f"log inconsistent with its own header")
                op = _OP_BY_REC[rec.kind]
                slot = lg.count
                lg.d_src[slot] = rec.a
                lg.d_dst[slot] = rec.b
                w = float(np.uint32(rec.c).view(np.float32))
                lg.d_w[slot] = w
                lg.d_kind[slot] = _DK_BY_OP[op]
                lg.d_epoch[slot] = rec.epoch
                lg.count = slot + 1
                lg._history.append((op, rec.a, rec.b, w, rec.epoch))
                lg.mutations += 1
                if op == "delete":
                    lg.deletions += 1
                    lg._anti.append((rec.epoch, op, rec.a, rec.b))
                elif op == "reweight":
                    lg.reweights += 1
                    lg._anti.append((rec.epoch, op, rec.a, rec.b))
                lg.peak_count = max(lg.peak_count, lg.count)
                lg.epoch = max(lg.epoch, rec.epoch)
            elif rec.kind == REC_COMPACT_START:
                pending_start = rec
            elif rec.kind == REC_COMPACT_DONE:
                if pending_start is None:
                    raise MutationLogError(
                        wal_path, "compact_pair",
                        f"COMPACT_DONE at epoch {rec.epoch} without "
                        f"a preceding COMPACT_START — the log's "
                        f"compaction bracket is broken")
                n = pending_start.a
                # refold from the ORIGIN through the replayed history
                # — the same _apply_ops construction compact ran, so
                # the recovered generation is bitwise-identical
                fold_epoch = rec.b
                lg.base = _apply_ops(
                    lg.origin,
                    [h for h in lg._history if h[4] <= fold_epoch],
                    lg.weighted)
                lg.base_epoch = fold_epoch
                lg.generation = rec.a
                # the surviving delta tail (appended after the fold's
                # snapshot) shifts down into a fresh block
                tail = lg.count - n
                ts, td = lg.d_src[n:lg.count].copy(), \
                    lg.d_dst[n:lg.count].copy()
                tw = lg.d_w[n:lg.count].copy()
                tk = lg.d_kind[n:lg.count].copy()
                te = lg.d_epoch[n:lg.count].copy()
                lg._fresh_delta()
                lg.d_src[:tail], lg.d_dst[:tail] = ts, td
                lg.d_w[:tail], lg.d_epoch[:tail] = tw, te
                lg.d_kind[:tail] = tk
                lg.count = tail
                lg.compactions += 1
                lg._anti = [t for t in lg._anti
                            if t[0] > fold_epoch]
                pending_start = None
        lg._slot_cache.clear()
        _emit("wal_replay", path=wal_path, records=len(recs),
              epoch=int(lg.epoch), generation=int(lg.generation),
              truncated_bytes=int(torn),
              delta_count=int(lg.count))
        return lg

    def close(self) -> None:
        if self._wal is not None:
            self._wal.close()


# ---------------------------------------------------------------------
# the compaction scheduler


class CompactionScheduler:
    """Economics-driven compaction scheduling under LIVE traffic
    (round 21) — replaces the serving tier's compact-between-drains
    occupancy heuristic.  ``decide()`` is a pure policy read over the
    live graph's :meth:`LiveGraph.compact_economics` (measured delta
    drag when the serve runners have fed samples), the admission
    ledger, and an optional SLO burn gauge (the fleet's
    error-budget burn, lux_tpu/fleet.py); ``maybe_compact()`` acts on
    it, respecting the pin/admission refusal rules (a
    CompactPinnedError race demotes the decision to a deferral, never
    an error).

    Decision order (first match wins):

    1. empty           -> none   (nothing published, nothing pending)
    2. admitted/pinned -> defer  (never fold a view out from under an
                                  admitted query — the wrong-answer
                                  class the torn-epoch audit is blind
                                  to)
    3. slo_burn        -> defer  (burn gauge over ``burn_max`` while
                                  occupancy still has headroom: the
                                  fold's ingest stall would feed the
                                  burn — back off unless the delta is
                                  nearly full, where DeltaFullError
                                  sheds loom larger)
    4. anti_monotone   -> compact (pending deletions/reweights cap
                                  admission freshness at every epoch
                                  they wait — fold at the first quiet
                                  window)
    5. occupancy       -> compact (past ``compact_threshold``,
                                  DeltaFullError backpressure
                                  threatens)
    6. drag            -> compact (standing per-boundary delta drag —
                                  measured median preferred — exceeds
                                  ``drag_budget_ns``)
    7. idle            -> none

    Every compact decision emits a ``compact_scheduled`` event
    carrying the economics that justified it
    (scripts/events_summary.py audits the trail: a scheduler
    compaction without its economics FAILS)."""

    def __init__(self, live: LiveGraph, *, burn=None,
                 burn_max: float = 0.5,
                 drag_budget_ns: float = 4096.0):
        self.live = live
        self.burn = burn              # callable -> current SLO burn
        self.burn_max = float(burn_max)
        self.drag_budget_ns = float(drag_budget_ns)
        self.scheduler_compactions = 0
        self.deferrals = 0

    def decide(self) -> dict:
        lv = self.live
        eco = lv.compact_economics()
        burn = float(self.burn()) if self.burn is not None else 0.0
        base = {
            "occupancy": eco["occupancy"],
            "threshold": eco["threshold"],
            "delta_count": eco["delta_count"],
            "anti_pending": eco["anti_pending"],
            "drag_ns": eco["delta_drag_ns_per_boundary"],
            "drag_source": eco["drag_source"],
            "admitted": int(lv.admitted),
            "pins": int(lv.pins),
            "burn": round(burn, 4),
        }
        if lv.count == 0 and not lv._anti:
            return {"action": "none", "reason": "empty", **base}
        if lv.pins or lv.admitted:
            self.deferrals += 1
            return {"action": "defer", "reason": "admitted", **base}
        if burn > self.burn_max and eco["occupancy"] < 0.9:
            self.deferrals += 1
            return {"action": "defer", "reason": "slo_burn", **base}
        if lv._anti:
            reason = "anti_monotone"
        elif eco["occupancy"] >= eco["threshold"]:
            reason = "occupancy"
        elif eco["delta_drag_ns_per_boundary"] >= self.drag_budget_ns:
            reason = "drag"
        else:
            return {"action": "none", "reason": "idle", **base}
        decision = {"action": "compact", "reason": reason, **base}
        _emit("compact_scheduled", **decision)
        return decision

    def maybe_compact(self, server=None) -> dict:
        """Run one scheduling step: decide, and on a compact decision
        fold + (when given the serving ``server``) refresh its
        engines onto the new generation.  A pin/admission race
        between decide and the fold demotes to a deferral."""
        decision = self.decide()
        if decision["action"] != "compact":
            return decision
        try:
            gen = self.live.compact(force=True)
        except CompactPinnedError:
            self.deferrals += 1
            return dict(decision, action="defer", reason="pin_race")
        if gen is not None:
            self.scheduler_compactions += 1
            if server is not None:
                server.refresh_live()
        return dict(decision, generation=gen)


# ---------------------------------------------------------------------
# oracle verification of live-serving answers


def check_live_answers(live: LiveGraph, responses,
                       weighted: bool = False) -> int:
    """Verify serving responses against the NumPy oracles evaluated
    at each response's ADMISSION epoch (``graph_at``) — bitwise for
    the integer apps, the chaos acceptance's correctness bar.
    Returns the mismatch count."""
    from lux_tpu.apps import components, pagerank, sssp

    bad = 0
    for r in responses:
        epoch = r.epoch or 0
        g_e = live.graph_at(epoch)
        if r.kind == "sssp":
            ref = sssp.reference_sssp_batched(
                g_e, [r.source], weighted=weighted)[:, 0]
            if not weighted:
                ref = np.where(ref >= int(sssp.HOP_INF),
                               int(sssp.HOP_INF), ref)
                ok = np.array_equal(r.answer.astype(np.int64), ref)
            else:
                ok = bool(np.allclose(r.answer, ref))
        elif r.kind == "components":
            ref = components.reference_components_batched(
                g_e, [r.source])[:, 0]
            ok = np.array_equal(r.answer.astype(np.int64), ref)
        else:
            reset = pagerank.one_hot_resets(g_e.nv, [r.source])
            ref = pagerank.reference_pagerank_batched(
                g_e, reset, max(1, r.iters))[:, 0]
            ok = bool(np.allclose(r.answer, ref, atol=5e-5))
        if not ok:
            bad += 1
            print(f"LIVE MISMATCH qid={r.qid} kind={r.kind} "
                  f"source={r.source} epoch={epoch}")
    return bad
