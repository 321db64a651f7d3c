"""Multi-query serving front-end: request batching + continuous refill.

ROADMAP item 2's front-end: production graph services answer BATCHES
of queries (k-source shortest paths, personalized PageRank with
per-user reset vectors, seeded reachability), and the engines now
carry a query-batch axis ``[vpad, B]`` so ONE state-table gather
serves every query per iteration (engine/program.py ``batch``;
delivered cost ~9/B ns/edge/query, PERF_NOTES "query batching").
This module is the continuous-batching layer on top — the LLM-serving
idiom applied to graph queries, on the segmented/telemetry substrate
PRs 1-8 built:

- a **request queue** (``Server.submit`` / ``BatchCollector``): each
  request is one query (a source vertex, or a reset distribution for
  personalized PageRank); the collector takes up to B queries, or
  whatever has arrived when the collection deadline expires.
- a **BatchRunner** per query kind holding ONE batched engine with a
  fixed column count B.  Queries occupy columns; free columns are
  IDLE (push: all-inactive, contributing the reduce identity through
  the ordinary pre-gather mask; pull: a converged fixed point whose
  updates are no-ops) — the retired-column identity rule
  (ARCHITECTURE.md "Query batching & serving").
- segments run on the EXISTING drivers: push kinds converge through
  ``segmented.each_converge_segment`` and pull kinds through
  ``segmented.each_run_segment`` (the generators behind
  ``converge_segments`` / ``run_segments``), with the
  continuous-batching refill implemented as the drivers' documented
  ``on_segment`` hook — so duration budgeting, telemetry segment
  events, iter-stats counters and the health watchdog all compose
  unchanged.
- the **scheduling rule** across kinds (``Server.serve``): one TURN —
  one segment and its boundary — for each kind that has work (a queued
  query or an occupied column), round-robin, until none has.  A
  runner is suspended between its turns with its state left on the
  device (``_RunnerBase.turn``; nothing is fetched or re-placed
  because another kind ran), so under sustained arrivals of several
  kinds none waits for another's queue to empty.  With one kind in
  the ring the turns are that runner's ``drain``: ``python -m
  lux_tpu.serve``, scripts/loadgen.py and the fleet's replicas all
  go through the same path.
- the **serving loop** (``Server.serve(deliver)``): responses are
  handed to ``deliver`` the moment they are made (no turn starts
  between a ``query_done`` and its hand-over); while no kind has
  work the loop blocks (span ``serve.idle``) until a ``submit`` from
  any thread or ``stop()``, and once stopped it ends when no kind
  has work.  ``run()`` is that loop with the stop already given,
  collected into a list.
- each segment boundary has TWO HALVES.  The first steers the next
  segment and is serial: the hook finds the converged columns (push:
  the column's frontier is empty; pull: the column's residual fell
  under ``tol``), takes each on the device (``_take_column``: an
  array of its own, not fetched), frees its slot and REFILLS the
  freed columns from the queue (pull refills also rewrite the column
  of the reset table on the device and hand the table back through
  ``PullEngine.update_program_arrays`` — no recompile).  The second
  only concerns answers that have already left the batch — the
  ``device_get`` of the taken columns, the unpadding, the cache
  insert, the per-query :class:`Response`, ``query_done``, the
  hand-over — and runs BEHIND THE NEXT DISPATCH, whichever runner's
  it is (``_AnswerWork``; the drivers' ``while_running`` seam), so
  the device computes while the host makes the answers; where no
  dispatch follows it is flushed at once (``_RunnerBase.turn``).  A
  suspended runner that comes back to the chip with free columns and
  queued queries starts them before its segment (``_refill``): a
  closed-loop caller that heard of its retirement behind another
  kind's dispatch has its next query in a column by its own kind's
  next segment.
- per-query telemetry: ``query_enqueue`` / ``query_start`` /
  ``query_done`` events (latency, wait, iterations, segments) plus a
  ``serve_refill`` event per boundary — rendered and validated by
  scripts/events_summary.py.
- streaming SLO metrics (round 17, lux_tpu/metrics.py): every Server
  owns a metrics Registry (``metrics=`` to share or ``metrics=False``
  to disable — the overhead-A/B switch) fed HOST-side at segment
  boundaries only (the hot-path-metrics lint contract): queue depth
  and collect wait-time on ``BatchCollector.collect``, batch
  occupancy / refill and segment counters per ``BatchRunner``
  boundary, per-kind latency histograms at retire, and — with
  ``Server(slo_ms={kind: target_ms})`` — per-kind SLO accounting:
  ``serve_slo_good_total`` / ``serve_slo_violation_total`` counters
  plus a rolling burn-rate gauge (violating fraction over the last
  ``SLO_WINDOW`` retirements; ARCHITECTURE.md "Serving metrics &
  SLOs" has the series catalogue).  The serving loop publishes a
  ``metrics_snapshot`` telemetry event at a hand-over, at most one
  per ``snapshot_every_s``; scripts/loadgen.py reads the snapshots
  back and scripts/events_summary.py cross-audits them against the
  raw ``query_done`` stream.

Costs and debts: a boundary moves one padded ``[P, vpad]`` column per
retired query to the host (behind the next segment) and a few ``[B]``
vectors back — the retire
(``_take_column``) and the refill (``_start_columns``) are device
programs of fixed shape, and so are the pull runner's per-column
residual (``_column_residuals``; 4 B a column come to the host) and
its live-graph correction, so the ``[nv, B]`` state never crosses
(push: PR 25; pull: PR 27).  Only a query that brings its own reset
vector uploads that one column.  The on-device batch sweep is owed
(PERF.md section 7, "batch-sweep-on-device").

Smoke: ``python -m lux_tpu.serve`` builds a small random graph,
enqueues 2B mixed queries (sssp + components + pagerank), drains them
through continuous-batching refill, and verifies every per-query
answer against the apps' batched NumPy oracles (exit 1 on any
mismatch).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import queue as _queuemod
import threading
import time
from typing import Callable

import numpy as np

from lux_tpu import telemetry

DEFAULT_SEG_ITERS = 4
KINDS = ("sssp", "components", "pagerank")

# rolling SLO burn-rate window: the violating fraction over the last
# SLO_WINDOW retirements per kind (a short multi-batch horizon — long
# enough to smooth one batch's retirements, short enough that a burn
# shows within a few boundaries)
SLO_WINDOW = 64

# live-graph delta-drag sampling cadence (round 21): every Nth
# _apply_delta boundary is fenced-timed into the compaction
# scheduler's economics — sparse enough that the fence's host
# round-trip never shows in serving latency, frequent enough that a
# drain leaves the scheduler a measured median
DRAG_SAMPLE_N = 8


@dataclasses.dataclass
class Request:
    """One query: ``source`` for sssp/components (and one-hot
    pagerank); ``reset`` [nv] overrides it for personalized
    pagerank.  ``tenant``/``priority``/``deadline_s`` are the
    serving-tier admission fields (lux_tpu/fleet.py): plain Servers
    ignore them; the fleet dispatcher quotes quotas per tenant,
    collects deadline-priority (PriorityCollector) and sheds against
    the deadline."""
    qid: int
    kind: str
    source: int | None = None
    reset: np.ndarray | None = None
    t_enqueue: float = 0.0
    tenant: str = "default"
    priority: int = 0
    deadline_s: float | None = None
    # live-graph serving (round 20, lux_tpu/livegraph.py): the epoch
    # this query was ADMITTED at — stamped by Server/FleetServer
    # submit from the live view, pinned for the query's whole life
    # (failover re-dispatch included), and audited at answer time
    # (scripts/events_summary.py torn-epoch rule).  None = static
    # graph.
    epoch: int | None = None
    # bypass the answer-cache LOOKUP for this request (retirement
    # still populates).  The fleet's warm queries set it: a warm
    # query served from a sibling replica's cached answer leaves
    # this replica's engines UNCOMPILED, defeating warm's whole
    # contract (lux_tpu/fleet.py FleetServer.warm).
    no_cache: bool = False


@dataclasses.dataclass
class Response:
    qid: int
    kind: str
    source: int | None
    answer: np.ndarray          # [nv] labels / distances / ranks
    iters: int                  # engine iterations while resident
    segments: int               # boundaries the query lived through
    latency_s: float            # enqueue -> retire
    wait_s: float               # enqueue -> column assignment
    converged: bool = True      # False: retired on the segment cap
    epoch: int | None = None    # admission epoch (live graphs)
    cached: bool = False        # served from the epoch-keyed cache


class BatchCollector:
    """Thread-safe request queue + the collect-up-to-B-or-deadline
    batching rule.  ``put`` is called by ``Server.submit`` (any
    thread); ``collect(n, deadline_s)`` returns up to ``n`` requests,
    waiting at most ``deadline_s`` for the FIRST one and then taking
    only what has already arrived (a deadline of 0 never blocks).

    With ``metrics``/``kind`` set (Server wires them), ``put`` and
    ``collect`` keep the ``serve_queue_depth`` gauge current and
    ``collect`` observes each request's queue wait (enqueue ->
    collection) into ``serve_wait_seconds`` — host-side, boundary-
    cadence calls only.  ``replica`` (the fleet, lux_tpu/fleet.py)
    labels the depth GAUGE per replica — N replicas sharing one
    (name, kind) gauge would be last-writer-wins; shared counters
    and histograms merge correctly and stay fleet-wide."""

    def __init__(self, metrics=None, kind: str | None = None,
                 replica: str | None = None):
        self._q: _queuemod.Queue = _queuemod.Queue()
        self.metrics = metrics
        self.kind = kind
        self.replica = replica

    def _labels(self) -> dict:
        if self.replica is None:
            return {"kind": self.kind}
        return {"kind": self.kind, "replica": self.replica}

    def pending_requests(self) -> list:
        """Snapshot of the queued requests WITHOUT consuming them
        (refresh_live's epoch-consistency guard)."""
        with self._q.mutex:
            return list(self._q.queue)

    def _depth(self) -> None:
        if self.metrics is not None:
            self.metrics.gauge("serve_queue_depth",
                               **self._labels()).set(self._q.qsize())

    def put(self, req: Request) -> None:
        self._q.put(req)
        self._depth()

    def __len__(self) -> int:
        return self._q.qsize()

    def collect(self, n: int, deadline_s: float = 0.0) -> list[Request]:
        out: list[Request] = []
        deadline = time.monotonic() + max(0.0, deadline_s)
        while len(out) < n:
            timeout = deadline - time.monotonic()
            try:
                if not out and timeout > 0:
                    out.append(self._q.get(timeout=timeout))
                else:
                    out.append(self._q.get_nowait())
            except _queuemod.Empty:
                break
        if self.metrics is not None:
            self._depth()
            now = time.monotonic()
            wait = self.metrics.histogram("serve_wait_seconds",
                                          kind=self.kind)
            for req in out:
                wait.observe(max(0.0, now - req.t_enqueue))
        return out


class PriorityCollector(BatchCollector):
    """Deadline-priority request queue (the fleet dispatcher's
    admission queue, lux_tpu/fleet.py) replacing the base collector's
    pure FIFO with a PINNED ordering rule:

    - requests collect highest ``priority`` first, FIFO within a
      priority — EXCEPT
    - a request already past HALF its ``deadline_s`` is AGED: aged
      requests outrank every un-aged one (among themselves: earliest
      absolute deadline first, then FIFO).

    Without the aging clause a saturated high-priority stream
    displaces low-priority requests indefinitely; with it a displaced
    request's extra wait is bounded by half its own deadline plus one
    collection round (tests/test_serve.py pins both halves with a
    deterministic injected clock).  ``collect``'s deadline semantics
    match the base class: wait at most ``deadline_s`` for the FIRST
    request, then take only what has already arrived."""

    def __init__(self, metrics=None, kind: str | None = None,
                 replica: str | None = None,
                 now: Callable[[], float] = time.monotonic):
        # deliberately NOT calling super().__init__: the base Queue
        # is replaced wholesale by the condition-guarded list
        # (collection is a SORT, not a pop), and allocating it would
        # leave a dead always-empty queue for any base path to
        # silently read
        self.metrics = metrics
        self.kind = kind
        self.replica = replica
        self.now = now
        self._items: list[Request] = []
        self._cv = threading.Condition()

    def _depth(self) -> None:
        if self.metrics is not None:
            self.metrics.gauge("serve_queue_depth",
                               **self._labels()).set(len(self))

    def put(self, req: Request) -> None:
        with self._cv:
            self._items.append(req)
            self._cv.notify()
        self._depth()

    def __len__(self) -> int:
        with self._cv:
            return len(self._items)

    def pending_requests(self) -> list:
        with self._cv:
            return list(self._items)

    def _key(self, idx: int, req: Request, now: float):
        aged = (req.deadline_s is not None
                and now - req.t_enqueue >= 0.5 * req.deadline_s)
        if aged:
            # aged bucket outranks everything; earliest absolute
            # deadline first so the most endangered request leads
            return (0, req.t_enqueue + req.deadline_s, idx)
        return (1, -int(req.priority), idx)

    def collect(self, n: int, deadline_s: float = 0.0) -> list[Request]:
        deadline = time.monotonic() + max(0.0, deadline_s)
        with self._cv:
            while not self._items:
                timeout = deadline - time.monotonic()
                if timeout <= 0:
                    break
                self._cv.wait(timeout)
            now = self.now()
            order = sorted(range(len(self._items)),
                           key=lambda i: self._key(i, self._items[i],
                                                   now))
            take = sorted(order[:max(0, n)])
            out = [self._items[i] for i in order[:max(0, n)]]
            for i in reversed(take):
                del self._items[i]
        if self.metrics is not None:
            self._depth()
            t = time.monotonic()
            wait = self.metrics.histogram("serve_wait_seconds",
                                          kind=self.kind)
            for req in out:
                wait.observe(max(0.0, t - req.t_enqueue))
        return out


# epoch-keyed answer cache (round 20, ROADMAP item 5a): a cached
# entry is served only while younger than its kind's TTL; with a
# per-kind SLO configured the TTL is SLO-derived (an answer this much
# older than the latency target the operator cares about is stale by
# that same standard), else unbounded — epoch keys already guarantee
# correctness, the TTL is a freshness policy on top.
CACHE_TTL_SLO_MULT = 50.0
CACHE_MAX_ENTRIES = 4096
# each entry copies a full nv-length answer vector, so an entry-count
# cap alone scales cache memory with GRAPH SIZE (4096 entries at
# rmat21 nv~2M f32 is ~34 GB) — the byte budget is the binding cap on
# big graphs, the entry count on small ones
CACHE_MAX_BYTES = 256 * 1024 * 1024


def _engine_family(kind: str) -> str:
    """The ONE kind-to-family rule (push kinds see base + published
    delta, pull kinds the base generation — livegraph module
    docstring): Server and FleetServer both pin through here, so a
    failover re-dispatch and the original admission can never
    disagree about the epoch."""
    return "pull" if kind == "pagerank" else "push"


def admission_epoch(live, kind: str) -> int | None:
    """READ the epoch a query of ``kind`` would pin (cache sweeps,
    re-stamps).  Admission itself must use ``admit_query`` — a
    separate read + admit would leave a window where a
    mutate+compact folds the just-stamped view away before the
    admission ledger protects it."""
    if live is None:
        return None
    return live.view_epoch(_engine_family(kind))


def _epoch_reproducible(live, req) -> bool:
    """Can the CURRENT generation still serve a queued query pinned
    at ``req.epoch``?  BOTH families replay any epoch in
    [base_epoch, epoch] (round 21): push kinds through the
    per-column delta mask, pull kinds through the base-generation +
    degree-correction step — the delta holds exactly the mutations
    past base_epoch, and admission never pins past a pending
    anti-monotone op (livegraph.view_epoch), so every mutation in
    the pinned window is an append both mechanisms express.
    Anything older was folded away and adoption would serve a torn
    view.  The ONE staleness rule refresh_live (Server and
    FleetServer) checks — comparing against the LATEST view epoch
    instead would wedge the server whenever ingest lands between
    compact() and refresh_live() while a reproducible query sits
    queued (compact refuses on the admission ledger, run() refuses
    on the stale base, refresh_live refuses on the false
    mismatch)."""
    if req.epoch is None:
        return False
    return req.epoch >= int(live.base_epoch)


def admit_query(live, kind: str) -> int | None:
    """ATOMIC admission: take the ledger entry and the epoch stamp
    under one LiveGraph lock acquisition (livegraph.LiveGraph.admit).
    Paired with exactly one ``live.release()`` at retirement/shed."""
    if live is None:
        return None
    return live.admit(_engine_family(kind))


@dataclasses.dataclass
class _CacheEntry:
    answer: np.ndarray
    iters: int
    epoch: int
    t: float


class AnswerCache:
    """Epoch-keyed (kind, source/reset-hash, epoch) -> answer cache
    for the serving front-end (round 20, ROADMAP item 5a).

    The EPOCH is part of the key, so a stale-epoch hit is impossible
    by construction — a query admitted after a mutation carries the
    new epoch and misses (tests pin this: a stale-epoch hit is a
    test failure).  ``sweep`` drops entries whose epoch is no longer
    any kind's live view epoch (invalidation on epoch advance keeps
    the map from accreting dead generations); ``ttl_s`` per kind
    bounds entry age (SLO-aware when built by Server from slo_ms);
    LRU-evicted past ``max_entries`` OR ``max_bytes`` — each entry
    copies a full nv-length answer, so the byte budget is the
    binding cap on big graphs.
    Thread-safe: submit threads look up while the drain thread
    inserts.  Hit/miss Counter metrics are incremented by the
    runners (serve_cache_hit_total / serve_cache_miss_total)."""

    def __init__(self, ttl_s: dict | None = None,
                 max_entries: int = CACHE_MAX_ENTRIES,
                 max_bytes: int = CACHE_MAX_BYTES):
        import collections
        self._d: dict = collections.OrderedDict()
        self._lock = threading.Lock()
        self.ttl_s = dict(ttl_s or {})
        self.max_entries = int(max_entries)
        self.max_bytes = int(max_bytes)
        self.bytes = 0              # sum of cached answer nbytes
        self.hits = 0
        self.misses = 0
        # round-22 observatory rule (scripts/lint_lux.py
        # budget-gauge): a consumer with a byte BUDGET must publish a
        # byte GAUGE — a cap nobody can watch approaching is how the
        # cache stayed unpriced through rounds 20-21
        self._gauge = None

    def set_metrics(self, registry, replica: str | None = None):
        """Mirror the exact internal byte ledger into a registry
        gauge (``serve_cache_bytes``); updated inside put/_pop under
        the cache lock, so the gauge can never lag the ledger."""
        labels = {} if replica is None else {"replica": replica}
        self._gauge = (None if registry is None
                       else registry.gauge("serve_cache_bytes",
                                           **labels))
        if self._gauge is not None:
            self._gauge.set(self.bytes)

    def _sync_gauge(self) -> None:
        if self._gauge is not None:
            self._gauge.set(self.bytes)

    def _pop(self, key) -> None:
        """Drop one entry, keeping the byte ledger exact (caller
        holds the lock)."""
        ent = self._d.pop(key)
        self.bytes -= ent.answer.nbytes
        self._sync_gauge()

    @classmethod
    def from_slo(cls, slo_ms: dict | None) -> "AnswerCache":
        """SLO-derived TTLs: an answer older than
        ``CACHE_TTL_SLO_MULT`` x the kind's latency target is stale
        by the operator's own standard.  The ONE construction rule
        behind ``cache=True`` — Server and FleetServer both build
        through here, so the TTL semantics can never desynchronize
        between the single-server and fleet tiers."""
        return cls(ttl_s={k: CACHE_TTL_SLO_MULT * v / 1e3
                          for k, v in (slo_ms or {}).items()})

    @staticmethod
    def query_key(req: Request):
        # memoized per Request: the reset digest hashes a full
        # nv-length vector, and get (lookup) + put (populate) would
        # otherwise both pay it inside the SLO-measured latency
        key = getattr(req, "_cache_key", None)
        if key is not None:
            return key
        if req.reset is not None:
            import hashlib
            buf = np.ascontiguousarray(req.reset,
                                       np.float32).tobytes()
            # 128-bit digest, NOT a 32-bit CRC: two distinct reset
            # vectors colliding would serve each other's answers —
            # a silently WRONG answer (converged, epoch-consistent,
            # invisible to every audit), and at ~77k distinct resets
            # a 32-bit key reaches even birthday odds
            key = ("reset",
                   hashlib.blake2b(buf, digest_size=16).digest(),
                   len(buf))
        else:
            key = ("source", req.source)
        req._cache_key = key
        return key

    def get(self, kind: str, req: Request,
            now: float) -> _CacheEntry | None:
        key = (kind, self.query_key(req), req.epoch or 0)
        ttl = self.ttl_s.get(kind)
        with self._lock:
            ent = self._d.get(key)
            if ent is not None and ttl is not None \
                    and now - ent.t > ttl:
                self._pop(key)       # expired: miss, and forget it
                ent = None
            if ent is None:
                self.misses += 1
                return None
            self._d.move_to_end(key)     # LRU: a hit renews recency
            self.hits += 1
            return ent

    def put(self, kind: str, req: Request, answer: np.ndarray,
            iters: int, epoch: int, now: float) -> None:
        key = (kind, self.query_key(req), epoch or 0)
        ent = _CacheEntry(np.asarray(answer).copy(), int(iters),
                          int(epoch or 0), now)
        with self._lock:
            old = self._d.get(key)
            if old is not None:
                self.bytes -= old.answer.nbytes
            self._d[key] = ent
            self._d.move_to_end(key)     # LRU: replace renews too
            self.bytes += ent.answer.nbytes
            self._sync_gauge()
            while len(self._d) > 1 \
                    and (len(self._d) > self.max_entries
                         or self.bytes > self.max_bytes):
                self._pop(next(iter(self._d)))

    def sweep(self, live_epochs: dict) -> int:
        """Drop entries whose (kind, epoch) is no longer a live view
        epoch — the invalidation-on-epoch-advance leg.  Returns the
        number dropped."""
        with self._lock:
            dead = [k for k in self._d
                    if k[0] in live_epochs
                    and k[2] != (live_epochs[k[0]] or 0)]
            for k in dead:
                self._pop(k)
        return len(dead)

    def hit_fraction(self) -> float | None:
        n = self.hits + self.misses
        return None if n == 0 else self.hits / n


@dataclasses.dataclass
class _Slot:
    req: Request
    t_start: float
    iter_start: int
    segments: int = 0


@dataclasses.dataclass(eq=False)
class _Leaving:
    """One query between the two halves of its last boundary: its
    column has been taken on the device (``_take_column``) and freed,
    its answer has not reached the host yet."""
    col: int
    slot: _Slot
    taken: object               # [P, vpad] device array
    total_iters: int            # the engine's count at the boundary
    converged: bool
    answer_epoch: int | None    # the column's own (``_answer_epoch``)
    twins: list = dataclasses.field(default_factory=list)

    @property
    def cache_epoch(self) -> int:
        """The epoch the retirement caches the answer under."""
        if self.answer_epoch is not None:
            return self.answer_epoch
        return self.slot.req.epoch or 0


class _AnswerWork:
    """The second halves of segment boundaries, waiting for a segment
    to run behind.  A boundary's first half steers the next segment
    (which columns are done, which queries start) and stays serial;
    what is left only concerns answers that have already left the
    batch — the ``device_get`` of the taken columns, the unpadding,
    the cache insert, the ``Response``, ``query_done``, the hand-over
    — and nothing in the next segment reads it.  A runner defers that
    half here as one job a boundary; ``run(hidden=True)`` is what the
    segment drivers' ``while_running`` seam calls right after the
    next dispatch, WHICHEVER runner's it is (a ``Server`` gives all
    its runners one queue; a runner on its own has its own), and
    ``run(hidden=False)`` is the flush where no dispatch follows
    (``_RunnerBase.turn``).  Jobs run oldest first; ``after`` (the
    Server's hand-over) is called once a job, outside any span."""

    def __init__(self):
        self._jobs: collections.deque = collections.deque()
        self.after: Callable | None = None

    def __len__(self) -> int:
        return len(self._jobs)

    def defer(self, job: Callable[[bool], None]) -> None:
        self._jobs.append(job)

    def run(self, hidden: bool) -> None:
        while self._jobs:
            self._jobs.popleft()(hidden)
            if self.after is not None:
                with telemetry.under(None):
                    self.after()


def _emit(event: str, **fields):
    from lux_tpu import telemetry
    telemetry.current().emit(event, **fields)


class _RunnerBase:
    """Shared slot bookkeeping for one batched engine of width B, and
    the unit the chip is shared in: ``turn`` runs ONE segment and its
    boundary and leaves the runner suspended with its state on the
    device; ``drain`` is turns until no work is left."""

    family = ""                 # "push" / "pull": the turn span's name

    def __init__(self, kind: str, B: int, seg_iters: int,
                 max_segments: int, metrics=None,
                 slo_ms: float | None = None, live=None, cache=None):
        self.kind = kind
        self.B = int(B)
        self.seg_iters = int(seg_iters)
        self.max_segments = int(max_segments)
        self.slots: list[_Slot | None] = [None] * self.B
        # per-column admission epochs (live graphs): the live steps
        # mask each column's delta edges to its OWN epoch, so columns
        # admitted at different epochs share one engine dispatch with
        # snapshot isolation intact
        self._col_epoch = np.zeros(self.B, np.int32)
        self.responses: list[Response] = []
        # the suspended segment driver (a generator of
        # lux_tpu/segmented.py) while any column is occupied; it holds
        # the device state between turns (``_state``: what it last
        # yielded; ``_iters``: the engine's count at its last
        # boundary).  None: idle, nothing resident
        self._segments = None
        self._state = None
        self._iters = 0
        # the boundaries' second halves (a Server replaces this by the
        # queue all its runners share) and the queries that are
        # between the two halves of theirs
        self.answers = _AnswerWork()
        self._leaving: list[_Leaving] = []
        self.metrics = metrics
        self.slo_ms = None if slo_ms is None else float(slo_ms)
        # live-graph serving (round 20, lux_tpu/livegraph.py): the
        # shared LiveGraph (resident queries PIN its generation so a
        # compaction cannot swap the base under them) and the
        # epoch-keyed answer cache (ROADMAP item 5a)
        self.live = live
        self.cache = cache
        # serving-tier hooks (lux_tpu/fleet.py): ``replica`` labels
        # the per-query events with the runner's replica name, and
        # ``on_boundary(runner)`` fires at the TOP of every segment
        # boundary — the fleet's heartbeat-beat + chaos-kill-plan
        # injection point (an exception raised there propagates out
        # of drain() as a mid-drain replica death)
        self.replica: str | None = None
        self.on_boundary: Callable | None = None
        # memory observatory (round 22, lux_tpu/memwatch.py): the
        # boundary sampler rides the SAME hook cadence — O(1) host
        # work per segment boundary, never inside the fused loop
        self.mem = None
        # rolling SLO window: True per retirement = violation
        import collections
        self._slo_window = collections.deque(maxlen=SLO_WINDOW)

    def _rep(self) -> dict:
        return {} if self.replica is None else {"replica": self.replica}

    @property
    def resident(self) -> bool:
        """Suspended between two turns with queries in its columns."""
        return self._segments is not None

    def turn(self, collector: BatchCollector, deadline_s: float = 0.0,
             switch: bool = False) -> list[Response]:
        """One turn at the chip: start from ``collector`` if nothing
        is resident (a suspended runner with free columns starts
        queued queries in them first: ``_refill``), then ONE segment
        and the FIRST half of its boundary (which columns are done,
        their ``_take_column`` dispatches, the slots freed, the
        refill from ``collector``, the placement).  The second half —
        the answers of the columns that left — is deferred to
        ``self.answers`` and runs right after the NEXT dispatch, this
        runner's or another's (``_AnswerWork``), so the device
        computes while the host fetches, unpads and delivers.  Where
        no dispatch of this runner follows — every column idle and
        the collector empty, no column taken at all, or the turn
        raised — the turn runs it before it ends: no answer waits
        for an idle runner.  Returns this runner's responses that
        became ready during the turn (a Server, whose runners share
        one queue, takes them from ``responses`` as they come).
        Afterwards the runner is suspended with its state on the
        device (``resident``), or idle.  The span
        ``serve.turn.<family>`` holds the turn (counts: ``kind``;
        ``switch`` 1 where the scheduler ran another runner's turn
        before this one), with the segment's ``serve.boundary`` as
        its child."""
        n0 = len(self.responses)
        with telemetry.span("serve.turn." + self.family,
                            kind=self.kind, switch=int(switch)):
            try:
                resume = None
                if self._segments is None:
                    self._segments = self._begin(collector, deadline_s)
                elif self._free_cols() and len(collector):
                    resume = self._refill(collector, deadline_s)
                if self._segments is not None:
                    try:
                        self._state = self._segments.send(resume)
                    except StopIteration:
                        self._segments = None
                    # every column idle after a boundary that found
                    # the collector empty: nothing is resident
                    if not self._occupied():
                        self._segments = None
            except BaseException:
                # a mid-drain death (fleet kill plans, a tripped
                # watchdog): the columns' state is gone with it; the
                # answers of columns that had left before it are not
                self._segments = self._state = None
                try:
                    self.answers.run(hidden=False)
                except Exception:   # noqa: BLE001 - the turn's own
                    pass            # failure is the one to report;
                    #                 ``unanswered()`` keeps the rest
                raise
            if self._segments is None:
                self._state = None
                self.answers.run(hidden=False)
        return self.responses[n0:]

    def drain(self, collector: BatchCollector,
              deadline_s: float = 0.0) -> list[Response]:
        """Serve until the collector is empty and every column is
        idle — turns until no work is left (the last one flushes its
        own answers); returns the responses retired during this
        drain."""
        n0 = len(self.responses)
        self.turn(collector, deadline_s)
        while self.resident:
            self.turn(collector, deadline_s)
        return self.responses[n0:]

    def _free_cols(self):
        return [c for c, s in enumerate(self.slots) if s is None]

    def _occupied(self):
        return [c for c, s in enumerate(self.slots) if s is not None]

    def _answer_epoch(self, col: int) -> int | None:
        """The epoch the answer in ``col`` was actually computed at:
        the column's own ``_col_epoch``, which the live mechanism
        (push: the delta mask; pull: the degree correction and the
        delta mass) reads on the device.  Audited against the
        admission epoch by scripts/events_summary.py; a divergence is
        a torn read, so this must come from the MECHANISM, never be
        copied from the request."""
        if self.live is None:
            return None
        return int(self._col_epoch[col])

    def _start(self, col: int, req: Request, total_iters: int):
        now = time.monotonic()
        self.slots[col] = _Slot(req=req, t_start=now,
                                iter_start=total_iters)
        if self.live is not None:
            self.live.pin()
        ep = {} if req.epoch is None else {"epoch": req.epoch}
        _emit("query_start", qid=req.qid, query_kind=self.kind,
              col=col,
              wait_s=round(now - req.t_enqueue, 6), **ep,
              **self._rep())

    def _retire(self, col: int, answer: np.ndarray, total_iters: int,
                converged: bool = True):
        """The second half of one retirement, once the ``answer``
        ([nv]) of the query that left column ``col`` is on the host:
        the Response, the cache insert, the SLO series,
        ``query_done`` — and the queries that asked the same thing
        while the answer was on its way (``_Leaving.twins``)."""
        left = next(x for x in self._leaving if x.col == col)
        self._leaving.remove(left)
        slot, answer_epoch = left.slot, left.answer_epoch
        now = time.monotonic()
        resp = Response(
            qid=slot.req.qid, kind=self.kind, source=slot.req.source,
            answer=answer, iters=total_iters - slot.iter_start,
            segments=slot.segments,
            latency_s=now - slot.req.t_enqueue,
            wait_s=slot.t_start - slot.req.t_enqueue,
            converged=converged, epoch=slot.req.epoch)
        self.responses.append(resp)
        if self.cache is not None and converged:
            self.cache.put(self.kind, slot.req, answer, resp.iters,
                           left.cache_epoch, now)
        slo = {}
        if self.slo_ms is not None:
            slo_ok = resp.latency_s * 1e3 <= self.slo_ms
            slo = {"slo_ms": self.slo_ms, "slo_ok": slo_ok}
            self._slo_window.append(not slo_ok)
        if self.metrics is not None:
            m = self.metrics
            m.histogram("serve_latency_seconds",
                        kind=self.kind).observe(resp.latency_s)
            m.counter("serve_retired_total", kind=self.kind).inc()
            if not converged:
                m.counter("serve_segment_cap_total",
                          kind=self.kind).inc()
            if self.slo_ms is not None:
                m.counter("serve_slo_good_total" if slo["slo_ok"]
                          else "serve_slo_violation_total",
                          kind=self.kind).inc()
                burn = (sum(self._slo_window)
                        / max(1, len(self._slo_window)))
                m.gauge("serve_slo_burn_rate",
                        kind=self.kind).set(burn)
        ep = {}
        if resp.epoch is not None:
            # answer_epoch comes from the serving MECHANISM (delta
            # mask / engine generation), epoch from admission — the
            # events_summary torn-epoch audit fails any divergence
            ep = {"epoch": resp.epoch,
                  "answer_epoch": (answer_epoch
                                   if answer_epoch is not None
                                   else resp.epoch)}
        _emit("query_done", qid=resp.qid, query_kind=self.kind,
              col=col,
              iters=resp.iters, segments=resp.segments,
              latency_s=round(resp.latency_s, 6),
              wait_s=round(resp.wait_s, 6), converged=converged,
              **ep, **slo, **self._rep())
        for req in left.twins:
            # the entry just put, unless it has gone already (a TTL
            # of milliseconds, another replica's inserts): the answer
            # is here either way
            t = time.monotonic()
            self._respond_cached(
                req, self._cached(req, t) or _CacheEntry(
                    answer, resp.iters, left.cache_epoch, t), t)
        return resp

    def unanswered(self) -> list[Request]:
        """The queries whose columns are freed and whose answers were
        never made (a second half that failed, or never ran): what a
        failover must re-dispatch besides the occupied columns
        (lux_tpu/fleet.py ``_mark_lost``).  Forgets them."""
        out = [q for left in self._leaving
               for q in (left.slot.req, *left.twins)]
        del self._leaving[:]
        return out

    def _cached(self, req: Request, now: float):
        """The cache's entry for ``req`` (or None), counted as the
        hit or miss it is."""
        ent = self.cache.get(self.kind, req, now)
        if self.metrics is not None:
            self.metrics.counter(
                "serve_cache_hit_total" if ent is not None
                else "serve_cache_miss_total", kind=self.kind).inc()
        return ent

    def _serve_cached(self, req: Request) -> bool:
        """Serve ``req`` straight from the epoch-keyed answer cache
        when possible — no column, no engine dispatch (ROADMAP item
        5a).  The entry's epoch equals the request's admission epoch
        BY KEY, so a hit can never be stale-epoch.  A query whose
        twin has just left its column (``_leaving``: the entry is
        one ``device_get`` away) takes no column either: it is
        answered with the twin, from the entry the twin puts."""
        if self.cache is None or req.no_cache:
            return False
        key = (AnswerCache.query_key(req), req.epoch or 0)
        for left in self._leaving:
            if left.converged and key == (
                    AnswerCache.query_key(left.slot.req),
                    left.cache_epoch):
                left.twins.append(req)
                return True
        now = time.monotonic()
        ent = self._cached(req, now)
        if ent is None:
            return False
        self._respond_cached(req, ent, now)
        return True

    def _respond_cached(self, req: Request, ent: _CacheEntry,
                        now: float) -> None:
        resp = Response(
            qid=req.qid, kind=self.kind, source=req.source,
            answer=ent.answer.copy(), iters=ent.iters, segments=0,
            latency_s=now - req.t_enqueue,
            wait_s=now - req.t_enqueue, converged=True,
            epoch=req.epoch, cached=True)
        self.responses.append(resp)
        slo = {}
        if self.slo_ms is not None:
            ok = resp.latency_s * 1e3 <= self.slo_ms
            slo = {"slo_ms": self.slo_ms, "slo_ok": ok}
            self._slo_window.append(not ok)
        if self.metrics is not None:
            m = self.metrics
            m.histogram("serve_latency_seconds",
                        kind=self.kind).observe(resp.latency_s)
            m.counter("serve_retired_total", kind=self.kind).inc()
            if self.slo_ms is not None:
                m.counter("serve_slo_good_total" if slo["slo_ok"]
                          else "serve_slo_violation_total",
                          kind=self.kind).inc()
        ep = {} if req.epoch is None else \
            {"epoch": req.epoch, "answer_epoch": ent.epoch}
        _emit("query_done", qid=resp.qid, query_kind=self.kind,
              col=-1, iters=resp.iters, segments=0,
              latency_s=round(resp.latency_s, 6),
              wait_s=round(resp.wait_s, 6), converged=True,
              cached=True, **ep, **slo, **self._rep())

    # -- columns on the device -------------------------------------------
    #
    # Both families keep their ``[P, vpad, B]`` state on the device
    # while any column is occupied; a retirement fetches its own
    # column (``_take_column``) and columns start through
    # ``_start_columns``, the first fill included.

    def _column_programs(self, mesh, *dtypes):
        """The device programs of fixed shape every boundary uses
        (``self.eng`` is built): ``_blank`` (zero arrays of
        ``dtypes``, what a drain starts from), ``_take`` and
        ``_reset``.  The state keeps the engine's parts sharding
        (``self._parts``; None without a mesh) through every program,
        so the engine never sees a second layout."""
        import jax
        import jax.numpy as jnp

        sg = self.eng.sg
        shape = (sg.num_parts, sg.vpad, self.B)
        self._parts = None
        if mesh is not None:
            from lux_tpu.parallel.mesh import parts_spec
            self._parts = parts_spec(mesh)

        def sharded(n):
            return None if mesh is None else (self._parts,) * n

        self._blank = jax.jit(
            lambda: tuple(jnp.zeros(shape, d) for d in dtypes),
            out_shardings=sharded(len(dtypes)))
        self._take = jax.jit(_take_column)
        self._reset = jax.jit(_start_columns, donate_argnums=(0, 1),
                              out_shardings=sharded(2))
        self._rows = np.diff(sg.starts).astype(np.int32)

    def _source_pos(self, req: Request) -> int:
        """Padded position (``part * vpad + offset``) of the query's
        source: ``_start_columns``' ``pos``."""
        sg = self.eng.sg
        s = int(req.source)
        if not 0 <= s < self.g.nv:
            raise ValueError(f"query {req.qid}: source {s} out of "
                             f"range [0, {self.g.nv})")
        part = int(np.searchsorted(sg.starts, s, side="right")) - 1
        return part * sg.vpad + s - int(sg.starts[part])

    def _turnover(self, cols=()):
        """The ``[B]`` host vectors that steer ``_start_columns``,
        with the columns ``cols`` marked to go idle; ``_fill`` marks
        the ones it starts."""
        mask = np.zeros(self.B, bool)
        mask[list(cols)] = True
        return (mask, np.full(self.B, -1, np.int32),
                np.full(self.B, self._inf, self._dtype))

    def _take_columns(self, bsp, label, cols, total_iters,
                      converged) -> None:
        """The first half of the retirements of one boundary
        (``serve.boundary.take``): one ``_take_column`` dispatch a
        column — a device array of its own, not fetched: the copy to
        the host runs beside the next segment (measured on the chip,
        PR 41: 3 ms for 8 columns; asking for it here, with
        ``copy_to_host_async``, cost the serial half 1.5 ms more) —
        and the slots freed, so that ``_fill`` can give the columns
        away.  The second half (``_answer_columns``) is deferred to
        ``self.answers``."""
        left = []
        with telemetry.span("serve.boundary.take"):
            for c, conv in zip(cols, converged):
                left.append(_Leaving(c, self.slots[c],
                                     self._take(label, np.int32(c)),
                                     total_iters, conv,
                                     self._answer_epoch(c)))
                self.slots[c] = None
                if self.live is not None:
                    self.live.unpin()
        self._leaving += left
        self.answers.defer(
            functools.partial(self._answer_columns, bsp, left))

    def _answer_columns(self, bsp, left, hidden: bool) -> None:
        """The second half: ``serve.boundary.fetch`` (one device_get
        of the taken columns; ``bytes``), ``.unpad`` and ``.retire``,
        children of the boundary ``bsp`` that took them though it has
        closed (``telemetry.under``).  ``hidden`` (1 behind a
        dispatch, 0 flushed with the device idle) goes onto ``bsp``."""
        import jax

        sg = self.eng.sg
        bsp.count(hidden=int(hidden))
        with telemetry.under(bsp):
            with telemetry.span("serve.boundary.fetch") as sp:
                padded = jax.device_get([x.taken for x in left])
                sp.count(bytes=sum(x.nbytes for x in padded))
            with telemetry.span("serve.boundary.unpad"):
                answers = [sg.from_padded(x) for x in padded]
            with telemetry.span("serve.boundary.retire"):
                for x, answer in zip(left, answers):
                    self._retire(x.col, answer, x.total_iters,
                                 x.converged)

    def _behind_dispatch(self) -> None:
        """The segment drivers' ``while_running``: the device has
        just been given a segment, the deferred answers run now."""
        self.answers.run(hidden=True)

    def _refill(self, collector, deadline_s: float):
        """A suspended runner comes back to the chip with free
        columns and queued queries (they came while other runners
        had their turns, or with the answers that left at its last
        boundary): they start now, not at the boundary after one
        more segment.  ``serve.boundary.fill`` and ``.place`` as a
        boundary's, children of the turn.  Returns the state to
        resume the driver with (None: the cache answered them all)."""
        turnover = self._turnover()
        with telemetry.span("serve.boundary.fill"):
            n_filled = self._fill(turnover, collector, self._iters,
                                  deadline_s)
        if not n_filled:
            return None
        _emit("serve_refill", query_kind=self.kind, retired=0,
              filled=n_filled, occupied=len(self._occupied()),
              queued=len(collector))
        if self.metrics is not None:
            self.metrics.counter("serve_refilled_total",
                                 kind=self.kind).inc(n_filled)
        return self._restart(turnover)

    def _fill(self, turnover, collector, total_iters,
              deadline_s) -> int:
        """Give free columns to queued requests — host bookkeeping
        only; what each started column must look like goes into
        ``turnover`` for the boundary's ``_start_columns``."""
        mask, pos, init = turnover
        free = self._free_cols()
        filled = 0
        first = True
        while free:
            reqs = collector.collect(len(free),
                                     deadline_s if first else 0.0)
            first = False
            if not reqs:
                break
            for req in reqs:
                if self._serve_cached(req):
                    continue     # answered without a column
                col = free.pop(0)
                mask[col] = True
                pos[col], init[col] = self._col_init(req)
                self._col_epoch[col] = req.epoch or 0
                self._start(col, req, total_iters)
                filled += 1
        return filled

    # -- the segment boundary's spans (lux_tpu/telemetry.py) -----------
    #
    # Every segment boundary is one ``serve.boundary`` span (counts:
    # retired, filled, occupied, queued, worked 0/1, family "push" /
    # "pull": what tells the two boundaries below apart; where it
    # worked also hidden 0/1), the child of its turn's
    # ``serve.turn.<family>``.  The span itself holds the boundary's
    # FIRST half, what the device waits for.  A push boundary that
    # neither retires nor refills has ``worked`` 0 and only the
    # ``.counts`` child (the device-to-host fetch of the [B] active
    # counts; after ``.delta`` on live graphs).  One that works has
    # ``.counts``, ``.take`` where a column retires (one
    # ``_take_column`` dispatch a retired query, the slots freed),
    # ``.fill`` (host bookkeeping only) and ``.place`` (the device
    # reset of the retired and refilled columns; ``bytes`` = the [B]
    # vectors that steer it; ends at dispatch).  A pull boundary has
    # ``.residual`` (the per-column residuals: a device program and
    # the fetch of its [B] result; after ``.delta``, the device
    # correction, on live graphs), ``.take`` where a column retires,
    # ``.fill`` always, and ``.place`` after a refill, as the push
    # one.  The SECOND half of a boundary that retired — ``.fetch``
    # (one padded label column per retired query: ``bytes``),
    # ``.unpad`` and ``.retire`` — are its children too, but run
    # after it has closed: behind the next segment's dispatch
    # (``hidden`` 1; in time they lie inside that turn's
    # ``segment.run``) or, where none follows, flushed at the end of
    # the turn (``hidden`` 0).  A boundary that worked without
    # retiring has no answers for the device to wait for: ``hidden``
    # 1.  A ``.fill`` / ``.place`` pair that is a child of the TURN
    # is the refill of a runner that came back to the chip with free
    # columns (``_refill``).

    def _enter_boundary(self) -> None:
        """The top of every segment boundary: the serving tier's hook
        (heartbeat, kill plans), the memory sample, and one more
        segment on every resident query's count."""
        if self.on_boundary is not None:
            self.on_boundary(self)
        if self.mem is not None:
            self.mem.sample(where=f"{self.kind}:boundary")
        for s in self.slots:
            if s is not None:
                s.segments += 1

    def _boundary_metrics(self, bsp, worked: bool, retired: int,
                          filled: int, queued: int) -> None:
        """Per-segment-boundary series (host-side by construction —
        the drivers' on_segment hooks are the only callers): the
        ``serve.boundary`` span's counts, then batch occupancy,
        segment count, retire/refill rates in the metrics registry."""
        bsp.count(worked=int(worked), retired=retired, filled=filled,
                  occupied=len(self._occupied()), queued=queued,
                  family=self.family)
        if worked and not retired:
            bsp.count(hidden=1)     # no answers to wait for
        if self.metrics is None:
            return
        m = self.metrics
        # counters are SHARED fleet-wide (they sum correctly across
        # replicas); the gauges are per-replica quantities and carry
        # the replica label when one is set — N replicas writing one
        # (name, kind) gauge would be last-writer-wins noise
        m.counter("serve_segments_total", kind=self.kind).inc()
        m.gauge("serve_batch_occupancy", kind=self.kind,
                **self._rep()).set(len(self._occupied()))
        m.gauge("serve_queue_depth", kind=self.kind,
                **self._rep()).set(queued)
        if filled:
            m.counter("serve_refilled_total",
                      kind=self.kind).inc(filled)


def _take_column(label, col):
    """Column ``col`` of the ``[P, vpad, B]`` labels as a contiguous
    ``[P, vpad]`` array: what one retirement brings to the host.
    ``col`` is traced, so every column is the same executable."""
    import jax

    return jax.lax.dynamic_index_in_dim(label, col, axis=2,
                                        keepdims=False)


def _start_columns(label, active, rows, cols, pos, init, inf):
    """The refill, on the device: every column in the ``[B]`` mask
    ``cols`` becomes a fresh query whose source sits at padded
    position ``pos[c]`` (``part * vpad + offset``) with label
    ``init[c]``, or an idle column (``pos[c]`` = -1: all ``inf``, no
    frontier).  Column for column this is ``sg.to_padded`` of "label
    ``inf`` everywhere but the source, frontier = the source alone":
    ``rows[p]`` is part p's count of real vertices, and the padding
    rows past it get 0 / False as ``to_padded`` gives them.
    Elementwise against an iota, no scatter: it shards over parts as
    it stands, and its shape does not depend on how many columns
    turn over.  The pull runner passes its float reset table in the
    frontier's place: a started column of it becomes the one-hot
    distribution of the source, an idle one all zero."""
    import jax.numpy as jnp

    P, vpad, _B = label.shape
    row = jnp.arange(vpad, dtype=jnp.int32)[None, :, None]
    part = jnp.arange(P, dtype=jnp.int32)[:, None, None]
    source = part * vpad + row == pos
    fresh = jnp.where(row < rows[:, None, None],
                      jnp.where(source, init, inf),
                      jnp.zeros((), label.dtype))
    return jnp.where(cols, fresh, label), jnp.where(cols, source, active)


class PushBatchRunner(_RunnerBase):
    """Continuous-batching runner for push kinds (sssp /
    components): one batched PushEngine, columns retire when their
    per-query frontier empties, refill rides the segment driver's
    ``on_segment`` hook.  The state stays on the device while any
    column is occupied, other runners' turns included: a retirement
    fetches its own label column (``_take_column``), columns start
    and go idle through ``_start_columns`` — the first fill
    included."""

    family = "push"

    def __init__(self, kind: str, g, B: int, *, num_parts: int = 1,
                 mesh=None, exchange: str = "auto",
                 health: bool = False, weighted: bool = False,
                 seg_iters: int = DEFAULT_SEG_ITERS,
                 max_segments: int = 10_000, metrics=None,
                 slo_ms: float | None = None, live=None, cache=None):
        super().__init__(kind, B, seg_iters, max_segments,
                         metrics=metrics, slo_ms=slo_ms, live=live,
                         cache=cache)
        self.g = g
        # delta-drag sampling cadence (round 21): every DRAG_SAMPLE_N
        # boundaries one _apply_delta is fenced-timed and fed to the
        # scheduler's economics (LiveGraph.record_drag_sample)
        self._delta_n = 0
        self.weighted = bool(weighted and kind == "sssp")
        placeholder = [0] * self.B
        if kind == "sssp":
            from lux_tpu.apps import sssp as app
            self.eng = app.build_engine(
                g, sources=placeholder, num_parts=num_parts,
                mesh=mesh, weighted=self.weighted,
                exchange=exchange, health=health)
            # hops are int32 under HOP_INF; weighted distances take
            # their type from the weights (apps/sssp.py)
            self._inf = self.eng.program.identity
            self._dtype = np.asarray(self._inf).dtype
        elif kind == "components":
            from lux_tpu.apps import components as app
            self.eng = app.build_engine(
                g, sources=placeholder, num_parts=num_parts,
                mesh=mesh, exchange=exchange, health=health)
            self._inf = np.int32(-1)
            self._dtype = np.int32
        else:
            raise ValueError(f"unknown push kind {kind!r}")
        self._column_programs(mesh, self._dtype, bool)

    def _col_init(self, req: Request):
        """(padded position of the source, its label) for a fresh
        query column — ``_start_columns``' ``pos`` and ``init``."""
        return (self._source_pos(req),
                int(req.source) if self.kind == "components" else 0)

    def _place_columns(self, label, active, turnover):
        """``serve.boundary.place``: one ``_start_columns`` dispatch
        on the (donated) device state; ``bytes`` is what goes to the
        device for it."""
        args = (self._rows, *turnover, self._inf)
        with telemetry.span("serve.boundary.place",
                            bytes=sum(a.nbytes for a in args)):
            return self._reset(label, active, *args)

    def _begin(self, collector: BatchCollector, deadline_s: float):
        """Fill the blank columns from ``collector`` and return the
        segment driver that serves them, suspended before its first
        segment (None where no query took a column)."""
        import jax
        import jax.numpy as jnp

        from lux_tpu.segmented import each_converge_segment

        turnover = self._turnover(range(self.B))
        if not self._fill(turnover, collector, 0, deadline_s):
            # cache hits may have retired queries without taking a
            # column — they are this turn's responses
            return None
        label, active = self._place_columns(*self._blank(), turnover)

        def hook(label, active, total, cnt):
            self._iters = total
            with telemetry.span("serve.boundary") as bsp:
                return boundary(bsp, label, active, total)

        def boundary(bsp, label, active, total):
            self._enter_boundary()
            if self.live is not None:
                # the live delta-relax step: delta blocks as jit
                # ARGUMENTS, each column masked to its OWN admission
                # epoch (snapshot isolation inside one dispatch).  A
                # column retires only when its frontier is empty AND
                # the delta offered no improvement — i.e. at the
                # fixed point of base + delta@its-epoch.
                with telemetry.span("serve.boundary.delta"):
                    label, active = self._apply_delta(label, active)
            with telemetry.span("serve.boundary.counts"):
                counts = np.asarray(jax.device_get(jnp.sum(
                    active, axis=tuple(range(active.ndim - 1)))))
            done = [c for c in self._occupied()
                    if counts[c] == 0
                    or self.slots[c].segments >= self.max_segments]
            want_fill = len(collector) > 0 and (
                done or self._free_cols())
            if not done and not want_fill:
                self._boundary_metrics(bsp, False, 0, 0,
                                       len(collector))
                # the delta step may have changed the device state —
                # hand the updated arrays back to the driver
                return (label, active) if self.live is not None \
                    else None
            # only the labels of the retiring columns will come to
            # the host, behind the next dispatch: a column leaves
            # with an empty frontier or is cut at max_segments, and
            # its mask is discarded either way
            if done:
                self._take_columns(bsp, label, done, total,
                                   [bool(counts[c] == 0) for c in done])
            turnover = self._turnover(done)
            with telemetry.span("serve.boundary.fill"):
                n_filled = self._fill(turnover, collector, total,
                                      deadline_s)
            _emit("serve_refill", query_kind=self.kind,
                  retired=len(done),
                  filled=n_filled, occupied=len(self._occupied()),
                  queued=len(collector))
            self._boundary_metrics(bsp, True, len(done), n_filled,
                                   len(collector))
            return self._place_columns(label, active, turnover)

        return each_converge_segment(
            self.eng, label, active, self.seg_iters, on_segment=hook,
            while_running=self._behind_dispatch)

    def _restart(self, turnover):
        """``_refill``'s placement on the suspended state."""
        label, active, _total = self._state
        return self._place_columns(label, active, turnover)

    def _apply_delta(self, label, active):
        """One live delta-relax application (livegraph.delta_step —
        cached per engine inside LiveGraph, shared with revalidate
        and register_audit) on the DEVICE state at a segment
        boundary.  Every ``DRAG_SAMPLE_N``-th application is
        fenced-timed (timing.fence — O(1) bytes, never a full-state
        fetch inside the timed region) and fed to the compaction
        scheduler's economics as a MEASURED per-boundary drag sample
        (LiveGraph.record_drag_sample)."""
        import jax.numpy as jnp

        args = self.live.delta_arrays(self.eng.sg)
        n_slots = int(self.live.count)
        self._delta_n += 1
        sample = (n_slots > 0
                  and self._delta_n % DRAG_SAMPLE_N == 1)
        if sample:
            t0 = time.perf_counter()
        label, active, _imp = self.live.delta_step(self.eng)(
            label, active, *args, jnp.asarray(self._col_epoch))
        if sample:
            from lux_tpu import timing
            timing.fence(label)
            self.live.record_drag_sample(
                time.perf_counter() - t0, n_slots)
        return label, active


def _column_residuals(new, prev, rows):
    """``[B]`` per-column residuals ``max |new - prev|`` over the real
    vertices of every part: ``rows[p]`` is part p's count of them, and
    the padding rows past it are left out, as ``sg.from_padded``
    leaves them out."""
    import jax.numpy as jnp

    vpad = new.shape[1]
    real = (jnp.arange(vpad, dtype=jnp.int32)[None, :, None]
            < rows[:, None, None])
    return jnp.max(jnp.where(real, jnp.abs(new - prev), 0),
                   axis=(0, 1))


def _put_column(table, column, col):
    """``table`` ``[P, vpad, B]`` with its column ``col`` replaced by
    ``column`` ``[P, vpad]``.  ``col`` is traced, so every column is
    the same executable."""
    import jax

    return jax.lax.dynamic_update_index_in_dim(table, column, col,
                                               axis=2)


def _delta_seen(kind, epoch, col_epoch):
    """``[cap, B]``: the delta slots each column's admission epoch
    sees — published appends up to it (an unused slot carries the
    epoch sentinel and is seen by none)."""
    from lux_tpu.livegraph import DK_APPEND

    return (kind == DK_APPEND)[:, None] & (epoch[:, None] <= col_epoch)


def _delta_degrees(deg_corr, cols, col_epoch, src_slot, _dst_slot, _w,
                   kind, epoch):
    """Live refill (delta block as ``LiveGraph.delta_arrays`` gives
    it): the degree correction of every column in the ``[B]`` mask
    ``cols`` becomes the number of delta appends leaving each vertex
    that the column's admission epoch sees — fixed for the column's
    residence (later appends carry later epochs, anti ops cap
    admission below themselves, so nothing admitted can change it)."""
    import jax.numpy as jnp

    seen = _delta_seen(kind, epoch, col_epoch).astype(deg_corr.dtype)
    count = jnp.zeros((deg_corr.shape[0] * deg_corr.shape[1],
                       deg_corr.shape[2]), deg_corr.dtype)
    count = count.at[src_slot].add(seen).reshape(deg_corr.shape)
    return jnp.where(cols, count, deg_corr)


def _delta_mass(new, prev, deg, deg_corr, col_epoch, src_slot, dst_slot,
                _w, kind, epoch, *, alpha):
    """The live half of a pull iteration: the engine produced ``new``
    = ``apply(acc_base)`` of ``prev`` with effective-degree
    normalization; one exact PPR iteration over ``graph_at(col_epoch)``
    additionally accumulates ``alpha * prev[src]`` into each delta
    append's destination, with the SAME normalization (linearity of
    the divide).  Each column sees the delta up to its own admission
    epoch — the snapshot-isolation rule of the push delta step.  One
    gather of ``prev`` at the slots' sources, one scatter-add into
    their destinations (an unused slot's lies past the table and is
    dropped)."""
    import jax.numpy as jnp

    flat = prev.reshape((-1, prev.shape[2]))
    mass = jnp.where(_delta_seen(kind, epoch, col_epoch),
                     jnp.take(flat, src_slot, axis=0), 0)
    acc = jnp.zeros_like(flat).at[dst_slot].add(mass, mode="drop")
    deg_eff = deg.astype(new.dtype)[..., None] + deg_corr
    return new + alpha * acc.reshape(new.shape) / jnp.maximum(deg_eff,
                                                              1)


class PullBatchRunner(_RunnerBase):
    """Continuous-batching runner for personalized PageRank: one
    batched PullEngine; a column retires when its per-query residual
    (max-abs state change over the WHOLE segment, an upper bound on
    any single iteration's) falls under ``tol``.  The state, the
    snapshot of it the segment began from and the reset table stay on
    the device while any column is occupied, other runners' turns
    included: a boundary fetches the ``[B]`` residuals and one column
    per retired query, and starts columns where they lie
    (``_place_columns``); the tables go back to the engine as device
    arrays (``PullEngine.update_program_arrays``).  Columns that
    retire without a successor keep iterating.

    Live graphs: appends change out-degree normalization, which the
    engine's base iteration cannot see — so each column runs at its
    OWN admission epoch via the base-generation + correction split:
    the engine normalizes by the EFFECTIVE degree (base + the
    column's delta-append out-degree, the ``deg_corr`` extra array)
    and the boundary adds the delta edges' rank mass
    (``_delta_mass``).  The correction is per-ITERATION math, so live
    forces ``seg_iters`` to 1 (the boundary must run between
    consecutive iterations, not after a burst)."""

    family = "pull"

    def __init__(self, kind: str, g, B: int, *, num_parts: int = 1,
                 mesh=None, exchange: str = "auto",
                 health: bool = False,
                 seg_iters: int = DEFAULT_SEG_ITERS,
                 tol: float = 1e-8, max_segments: int = 500,
                 metrics=None, slo_ms: float | None = None,
                 live=None, cache=None):
        super().__init__(kind, B, seg_iters, max_segments,
                         metrics=metrics, slo_ms=slo_ms, live=live,
                         cache=cache)
        if kind != "pagerank":
            raise ValueError(f"unknown pull kind {kind!r}")
        import jax
        import jax.numpy as jnp

        from lux_tpu.apps import pagerank as app
        self.g = g
        self.tol = float(tol)
        if live is not None:
            self.seg_iters = 1
        # the tables start uniform (a view: no [nv, B] host array);
        # the first fill zeroes every column it does not start
        self.eng = app.build_engine(
            g, num_parts=num_parts, mesh=mesh,
            resets=np.broadcast_to(np.float32(1.0 / g.nv), (g.nv, B)),
            exchange=exchange, health=health)
        # an idle column is all zero under a zero reset: a fixed point
        self._inf, self._dtype = np.float32(0), np.float32
        self._column_programs(mesh, self._dtype)
        self._deg = np.asarray(g.out_degrees, np.float32)
        # the state the segment in flight began from (a device copy)
        self._prev = None
        self._residual = jax.jit(_column_residuals)
        self._snapshot = jax.jit(jnp.copy, out_shardings=self._parts)
        self._put = jax.jit(_put_column, donate_argnums=0,
                            out_shardings=self._parts)
        if live is not None:
            import functools
            self._degrees = jax.jit(_delta_degrees, donate_argnums=0,
                                    out_shardings=self._parts)
            self._mass = jax.jit(
                functools.partial(_delta_mass, alpha=app.ALPHA),
                donate_argnums=0, out_shardings=self._parts)

    def _col_share(self, req: Request, reset, v):
        """What a fresh column holds at the vertices ``v`` (an index
        or a slice) whose reset share is ``reset``: the share over
        the EFFECTIVE out-degree the engine's apply uses (base + the
        appends the query's epoch sees) — mixing a base-degree start
        with corrected-degree iteration would put the column off its
        own trajectory."""
        deg = self._deg[v]
        if self.live is not None:
            ds, _dd, _dw, de = self.live.append_deltas()
            deg = deg + np.bincount(
                ds[de <= int(req.epoch or 0)],
                minlength=self.g.nv)[v].astype(np.float32)
        return np.where(deg > 0, reset / np.maximum(deg, 1),
                        reset).astype(np.float32)

    def _col_init(self, req: Request):
        """(padded position of the source, its share there) for a
        fresh one-hot column — ``_start_columns``' ``pos`` and
        ``init``; a query with its own ``reset`` vector starts as an
        idle column, which ``_place_columns`` then writes whole."""
        if req.reset is None:
            return (self._source_pos(req),
                    self._col_share(req, np.float32(1),
                                    int(req.source)))
        if np.shape(req.reset) != (self.g.nv,):
            raise ValueError(f"query {req.qid}: reset must be [nv], "
                             f"got {np.shape(req.reset)}")
        return -1, self._inf

    def _place_columns(self, state, turnover):
        """``serve.boundary.place``: the columns marked in
        ``turnover`` start where they lie — ``_start_columns`` on the
        (donated) state and reset table, the table in the frontier's
        place (its column becomes 1.0 at the source); a started query
        that brought its own ``reset`` vector then has that column
        and its share uploaded and written (``_put_column``); on live
        graphs ``_delta_degrees`` counts the columns' degree
        corrections.  The tables go back to the engine as the device
        arrays they are.  ``bytes`` is what goes to the device for it
        all; ends at dispatch."""
        eng, sg = self.eng, self.eng.sg
        sent = [self._rows, *turnover, self._inf]
        with telemetry.span("serve.boundary.place") as sp:
            state, reset = self._reset(state, eng.arrays["prog_reset"],
                                       *sent)
            for col in self._occupied():
                req = self.slots[col].req
                if not turnover[0][col] or req.reset is None:
                    continue
                own = np.asarray(req.reset, np.float32)
                share = self._col_share(req, own, slice(None))
                sent += [sg.to_padded(share), sg.to_padded(own)]
                state = self._put(state, sent[-2], np.int32(col))
                reset = self._put(reset, sent[-1], np.int32(col))
            tables = {"reset": reset}
            if self.live is not None:
                live = (self._col_epoch, *self.live.delta_arrays(sg))
                sent += live
                tables["deg_corr"] = self._degrees(
                    eng.arrays["prog_deg_corr"], turnover[0], *live)
            eng.update_program_arrays(**tables)
            sp.count(bytes=sum(a.nbytes for a in sent))
            return state

    def _begin(self, collector: BatchCollector, deadline_s: float):
        """As ``PushBatchRunner._begin``."""
        import jax

        from lux_tpu.segmented import each_run_segment
        from lux_tpu.timing import fence

        turnover = self._turnover(range(self.B))
        if not self._fill(turnover, collector, 0, deadline_s):
            return None                  # cache hits take no column
        state = self._place_columns(*self._blank(), turnover)
        # what the segment begins from: ``eng.run`` donates its input
        self._prev = self._snapshot(state)

        def hook(state, done_iters):
            # the pull driver dispatches a segment and waits for it
            # only where it times one: wait here, so that the
            # boundary's spans hold the boundary and not the segment
            fence(state)
            self._iters = done_iters
            with telemetry.span("serve.boundary") as bsp:
                return boundary(bsp, state, done_iters)

        def boundary(bsp, state, done_iters):
            prev = self._prev
            self._enter_boundary()
            if self.live is not None and self.live.count:
                # after it ``state`` is one exact PPR iteration of
                # ``prev`` over each column's graph_at(col_epoch)
                with telemetry.span("serve.boundary.delta"):
                    state = self._mass(
                        state, prev, self.eng.arrays["deg"],
                        self.eng.arrays["prog_deg_corr"],
                        self._col_epoch,
                        *self.live.delta_arrays(self.eng.sg))
            # per-query convergence: 4 B a column come to the host
            with telemetry.span("serve.boundary.residual"):
                res = np.asarray(jax.device_get(
                    self._residual(state, prev, self._rows)))
            done = [c for c in self._occupied()
                    if res[c] <= self.tol
                    or self.slots[c].segments >= self.max_segments]
            if done:
                self._take_columns(
                    bsp, state, done, done_iters,
                    [bool(res[c] <= self.tol) for c in done])
            turnover = self._turnover()
            with telemetry.span("serve.boundary.fill"):
                n_filled = self._fill(turnover, collector, done_iters,
                                      deadline_s)
            if done or n_filled:
                _emit("serve_refill", query_kind=self.kind,
                      retired=len(done), filled=n_filled,
                      occupied=len(self._occupied()),
                      queued=len(collector))
            self._boundary_metrics(bsp, bool(done or n_filled),
                                   len(done), n_filled, len(collector))
            if n_filled:
                state = self._place_columns(state, turnover)
            self._prev = self._snapshot(state)
            return state

        return each_run_segment(self.eng, state,
                                np.iinfo(np.int32).max,
                                self.seg_iters, on_segment=hook,
                                while_running=self._behind_dispatch)

    def _restart(self, turnover):
        """``_refill``'s placement on the suspended state, and the
        snapshot the next segment's residuals are taken against."""
        state = self._place_columns(self._state, turnover)
        self._prev = self._snapshot(state)
        return state


class Server:
    """Route queries by kind to per-kind BatchRunners and drain them.

    One engine per kind is built lazily at the first query of that
    kind (column count ``batch``); ``serve(deliver)`` is the serving
    loop (continuous-batching refill, responses handed over at the
    turn that retires them, blocking while nothing is queued or
    resident) and ``run()`` that loop with the stop given: it drains
    every kind's queue and returns the responses in retirement
    order.  ``submit`` may be called from any thread.  ``deadline_s``
    is the batch collector's wait-for-more budget (0 = serve whatever
    is queued — the offline/smoke mode).

    ``slo_ms`` maps query kinds to per-kind latency targets in
    milliseconds (SLO good/violation counters + the rolling burn-rate
    gauge); ``metrics`` is a lux_tpu.metrics.Registry to share, None
    for a fresh private one, or False to disable metrics entirely
    (the overhead-A/B switch, PERF_NOTES round 17)."""

    def __init__(self, g, batch: int = 4, *, num_parts: int = 1,
                 mesh=None, exchange: str = "auto",
                 health: bool = False, weighted: bool = False,
                 seg_iters: int = DEFAULT_SEG_ITERS,
                 tol: float = 1e-8, deadline_s: float = 0.0,
                 slo_ms: dict | None = None, metrics=None,
                 snapshot_every_s: float = 1.0, on_boundary=None,
                 replica: str | None = None, live=None,
                 cache: bool | AnswerCache = False, mem=None):
        self.g = g
        # live-graph serving (round 20, lux_tpu/livegraph.py):
        # ``live`` mutates under the queries — submit pins each
        # query's admission epoch from the live view, the push
        # runners apply the delta-relax step at boundaries, and
        # ``mutate``/``refresh_live`` are the ingest/compaction
        # surfaces.  ``g`` must be the live graph's CURRENT base
        # (engines and oracles key off it).
        self.live = live
        if live is not None and g is not live.base:
            raise ValueError(
                "Server(live=...) requires g to be live.base — the "
                "engines must serve the live graph's own base "
                "generation")
        if cache is True:
            self.cache: AnswerCache | None = \
                AnswerCache.from_slo(slo_ms)
        elif cache:
            self.cache = cache
        else:
            self.cache = None
        # fleet hooks (lux_tpu/fleet.py): the subprocess replica
        # worker runs a whole Server and needs its runners to beat
        # the replica board (and fire kill plans) at every boundary
        self.on_boundary = on_boundary
        self.replica = replica
        # round-22 memory observatory: a memwatch.MemoryTrail the
        # runners sample at every segment boundary (assignable after
        # construction too — runners are built lazily on first use)
        self.mem = mem
        self.batch = int(batch)
        self.opts = dict(num_parts=num_parts, mesh=mesh,
                         exchange=exchange, health=health)
        self.weighted = bool(weighted)
        self.seg_iters = int(seg_iters)
        self.tol = float(tol)
        self.deadline_s = float(deadline_s)
        self.slo_ms = dict(slo_ms or {})
        for k in self.slo_ms:
            if k not in KINDS:
                raise ValueError(f"slo_ms names unknown kind {k!r}; "
                                 f"choose from {KINDS}")
        if metrics is False:
            self.metrics = None
        elif metrics is None:
            from lux_tpu import metrics as metrics_mod
            self.metrics = metrics_mod.Registry()
        else:
            self.metrics = metrics
        if self.cache is not None:
            self.cache.set_metrics(self.metrics, replica)
        self.snapshot_every_s = float(snapshot_every_s)
        self._last_snapshot = 0.0
        self._collectors: dict[str, BatchCollector] = {}
        self._runners: dict[str, _RunnerBase] = {}
        self._last_turn: _RunnerBase | None = None   # serve()'s ring
        # the boundaries' second halves, of every runner: whichever
        # turn dispatches next runs them (``_AnswerWork``)
        self._answers = _AnswerWork()
        # what submitters (any thread) share with the serving loop:
        # the qids, the kinds' collectors and the stop.  The loop
        # waits on it while no kind has work
        self._wake = threading.Condition()
        self._next_qid = 0
        self._stopping = False

    def _collector(self, kind: str) -> BatchCollector:
        if kind not in KINDS:
            raise ValueError(f"unknown query kind {kind!r}; choose "
                             f"from {KINDS}")
        with self._wake:
            if kind not in self._collectors:
                self._collectors[kind] = BatchCollector(
                    metrics=self.metrics, kind=kind)
            return self._collectors[kind]

    def _runner(self, kind: str) -> _RunnerBase:
        if kind not in self._runners:
            mkw = dict(metrics=self.metrics,
                       slo_ms=self.slo_ms.get(kind),
                       live=self.live, cache=self.cache)
            if kind == "pagerank":
                self._runners[kind] = PullBatchRunner(
                    kind, self.g, self.batch,
                    seg_iters=self.seg_iters, tol=self.tol,
                    **mkw, **self.opts)
            else:
                self._runners[kind] = PushBatchRunner(
                    kind, self.g, self.batch,
                    weighted=self.weighted,
                    seg_iters=self.seg_iters, **mkw, **self.opts)
            self._runners[kind].on_boundary = self.on_boundary
            self._runners[kind].replica = self.replica
            self._runners[kind].mem = self.mem
            self._runners[kind].answers = self._answers
        return self._runners[kind]

    def set_metrics(self, registry) -> None:
        """Re-point every collector and runner at ``registry`` (or
        None to disable).  The load harness uses this to give each
        ramp step a FRESH registry without rebuilding the engines —
        series are fetched from the registry at use time, so the swap
        is complete at the next boundary."""
        self.metrics = registry
        for coll in list(self._collectors.values()):
            coll.metrics = registry
        for runner in self._runners.values():
            runner.metrics = registry
        if self.cache is not None:
            self.cache.set_metrics(registry, self.replica)

    def emit_metrics_snapshot(self, **extra):
        """Publish a ``metrics_snapshot`` telemetry event for this
        server's registry (None when metrics are disabled or no
        event sink is active)."""
        if self.metrics is None:
            return None
        return self.metrics.emit_snapshot(**extra)

    def _admission_epoch(self, kind: str) -> int | None:
        return admission_epoch(self.live, kind)

    def submit(self, kind: str, source: int | None = None,
               reset=None, tenant: str = "default",
               priority: int = 0,
               deadline_s: float | None = None) -> int:
        coll = self._collector(kind)
        # stamp + admission-ledger entry in ONE lock acquisition:
        # the generation must survive until this query retires, and
        # resident pins alone cannot protect it while QUEUED;
        # released per response at its hand-over (serve())
        epoch = admit_query(self.live, kind)
        if self.metrics is not None:
            self.metrics.counter("serve_queries_total",
                                 kind=kind).inc()
        with self._wake:
            # qid and queue position under one lock: concurrent
            # submitters get dense qids and are served in qid order
            qid = self._next_qid
            self._next_qid += 1
            req = Request(qid=qid, kind=kind,
                          source=None if source is None
                          else int(source),
                          reset=(None if reset is None
                                 else np.asarray(reset, np.float32)),
                          t_enqueue=time.monotonic(),
                          tenant=str(tenant), priority=int(priority),
                          deadline_s=(None if deadline_s is None
                                      else float(deadline_s)),
                          epoch=epoch)
            coll.put(req)
            self._wake.notify_all()
        _emit("query_enqueue", qid=qid, query_kind=kind,
              source=req.source, queued=len(coll))
        return qid

    def mutate(self, src, dst, weights=None,
               op: str = "append") -> int:
        """Ingest path: publish one mutation batch into the live
        graph (WAL-journaled, one new epoch).  ``op`` routes the
        full round-21 algebra: "append" (default), "delete"
        (weights ignored), "reweight" (weights are the NEW values).
        Raises livegraph.DeltaFullError when ingest has outrun
        compaction — the backpressure signal the fleet's admission
        converts into a typed ``AdmissionError(reason="delta_full")``
        shed (lux_tpu/fleet.py)."""
        if self.live is None:
            raise ValueError("mutate() needs a live graph "
                             "(Server(live=LiveGraph(...)))")
        if op == "append":
            return self.live.append_edges(src, dst, weights)
        if op == "delete":
            return self.live.delete_edges(src, dst)
        if op == "reweight":
            return self.live.reweight_edges(src, dst, weights)
        raise ValueError(f"unknown mutation op {op!r}; choose from "
                         f"('append', 'delete', 'reweight')")

    def slo_burn(self) -> float:
        """Worst per-kind rolling SLO-burn fraction across this
        server's runners (0.0 before any SLO accounting) — the
        CompactionScheduler's backoff input
        (livegraph.CompactionScheduler(burn=server.slo_burn))."""
        worst = 0.0
        for r in self._runners.values():
            if r._slo_window:
                worst = max(worst, sum(r._slo_window)
                            / len(r._slo_window))
        return worst

    def refresh_live(self) -> None:
        """Adopt the live graph's NEW generation after a compaction:
        drop the runners so the next drain rebuilds engines over the
        compacted base.  Refuses while anything is resident, or
        while a QUEUED query pins an epoch the new base cannot
        REPRODUCE: push kinds replay any epoch >= base_epoch via the
        per-column delta mask (the post-compact delta holds exactly
        the mutations past base_epoch, so later ingest does NOT
        strand an already-queued query), pull kinds only the base
        generation itself — anything older was folded away and
        adoption would serve a torn view."""
        if self.live is None:
            return
        # list(): a submitter thread may add a new kind's collector
        # mid-iteration (same race serve() guards against)
        for kind, coll in list(self._collectors.items()):
            stale = [req for req in coll.pending_requests()
                     if not _epoch_reproducible(self.live, req)]
            if stale:
                raise RuntimeError(
                    f"refresh_live with {len(stale)} {kind!r} "
                    f"query(ies) queued at an epoch the new "
                    f"generation cannot reproduce — drain first")
        for kind, r in self._runners.items():
            if r._occupied():
                raise RuntimeError(
                    f"refresh_live with resident {kind!r} columns — "
                    f"drain first")
        self.g = self.live.base
        self._runners.clear()
        self._last_turn = None      # or it keeps an old engine alive

    def _wants_turn(self, kind: str, coll: BatchCollector) -> bool:
        """A queued query or an occupied column of this kind."""
        runner = self._runners.get(kind)
        return len(coll) > 0 or (runner is not None and runner.resident)

    def _has_work(self) -> bool:
        # list(): submit() may add a NEW kind's collector from a
        # submitter thread meanwhile
        return any(self._wants_turn(kind, coll) for kind, coll
                   in list(self._collectors.items()))

    def _begin_drain(self) -> None:
        """What holds from the first turn after an idle spell (or
        the loop's start) to the next: the generation is the live
        graph's, and the cache holds no epoch that no view exposes."""
        if self.live is not None and self.g is not self.live.base:
            # generation adoption is ENFORCED, not caller etiquette:
            # serving on a stale base after a compaction converges
            # old-base + empty delta — a wrong answer whose
            # answer_epoch still equals its admission epoch, so the
            # torn-epoch audit can never see it.  A wrong answer is
            # a crash, never a published number.
            raise RuntimeError(
                "live graph compacted to a new generation — call "
                "refresh_live() before serving")
        if self.cache is not None and self.live is not None:
            # invalidation on epoch advance: entries keyed to epochs
            # no view still exposes can never hit again — drop them
            self.cache.sweep({k: self._admission_epoch(k)
                              for k in KINDS})

    def _await_work(self) -> bool:
        """Block until a kind has work or the stop is given; False
        once stopped with no work left.  One ``serve.idle`` span a
        wait: nothing is queued or resident inside it."""
        with self._wake:
            if self._has_work():
                return True
            if self._stopping:
                return False
        with telemetry.span("serve.idle"), self._wake:
            self._wake.wait_for(
                lambda: self._stopping or self._has_work())
        return True

    def _hand_over_ready(self, deliver) -> None:
        """Hand over whatever the runners have ready: the answers one
        boundary's second half has just made (``_AnswerWork.after``),
        or what a turn answered from the cache."""
        responses = []
        for runner in self._runners.values():
            # the loop outlives any drain: what is handed over is
            # the caller's, not the runner's to keep
            responses += runner.responses
            del runner.responses[:]
        self._hand_over(deliver, responses)

    def _hand_over(self, deliver, responses: list) -> None:
        """Responses leave the server: span ``serve.deliver`` (count
        ``responses``) round their release and the caller's
        ``deliver``; then the snapshot cadence."""
        if not responses:
            return
        with telemetry.span("serve.deliver", responses=len(responses)):
            if self.live is not None:
                # one release per retired response: the admit()
                # taken at submit ends exactly when the answer
                # leaves the server
                for _ in responses:
                    self.live.release()
            deliver(responses)
        now = time.monotonic()
        if now - self._last_snapshot >= self.snapshot_every_s:
            self._last_snapshot = now
            self.emit_metrics_snapshot()

    def stop(self) -> None:
        """End ``serve()`` once no kind has work (any thread; before
        the loop starts too: it then drains what is queued and
        returns)."""
        with self._wake:
            self._stopping = True
            self._wake.notify_all()

    def serve(self, deliver: Callable[[list[Response]], None]) -> None:
        """The serving loop, on the calling thread (continuous
        batching: later queries refill columns freed by earlier
        retirements).  The kinds share the chip by turns — one
        segment and its boundary each, round-robin over the kinds
        that have work (a queued query or an occupied column) — so
        under sustained arrivals of several kinds none waits for
        another's queue to empty; every runner's state stays on the
        device between its turns.  A boundary's answers are made
        behind the NEXT turn's dispatch, whichever kind's it is
        (``_RunnerBase.turn``, ``_AnswerWork``), and handed to
        ``deliver`` (a list, in retirement order) the moment they
        are made — the device computes meanwhile; where no dispatch
        follows (the turn left nothing resident of its kind, or
        raised) they are made and handed over before the turn ends,
        so nothing waits while the loop blocks.  What a turn
        answered from the cache is handed over when it ends.  While
        no kind has work the loop blocks until a ``submit`` from any
        thread or ``stop()``; once stopped it returns when no kind
        has work.  Publishes a ``metrics_snapshot`` event at a
        hand-over, at most one per ``snapshot_every_s``
        (``emit_metrics_snapshot()`` snapshots on demand)."""
        try:
            self._answers.after = functools.partial(
                self._hand_over_ready, deliver)
            self._begin_drain()
            while True:
                served = False
                # list(): submit() may add a NEW kind's collector
                # from a submitter thread while the ring goes round
                for kind, coll in list(self._collectors.items()):
                    if not self._wants_turn(kind, coll):
                        continue
                    runner = self._runner(kind)
                    runner.turn(
                        coll, self.deadline_s,
                        switch=self._last_turn not in (None, runner))
                    self._last_turn = runner
                    served = True
                    self._hand_over_ready(deliver)
                if served:
                    continue
                # nothing to dispatch behind: a turn that leaves its
                # runner idle has flushed already, this is the loop's
                # own word that nothing waits while it blocks
                self._answers.run(hidden=False)
                if not self._await_work():
                    return
                self._begin_drain()
        finally:
            self._answers.after = None
            with self._wake:
                self._stopping = False

    def run(self) -> list[Response]:
        """Drain every kind's queue: ``serve()`` with the stop
        already given; returns the responses in retirement order."""
        out: list[Response] = []
        self.stop()
        self.serve(out.extend)
        return out


# ---------------------------------------------------------------------
# smoke: python -m lux_tpu.serve

def _smoke_graph(scale: int, ef: int, seed: int = 0):
    from lux_tpu.graph import Graph
    r = np.random.default_rng(seed)
    nv = 1 << scale
    ne = nv * ef
    return Graph.from_edges(r.integers(0, nv, ne),
                            r.integers(0, nv, ne), nv)


def _check_answers(g, responses) -> int:
    """Verify every response against the apps' batched NumPy oracles;
    returns the mismatch count."""
    from lux_tpu.apps import components, pagerank, sssp
    bad = 0
    for r in responses:
        if r.kind == "sssp":
            ref = sssp.reference_sssp_batched(g, [r.source])[:, 0]
            ref = np.where(ref >= int(sssp.HOP_INF),
                           int(sssp.HOP_INF), ref)
            ok = np.array_equal(r.answer.astype(np.int64), ref)
        elif r.kind == "components":
            ref = components.reference_components_batched(
                g, [r.source])[:, 0]
            ok = np.array_equal(r.answer.astype(np.int64), ref)
        else:
            reset = pagerank.one_hot_resets(g.nv, [r.source])
            ref = pagerank.reference_pagerank_batched(
                g, reset, max(1, r.iters))[:, 0]
            ok = bool(np.allclose(r.answer, ref, atol=5e-5))
        if not ok:
            bad += 1
            print(f"MISMATCH qid={r.qid} kind={r.kind} "
                  f"source={r.source}")
    return bad


def main(argv=None) -> int:
    import argparse

    from lux_tpu import runtime
    runtime.use_compile_cache()
    ap = argparse.ArgumentParser(
        prog="python -m lux_tpu.serve",
        description="continuous-batching serve smoke: 2B mixed "
                    "queries drain through refill; answers are "
                    "oracle-checked")
    ap.add_argument("-scale", type=int, default=9,
                    help="graph scale (nv = 2**scale; default 9)")
    ap.add_argument("-ef", type=int, default=8)
    ap.add_argument("-batch", type=int, default=4,
                    help="engine column count B (default 4)")
    ap.add_argument("-queries", type=int, default=0,
                    help="total mixed queries (default 2B)")
    ap.add_argument("-kinds", default="sssp,components,pagerank",
                    help="comma list of query kinds to mix")
    ap.add_argument("-np", type=int, default=2, dest="num_parts")
    ap.add_argument("-seg-iters", type=int, default=2,
                    dest="seg_iters",
                    help="iterations per serve segment (the refill "
                         "cadence)")
    ap.add_argument("-seed", type=int, default=0)
    ap.add_argument("-events", default=None, metavar="FILE",
                    help="append the per-query telemetry trail as "
                         "JSONL (render: scripts/events_summary.py)")
    ap.add_argument("-no-check", action="store_true", dest="no_check",
                    help="skip the oracle verification")
    args = ap.parse_args(argv)

    from lux_tpu import telemetry

    kinds = [k.strip() for k in args.kinds.split(",") if k.strip()]
    for k in kinds:
        if k not in KINDS:
            print(f"error: unknown kind {k!r}")
            return 2
    g = _smoke_graph(args.scale, args.ef, args.seed)
    n_queries = args.queries or 2 * args.batch
    rng = np.random.default_rng(args.seed + 1)

    ev = telemetry.EventLog(args.events) if args.events else \
        telemetry.EventLog()
    with telemetry.use(events=ev):
        ev.emit("run_start", schema=telemetry.SCHEMA, app="serve",
                file=f"<rmat{args.scale}>", mesh=1,
                np=args.num_parts)
        srv = Server(g, batch=args.batch, num_parts=args.num_parts,
                     seg_iters=args.seg_iters)
        # mixed-kind queue of 2B queries, biased so the primary kind
        # OVERSUBSCRIBES its B columns — later queries must wait for
        # retirements and enter through continuous-batching refill
        others = kinds[1:]
        seq = [others[i - 1] if 0 < i <= len(others) else kinds[0]
               for i in range(n_queries)]
        for k in seq:
            srv.submit(k, source=int(rng.integers(0, g.nv)))
        t0 = time.perf_counter()
        responses = srv.run()
        elapsed = time.perf_counter() - t0
        ev.emit("run_done", seconds=round(elapsed, 6),
                iters=sum(r.iters for r in responses))
    refills = sum(1 for e in ev.events
                  if e["kind"] == "serve_refill"
                  and e.get("retired", 0) and e.get("filled", 0))
    ev.close()

    lat = sorted(r.latency_s for r in responses)
    p50 = lat[len(lat) // 2] if lat else 0.0
    for r in responses:
        print(f"query {r.qid} [{r.kind}] source={r.source}: "
              f"{r.iters} iters over {r.segments} segment(s), "
              f"latency {r.latency_s * 1e3:.1f} ms"
              + ("" if r.converged else " (SEGMENT CAP)"))
    print(f"# served {len(responses)}/{n_queries} queries "
          f"(B={args.batch}, {len(kinds)} kind(s)) in {elapsed:.2f}s; "
          f"p50 latency {p50 * 1e3:.1f} ms, max "
          f"{(lat[-1] if lat else 0) * 1e3:.1f} ms; "
          f"{refills} retire+refill boundary(ies)")
    if len(responses) != n_queries:
        print("error: queue did not drain")
        return 1
    if n_queries > args.batch and not refills:
        print("error: oversubscribed queue drained without any "
              "continuous-batching refill")
        return 1
    if not args.no_check:
        bad = _check_answers(g, responses)
        if bad:
            print(f"error: {bad} answer(s) mismatched their oracle")
            return 1
        print("# all answers match their NumPy oracles")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
