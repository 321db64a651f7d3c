"""Resilient serving tier: replicated runners, heartbeat failover,
admission control, and brownout shedding.

The reference inherits fault tolerance from the Legion/Realm runtime
(task re-mapping under node loss, reference README.md:33-38); the
serving front-end (lux_tpu/serve.py) has no such layer — one Server
holds one BatchRunner per kind, and a topology fault kills every
in-flight query with it.  This module is that layer, composed from
pieces earlier rounds already proved: the heartbeat board (round 11),
the classified-retry machinery (rounds 6/11), the SLO metrics
substrate (round 17), and the continuous-batching runners themselves.

- **ReplicaPool**: a :class:`FleetServer` owns N replicas, each a full
  per-kind runner set (``serve.PushBatchRunner`` /
  ``PullBatchRunner``) — in-process by default, plus capability-gated
  SUBPROCESS replicas (``add_subprocess_replica``: an independent OS
  process running a whole ``serve.Server`` fed through a shared spool
  directory, hard-killable, its liveness visible only through the
  shared-dir :class:`heartbeat.ReplicaBoard`).  Every replica beats
  the board at each segment boundary (the runners' ``on_boundary``
  hook), and ``replica_up`` / ``replica_lost`` events trail the
  membership.

- **Admission control** (``submit``): requests carry
  tenant/priority/deadline (serve.Request); admission sheds with a
  typed :class:`AdmissionError` — reasons, in check order:
  ``no_capacity`` (no healthy replica), ``brownout`` (surviving
  capacity dropped and the request's priority is below the brownout
  floor — lowest-priority tenants shed FIRST), ``quota`` (the
  tenant's in-flight+queued count at its configured cap),
  ``queue_full`` (bounded per-kind queue), and ``deadline``
  (projected wait — queue-ahead x mean observed service time /
  surviving column capacity, the ``fleet_service_seconds`` histogram
  mean — exceeds the query's own deadline).  Admitted requests queue
  in a deadline-priority :class:`serve.PriorityCollector` (aged
  requests past half their deadline cannot be displaced
  indefinitely — the pinned aging rule) and are routed to the
  healthiest replica: min (beat age + burn-weighted SLO burn, load).
  Every shed gets a ``query_shed`` event and a record in
  ``shed_records``; resilience.classify treats AdmissionError as
  FATAL (an intentional rejection must never be retried into
  re-admission by a supervisor).

- **Failover** (exactly-once): a replica death mid-drain
  (heartbeat.WorkerLostError, faults.InjectedWorkerKill/
  InjectedDeviceLoss from a :class:`faults.ReplicaKillPlan`, a
  subprocess exit, or beat staleness past ``replica_deadline_s``)
  marks the replica lost and re-dispatches its un-retired in-flight
  queries to survivors — per query, after a
  ``resilience.RetryPolicy`` decorrelated-jitter backoff — each with
  a ``failover`` event naming from/to replicas.  Retirement is
  EXACTLY-ONCE: the front-end dedups on qid (``_retired``), a
  replayed query that already retired is dropped
  (``dup_dropped``), and because engines are deterministic in the
  graph arrays and the source, a re-dispatched integer-app query's
  answer is bitwise-equal to a fault-free run's.  The chaos
  acceptance (tests/test_fleet.py) kills a replica mid-load under
  oversubscribed mixed-kind loadgen traffic on the 8-virtual-device
  mesh and proves: every admitted answer oracle-correct, zero
  duplicate retirements, every shed typed, SLO-good fraction over
  admitted queries at target.

- **Brownout**: losing a replica raises the brownout level (one per
  lost replica); while browned out, admission requires
  ``priority >= brownout_min_priority``, so the lowest-priority
  tenants shed first and the surviving capacity serves the paying
  traffic.  The floor defaults to 0 — brownout shedding is an
  OPERATOR POLICY (which tenants are sacrificial), not a default: a
  fleet that silently dropped every default-priority query on the
  first replica loss would fail its admitted-SLO contract exactly
  when resilience matters.  A ``brownout`` event marks each level change, and
  per-replica health gauges (``fleet_replica_beat_age``) plus the
  fleet gauges (``fleet_replicas_healthy``, ``fleet_brownout_level``)
  ride the shared metrics registry.

- **Self-healing** (round 24): three layers on top of failover, so a
  fleet recovers CAPACITY and even a whole-process crash, not just
  in-flight queries.  (1) The durable ADMISSION JOURNAL
  (``journal_path=``, lux_tpu/journal.py — the MutationLog's
  CRC-chained LUXJ sidecar): every admit is fsynced to disk BEFORE it
  queues and every retirement (answer or late shed) is journaled at
  the exactly-once gate, so :meth:`FleetServer.recover` can restart a
  crashed fleet — replay the journal (torn tail truncated like the
  WAL's), seed the persisted qid dedup, and re-dispatch every
  admitted-unretired query at its ORIGINAL admission epoch
  (bitwise-equal answers for the integer apps; the only recovery
  sheds are the typed ``reset_unavailable`` / ``epoch_folded``).
  Recovery ordering is WAL replay -> generation adoption -> journal
  re-dispatch (ARCHITECTURE.md "Self-healing fleet").  (2) REPLICA
  RESURRECTION (``heal=True``): the run loop's supervisor respawns
  lost in-process replicas under ``respawn_retry`` decorrelated-
  jitter backoff; N deaths of one name inside ``flap_window_s``
  (resilience.FlapDetector) trip a typed QUARANTINE instead, and
  routing re-entry is gated on an ORACLE-CHECKED CANARY query (a
  wrong-computing replica is strictly worse than a dead one) — the
  brownout level decays as replicas rejoin, and ``fleet_mttr_seconds``
  records first-loss -> pool-whole.  (3) The WHOLE-FLEET KILL drill
  (faults.FLEET_CRASH / REPLICA_FLAP, tests/test_fleet.py) proves
  zero lost admitted queries, zero duplicate retirements, and
  oracle-equal answers at pre-crash epochs across a full
  crash-restart.

Bench: ``bench.py -config serve-chaos`` drives a FleetServer under an
open-loop load with an armed kill plan and emits serve-slo lines
extended with shed_fraction/failovers/replicas plus the round-24
healing gauges (respawns/quarantines/mttr_s/journal_replayed;
scripts/check_bench.py rejects the contradictions); the real-TPU
kill-under-load drill is carried as debt ``serve-chaos-on-device``
(lux_tpu/observe.py).  Smoke: ``python -m lux_tpu.fleet`` drains an
oversubscribed mixed load across 2 replicas with replica 1 killed
mid-drain and oracle-checks every retired answer.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

from lux_tpu import faults as faults_mod
from lux_tpu import heartbeat as heartbeat_mod
from lux_tpu import journal as journal_mod
from lux_tpu import resilience
from lux_tpu import serve as serve_mod
from lux_tpu.serve import (KINDS, DEFAULT_SEG_ITERS, PriorityCollector,
                           PullBatchRunner, PushBatchRunner, Request,
                           Response, _emit)

# shed reasons (AdmissionError.reason / query_shed events), in the
# order admission checks them
SHED_NO_CAPACITY = "no_capacity"
SHED_BROWNOUT = "brownout"
SHED_QUOTA = "quota"
SHED_QUEUE_FULL = "queue_full"
SHED_DEADLINE = "deadline"
SHED_RETRIES = "retries"
# round 20 (live graphs, lux_tpu/livegraph.py): the INGEST path's
# backpressure — the fixed-capacity delta blocks are full because
# mutations outran compaction, so the append is shed with the same
# typed AdmissionError discipline as a query (never silently dropped,
# never blocking the serving loop)
SHED_DELTA_FULL = "delta_full"
# round 22 (memory observatory, lux_tpu/memwatch.py): admitting B
# more columns is priced in BYTES (batch state + answer-cache
# headroom on top of the replica's unified ledger) and shed typed
# when the projection crosses the per-replica budget — the same
# projected-resource pattern as the deadline check, applied to the
# resource ROADMAP item 3 names as the wall
SHED_MEMORY = "memory"
# round 24 (self-healing fleet): RECOVERY-only shed reasons.  A
# journalled admit whose reset vector the recovering caller did not
# re-supply (the journal stores only the digest — a reset vector is
# nv floats and cannot live in a fixed record), and an admission
# epoch the recovered generation can no longer REPRODUCE (a durable
# compaction folded past it before the crash) — both are closed
# TYPED at recovery, never silently dropped: the journal gets a
# RETIRE(shed) record and the trail a query_shed event
SHED_RESET_UNAVAILABLE = "reset_unavailable"
SHED_EPOCH_FOLDED = "epoch_folded"

# routing health score: beat age (s) + BURN_WEIGHT x the replica's
# rolling SLO-burn fraction — a replica burning its whole SLO budget
# scores like one BURN_WEIGHT seconds behind on its heartbeat
BURN_WEIGHT = 5.0

# parent poll cadence while only subprocess answers are outstanding
REMOTE_POLL_S = 0.01
# a subprocess replica may queue up to this many x batch requests
# beyond its resident columns before routing passes it over
REMOTE_QUEUE_FACTOR = 2


class AdmissionError(RuntimeError):
    """Typed shed: the serving tier REJECTED a query instead of
    admitting it.  Carries qid/kind/tenant/reason (one of the SHED_*
    constants) and the projected wait when the deadline check shed
    it.  resilience.classify treats this as FATAL — an intentional
    rejection is a DECISION, not a failure; retrying would re-admit a
    query the tier chose to shed."""

    def __init__(self, qid: int, kind: str, tenant: str, reason: str,
                 projected_wait_s: float | None = None,
                 deadline_s: float | None = None,
                 projected_bytes: int | None = None,
                 budget_bytes: int | None = None):
        msg = (f"query {qid} [{kind}] from tenant {tenant!r} shed: "
               f"{reason}")
        if projected_wait_s is not None:
            msg += (f" (projected wait {projected_wait_s:.3f}s vs "
                    f"deadline {deadline_s}s)")
        if projected_bytes is not None:
            msg += (f" (projected {projected_bytes} bytes vs budget "
                    f"{budget_bytes} bytes)")
        super().__init__(msg)
        self.qid = int(qid)
        self.kind = kind
        self.tenant = tenant
        self.reason = reason
        self.projected_wait_s = projected_wait_s
        self.deadline_s = deadline_s
        self.projected_bytes = projected_bytes
        self.budget_bytes = budget_bytes


class _InProcessReplica:
    """One in-process runner set (one batched engine per kind) plus
    its health bookkeeping."""

    remote = False

    def __init__(self, fleet: "FleetServer", name: str, index: int):
        self.fleet = fleet
        self.name = name
        self.index = int(index)
        self.state = "up"
        self.error: BaseException | None = None
        self._runners: dict = {}
        self._collectors: dict = {}

    def runner(self, kind: str):
        if kind not in self._runners:
            r = self.fleet._build_runner(kind)
            r.replica = self.name
            r.on_boundary = lambda runner, rep=self: \
                self.fleet._boundary(rep, runner)
            self._runners[kind] = r
        return self._runners[kind]

    def collector(self, kind: str) -> PriorityCollector:
        if kind not in self._collectors:
            self._collectors[kind] = PriorityCollector(
                metrics=self.fleet.metrics, kind=kind,
                replica=self.name)
        return self._collectors[kind]

    def pending(self, kind: str) -> int:
        n = len(self._collectors[kind]) if kind in self._collectors \
            else 0
        if kind in self._runners:
            n += sum(1 for s in self._runners[kind].slots
                     if s is not None)
        return n

    def pending_total(self) -> int:
        kinds = set(self._collectors) | set(self._runners)
        return sum(self.pending(k) for k in kinds)

    def slo_burn(self) -> float:
        """Mean rolling SLO-burn fraction over this replica's
        runners (0.0 when no SLO accounting ran yet)."""
        fracs = []
        for r in self._runners.values():
            if r._slo_window:
                fracs.append(sum(r._slo_window) / len(r._slo_window))
        return sum(fracs) / len(fracs) if fracs else 0.0


class _SubprocessReplica:
    """A replica in its own OS process (a whole serve.Server fed
    through a spool directory).  Liveness comes from the replica
    board (and the process exit code); answers arrive as
    npy+json file pairs, json written LAST so its presence marks a
    complete answer."""

    remote = True

    def __init__(self, fleet: "FleetServer", name: str, index: int,
                 spool: str, proc, batch: int):
        self.fleet = fleet
        self.name = name
        self.index = int(index)
        self.state = "up"
        self.error: BaseException | None = None
        self.spool = spool
        self.inbox = os.path.join(spool, f"inbox_{name}")
        self.outdir = os.path.join(spool, f"out_{name}")
        self.proc = proc
        self.batch = int(batch)
        self.inflight: dict[int, Request] = {}

    def free(self) -> int:
        return REMOTE_QUEUE_FACTOR * self.batch - len(self.inflight)

    def pending(self, kind: str) -> int:
        return sum(1 for r in self.inflight.values()
                   if r.kind == kind)

    def pending_total(self) -> int:
        return len(self.inflight)

    def slo_burn(self) -> float:
        return 0.0          # worker-side burn is not exported (yet)

    def dispatch(self, req: Request) -> None:
        doc = {"qid": req.qid, "kind": req.kind, "source": req.source}
        if req.reset is not None:
            # personalized-pagerank reset vectors ride an npy
            # sidecar, written BEFORE the request json (the json's
            # presence marks a complete request pair)
            fd, tmp = tempfile.mkstemp(dir=self.spool,
                                       suffix=".rst.tmp")
            with os.fdopen(fd, "wb") as f:
                # lockcheck: allow(durable-before-visible) same-host
                # IPC spool, not a durability record: a torn/lost
                # reset is re-dispatched from the admission journal
                np.save(f, np.asarray(req.reset, np.float32))
            os.replace(tmp, os.path.join(
                self.inbox, f"q{req.qid:08d}.reset.npy"))
            doc["reset"] = True
        fd, tmp = tempfile.mkstemp(dir=self.spool, suffix=".req.tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, os.path.join(self.inbox,
                                     f"q{req.qid:08d}.json"))
        self.inflight[req.qid] = req

    def stop(self) -> None:
        try:
            with open(os.path.join(self.spool, "stop"), "w") as f:
                f.write("stop\n")
        except OSError:
            pass
        if self.proc is not None and self.proc.poll() is None:
            try:
                self.proc.terminate()
            except OSError:
                pass


class FleetServer:
    """The resilient serving tier above serve.Server: route queries
    by kind across a pool of replicas with admission control,
    heartbeat-supervised failover and brownout shedding (module
    docstring has the full contract).  Duck-type compatible with
    serve.Server for scripts/loadgen.py: ``g``/``submit``/``run``/
    ``serve``/``stop``/``set_metrics``/``emit_metrics_snapshot``."""

    def __init__(self, g, *, replicas: int = 2, batch: int = 4,
                 num_parts: int = 1, mesh=None, exchange: str = "auto",
                 health: bool = False, weighted: bool = False,
                 seg_iters: int = DEFAULT_SEG_ITERS, tol: float = 1e-8,
                 slo_ms: dict | None = None, metrics=None,
                 snapshot_every_s: float = 1.0,
                 board_path: str | None = None,
                 max_queue: int = 256, quota: dict | None = None,
                 brownout_min_priority: int = 0,
                 retry: resilience.RetryPolicy | None = None,
                 fault: faults_mod.ReplicaKillPlan | None = None,
                 replica_deadline_s: float = 3.0, live=None,
                 cache: bool = False,
                 mem_budget_bytes: int | None = None,
                 mem_horizon_s: float = 5.0,
                 mem_clock=time.monotonic,
                 journal_path: str | None = None,
                 heal: bool = False,
                 respawn_retry: resilience.RetryPolicy | None = None,
                 flap_threshold: int = 3,
                 flap_window_s: float = 60.0,
                 heal_clock=time.monotonic):
        if replicas < 1:
            raise ValueError(f"fleet needs >= 1 replica, got "
                             f"{replicas}")
        self.g = g
        # live-graph serving (round 20, lux_tpu/livegraph.py): one
        # SHARED LiveGraph across every in-process replica — its
        # published delta blocks are immutable, so a failed-over
        # query re-runs on the survivor at its ORIGINAL admission
        # epoch and (integer apps) answers bitwise-identically.
        # Subprocess replicas serve the static graph spec and carry
        # no live handle, so a live fleet REFUSES them (typed, in
        # add_subprocess_replica): a remote answer computed on the
        # static base would wear epoch=None and evade both the
        # torn-epoch audit and check_live_answers.
        self.live = live
        if live is not None and g is not live.base:
            raise ValueError(
                "FleetServer(live=...) requires g to be live.base")
        if cache is True:
            from lux_tpu.serve import AnswerCache
            self.cache = AnswerCache.from_slo(slo_ms)
        elif cache:
            self.cache = cache
        else:
            self.cache = None
        self.batch = int(batch)
        self.opts = dict(num_parts=num_parts, mesh=mesh,
                         exchange=exchange, health=health)
        self.weighted = bool(weighted)
        self.seg_iters = int(seg_iters)
        self.tol = float(tol)
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
        self.snapshot_every_s = float(snapshot_every_s)
        self._last_snapshot = 0.0
        self.board = heartbeat_mod.ReplicaBoard(
            board_path or tempfile.mkdtemp(prefix="lux_fleet_board_"),
            deadline_s=float(replica_deadline_s))
        self.replica_deadline_s = float(replica_deadline_s)
        self.max_queue = int(max_queue)
        self.quota = dict(quota or {})
        self.brownout_min_priority = int(brownout_min_priority)
        self.retry = retry or resilience.RetryPolicy(
            retries=3, backoff_s=0.02, max_backoff_s=0.5)
        self.fault = fault
        # round-22 memory observatory: per-replica byte budget (None
        # = unbudgeted — no memory admission, no forecaster) + the
        # boundary-fed occupancy trails (built lazily per replica at
        # its first boundary; fake-clock-injectable for tests)
        self.mem_budget_bytes = (None if mem_budget_bytes is None
                                 else int(mem_budget_bytes))
        self.mem_horizon_s = float(mem_horizon_s)
        self.mem_clock = mem_clock
        self._mem_trails: dict = {}

        import threading
        # RLock: admission (submitter threads) and retirement /
        # late-shed bookkeeping (the drain thread) share the tenant
        # and qid maps; _shed runs both under the lock (inside
        # _admission) and outside it
        self._lock = threading.RLock()
        # serve()'s wait between two drains: submit and stop() wake it
        self._wake = threading.Condition()
        self._stopping = False
        # all kinds pre-created: _queues is never mutated after
        # construction, so the run loop / pending views can iterate
        # it while submitter threads insert requests (a lazy
        # setdefault here would be a dict-changed-size crash)
        self._queues: dict[str, PriorityCollector] = {
            k: PriorityCollector(metrics=None, kind=k)
            for k in KINDS}
        self._replicas: list = []
        self._next_qid = 0
        self._qreq: dict[int, Request] = {}
        self._retired: set[int] = set()
        self._attempts: dict[int, int] = {}
        self._tenant_load: dict[str, int] = {}
        self.failovers = 0
        self.dup_dropped = 0
        self.shed_records: list[AdmissionError] = []
        self._brownout = 0
        # round-24 self-healing state.  The admission journal makes
        # every admit durable BEFORE it queues (and every retirement
        # durable at the exactly-once gate), so FleetServer.recover
        # can re-dispatch a crashed fleet's admitted-unretired
        # queries at their original epochs; ``heal`` arms the
        # resurrection supervisor (respawn under decorrelated-jitter
        # backoff, flap -> quarantine, canary-gated routing
        # re-entry).
        self.journal = (None if journal_path is None else
                        journal_mod.AdmissionJournal(journal_path,
                                                     nv=g.nv))
        self._journaled: set[int] = set()
        self.heal = bool(heal)
        self.respawn_retry = respawn_retry or self.retry
        self.heal_clock = heal_clock
        self.flap = resilience.FlapDetector(
            threshold=int(flap_threshold),
            window_s=float(flap_window_s), clock=heal_clock)
        self.respawns = 0
        self.quarantines = 0
        self.journal_replayed = 0
        self.mttr_s: float | None = None
        self._respawn_at: dict[str, float] = {}
        self._respawn_attempts: dict[str, int] = {}
        self._canaries: set[int] = set()
        self._t_degraded: float | None = None
        for i in range(int(replicas)):
            self._add_inproc_replica()

    # -- replica pool --------------------------------------------------

    @property
    def replica_names(self) -> list[str]:
        return [r.name for r in self._replicas]

    def _add_inproc_replica(self):
        name = f"r{len(self._replicas)}"
        rep = _InProcessReplica(self, name, len(self._replicas))
        self._replicas.append(rep)
        self.board.beat(name, status="up", boundary=0)
        _emit("replica_up", replica=name, remote=False,
              capacity=self.batch)
        self._health_gauges()
        return rep

    def add_subprocess_replica(self, graph_spec: dict, *,
                               workdir: str | None = None,
                               num_parts: int = 1,
                               kill_boundary: int | None = None,
                               spawn_budget_s: float = 60.0):
        """Spawn a subprocess replica (capability probe included):
        launch the worker, wait up to ``spawn_budget_s`` for its
        first board beat, and return the replica — or None when the
        environment cannot spawn one in budget (the caller falls back
        to an in-process replica; the chaos drill's documented
        fallback path).  ``graph_spec`` must rebuild the SAME graph
        the parent serves (see ``_graph_from_spec``);
        ``kill_boundary`` arms a hard-kill ReplicaKillPlan inside the
        worker."""
        import subprocess

        if self.live is not None:
            raise ValueError(
                "subprocess replicas serve the static graph spec "
                "and cannot answer at a live admission epoch — a "
                "live-graph fleet is in-process only")
        name = f"r{len(self._replicas)}"
        spool = workdir or tempfile.mkdtemp(prefix="lux_fleet_")
        os.makedirs(os.path.join(spool, f"inbox_{name}"),
                    exist_ok=True)
        os.makedirs(os.path.join(spool, f"out_{name}"), exist_ok=True)
        spec = {"name": name, "dir": spool, "board": self.board.path,
                "graph": dict(graph_spec), "batch": self.batch,
                "num_parts": int(num_parts),
                "seg_iters": self.seg_iters, "tol": self.tol,
                "weighted": self.weighted,
                "kill_boundary": kill_boundary}
        spec_path = os.path.join(spool, f"spec_{name}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        proc = subprocess.Popen(
            [sys.executable, "-m", "lux_tpu.fleet", "-worker",
             spec_path],
            env=_worker_env(ndev=num_parts), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        t0 = time.monotonic()
        ok = False
        while time.monotonic() - t0 < float(spawn_budget_s):
            if self.board.read(name) is not None:
                ok = True
                break
            if proc.poll() is not None:
                break
            time.sleep(0.05)
        if not ok:
            if proc.poll() is None:
                proc.kill()
            return None
        rep = _SubprocessReplica(self, name, len(self._replicas),
                                 spool, proc, self.batch)
        self._replicas.append(rep)
        _emit("replica_up", replica=name, remote=True,
              capacity=self.batch)
        self._health_gauges()
        return rep

    def _build_runner(self, kind: str):
        mkw = dict(metrics=self.metrics,
                   slo_ms=self.slo_ms.get(kind),
                   live=self.live, cache=self.cache)
        if kind == "pagerank":
            return PullBatchRunner(kind, self.g, self.batch,
                                   seg_iters=self.seg_iters,
                                   tol=self.tol, **mkw, **self.opts)
        return PushBatchRunner(kind, self.g, self.batch,
                               weighted=self.weighted,
                               seg_iters=self.seg_iters, **mkw,
                               **self.opts)

    def _boundary(self, rep, runner) -> None:
        """Per-replica segment-boundary hook: beat the board, sample
        the memory trail (budgeted fleets only — the forecaster's
        mem_pressure warning must land BEFORE any memory shed or
        DeltaFullError in the event trail), then fire the chaos plan
        (whose raise propagates out of the drain as a mid-drain
        death)."""
        self.board.beat(rep.name, status="up", kind=runner.kind)
        if self.mem_budget_bytes is not None:
            self.mem_trail(rep.name).sample(
                where=f"{runner.kind}:boundary")
        if self.fault is not None:
            self.fault.fire(rep.name)

    def mem_trail(self, name: str):
        """The named replica's boundary-fed occupancy trail
        (memwatch.MemoryTrail, built lazily; budget + forecaster
        attached when the fleet carries ``mem_budget_bytes``).  The
        trail's bytes source is the replica's UNIFIED ledger —
        static engine terms + the shared dynamic consumers — priced
        by host arithmetic only (no compile, no device traffic: the
        boundary hook contract)."""
        from lux_tpu import memwatch

        if name not in self._mem_trails:
            rep = next(r for r in self._replicas if r.name == name)
            self._mem_trails[name] = memwatch.MemoryTrail(
                bytes_fn=lambda: self._replica_bytes(rep),
                metrics=self.metrics or None, replica=name,
                budget_bytes=self.mem_budget_bytes,
                horizon_s=self.mem_horizon_s, clock=self.mem_clock)
        return self._mem_trails[name]

    def _replica_bytes(self, rep) -> int:
        """One replica's unified-ledger total right now (memwatch
        pillar 2): its built runners' static terms + the tier-shared
        cache/live/staging consumers."""
        from lux_tpu import memwatch

        return memwatch.replica_ledger(self, rep).total_bytes

    def set_fault(self, plan) -> None:
        """Arm (or disarm with None) a faults.ReplicaKillPlan — bench
        arms it AFTER the engine-compile warmup so the kill boundary
        counts only loaded traffic."""
        self.fault = plan

    def _healthy(self) -> list:
        return [r for r in self._replicas if r.state == "up"]

    def _score(self, rep, kind: str) -> float:
        age = self.board.age(rep.name)
        return (age if age is not None else 0.0) \
            + BURN_WEIGHT * rep.slo_burn()

    def _pick(self, kind: str):
        """Healthiest replica with room: min (health score, load)."""
        cands = [r for r in self._healthy()
                 if not r.remote or r.free() > 0]
        if not cands:
            return None
        return min(cands, key=lambda r: (round(self._score(r, kind),
                                               6),
                                         r.pending_total(), r.index))

    def routing_target(self, kind: str) -> str | None:
        """The replica name the NEXT query of ``kind`` would route
        to (None when none is healthy).  Chaos drills arm their kill
        plans on this: routing is a positive-feedback loop — the
        picked replica drains, which refreshes its beat, which keeps
        it the pick — so a plan armed on any FIXED index is a coin
        flip on millisecond beat timing inside warm(), and the
        losing side is a drill whose kill never fires (the round-22
        serve-chaos fix; tests/test_memwatch.py pins it)."""
        rep = self._pick(kind)
        return None if rep is None else rep.name

    def _health_gauges(self) -> None:
        if self.metrics is None:
            return
        m = self.metrics
        m.gauge("fleet_replicas_healthy").set(len(self._healthy()))
        m.gauge("fleet_brownout_level").set(self._brownout)
        for rep in self._replicas:
            age = self.board.age(rep.name)
            m.gauge("fleet_replica_beat_age",
                    replica=rep.name).set(age if age is not None
                                          else -1.0)

    # -- admission -----------------------------------------------------

    def _queue(self, kind: str) -> PriorityCollector:
        # fleet queues carry no metrics handle: queue-wait is
        # observed once, at column collection in the replica's own
        # collector (double-observing would halve every percentile)
        if kind not in self._queues:
            raise ValueError(f"unknown query kind {kind!r}; choose "
                             f"from {KINDS}")
        return self._queues[kind]

    def _projected_wait(self, kind: str) -> float:
        """Queue-ahead x mean observed service time / surviving
        column capacity — 0.0 until the first retirement seeds the
        service-time histogram (cold admission is optimistic by
        design: shedding on no evidence would brown out an idle
        tier)."""
        mean = None
        if self.metrics is not None:
            mean = self.metrics.histogram("fleet_service_seconds",
                                          kind=kind).mean()
        if mean is None:
            return 0.0
        ahead = len(self._queue(kind)) + sum(
            r.pending(kind) for r in self._healthy())
        cap = self.batch * max(1, len(self._healthy()))
        return ahead * mean / cap

    def _shed(self, req: Request, reason: str, *,
              projected: float | None = None,
              projected_bytes: int | None = None,
              raise_: bool = True):
        err = AdmissionError(req.qid, req.kind, req.tenant, reason,
                             projected_wait_s=projected,
                             deadline_s=req.deadline_s,
                             projected_bytes=projected_bytes,
                             budget_bytes=(self.mem_budget_bytes
                                           if projected_bytes
                                           is not None else None))
        with self._lock:
            self.shed_records.append(err)
            if req.qid in self._qreq:   # late shed of an admitted req
                self._qreq.pop(req.qid, None)
                self._tenant_load[req.tenant] = max(
                    0, self._tenant_load.get(req.tenant, 1) - 1)
                if self.live is not None:
                    self.live.release()
            if (self.journal is not None
                    and req.qid in self._journaled):
                # a late shed RETIRES the journal entry (cause
                # "shed"): recover() must not resurrect a query the
                # fleet already rejected with a typed AdmissionError
                self.journal.append_retire(req.qid, "shed")
                self._journaled.discard(req.qid)
        if self.metrics is not None:
            self.metrics.counter("fleet_shed_total", kind=req.kind,
                                 reason=reason).inc()
        extra = {} if projected is None else {
            "projected_wait_s": round(projected, 6)}
        if projected_bytes is not None:
            extra["projected_bytes"] = int(projected_bytes)
            extra["budget_bytes"] = self.mem_budget_bytes
        _emit("query_shed", qid=req.qid, query_kind=req.kind,
              tenant=req.tenant, priority=req.priority,
              reason=reason, **extra)
        if raise_:
            raise err
        return err

    def _admission(self, req: Request) -> None:
        if not self._healthy():
            self._shed(req, SHED_NO_CAPACITY)
        if self._brownout and req.priority < self.brownout_min_priority:
            self._shed(req, SHED_BROWNOUT)
        cap = self.quota.get(req.tenant)
        if cap is not None \
                and self._tenant_load.get(req.tenant, 0) >= cap:
            self._shed(req, SHED_QUOTA)
        if len(self._queue(req.kind)) >= self.max_queue:
            self._shed(req, SHED_QUEUE_FULL)
        if req.deadline_s is not None:
            p = self._projected_wait(req.kind)
            if p > req.deadline_s:
                self._shed(req, SHED_DEADLINE, projected=p)
        if self.mem_budget_bytes is not None:
            b = self._projected_bytes(req.kind)
            if b is not None and b > self.mem_budget_bytes:
                self._shed(req, SHED_MEMORY, projected_bytes=b)

    def _projected_bytes(self, kind: str) -> int | None:
        """Projected resident bytes of the routing target AFTER
        admitting this query's batch (memwatch pillar 3): the
        replica's unified-ledger total + batch x (column state +
        answer-cache headroom).  None when no replica is routable
        (the no_capacity check upstream already shed) or the target
        replica has not built the kind's engine yet (a cold replica
        cannot be priced per column — cold admission stays
        optimistic, exactly like _projected_wait)."""
        from lux_tpu import memwatch

        rep = self._pick(kind)
        if rep is None or rep.remote:
            return None
        runner = rep._runners.get(kind)
        if runner is None:
            return None
        return memwatch.projected_admission_bytes(
            self._replica_bytes(rep), batch=self.batch,
            column_bytes=memwatch.column_state_bytes(runner.eng),
            answer_bytes=(0 if self.cache is None
                          else self.g.nv
                          * memwatch.ANSWER_BYTES_PER_VERTEX))

    def _admission_epoch(self, kind: str) -> int | None:
        """READ the epoch a query of ``kind`` would pin (cache
        sweeps; admission itself stamps atomically through
        serve.admit_query).  The pin survives failover re-dispatch,
        so a re-run on a survivor answers at the same epoch bitwise
        (serve._engine_family is the one kind-to-family rule)."""
        return serve_mod.admission_epoch(self.live, kind)

    def mutate(self, src, dst, weights=None,
               tenant: str = "default", op: str = "append") -> int:
        """The serving tier's INGEST path: publish one mutation
        batch into the shared live graph — ``op`` routes the full
        round-21 algebra ("append" default / "delete" / "reweight",
        serve.Server.mutate's rule).  When the delta blocks are full
        (ingest outran compaction) the mutation is shed with a
        typed ``AdmissionError(reason="delta_full")`` — recorded in
        shed_records and as a query_shed event like every other
        rejection — instead of blocking or silently dropping."""
        from lux_tpu import livegraph

        if self.live is None:
            raise ValueError("mutate() needs a live graph "
                             "(FleetServer(live=LiveGraph(...)))")
        if op not in ("append", "delete", "reweight"):
            raise ValueError(f"unknown mutation op {op!r}; choose "
                             f"from ('append', 'delete', "
                             f"'reweight')")
        try:
            if op == "delete":
                return self.live.delete_edges(src, dst)
            if op == "reweight":
                return self.live.reweight_edges(src, dst, weights)
            return self.live.append_edges(src, dst, weights)
        except livegraph.DeltaFullError:
            with self._lock:
                qid = self._next_qid
                self._next_qid += 1
            req = Request(qid=qid, kind="mutation",
                          t_enqueue=time.monotonic(),
                          tenant=str(tenant))
            self._shed(req, SHED_DELTA_FULL)

    def slo_burn(self) -> float:
        """Worst replica rolling SLO-burn fraction — the
        CompactionScheduler's backoff input (the same per-replica
        gauge routing already weighs, taken fleet-wide)."""
        return max((rep.slo_burn() for rep in self._replicas),
                   default=0.0)

    def refresh_live(self) -> None:
        """Adopt the live graph's new generation after a compaction
        (serve.Server.refresh_live's fleet analogue): every replica's
        runners are dropped and lazily rebuilt over the compacted
        base.  Refuses while queries are dispatched/resident at a
        replica, or CENTRALLY queued at an epoch the new base cannot
        REPRODUCE (serve._epoch_reproducible — both families replay
        any epoch >= base_epoch: push via the delta mask, pull via
        the degree-correction step; serve.Server.refresh_live's
        rule)."""
        if self.live is None:
            return
        stale = [req for q in self._queues.values()
                 for req in q.pending_requests()
                 if not serve_mod._epoch_reproducible(self.live,
                                                     req)]
        if stale:
            raise RuntimeError(
                f"refresh_live with {len(stale)} query(ies) queued "
                f"at an epoch the new generation cannot reproduce — "
                f"drain first")
        if any(rep.pending_total() for rep in self._healthy()):
            raise RuntimeError("refresh_live with queries still "
                               "dispatched or resident — drain "
                               "first")
        self.g = self.live.base
        for rep in self._replicas:
            if not rep.remote:
                rep._runners.clear()

    def submit(self, kind: str, source: int | None = None,
               reset=None, tenant: str = "default", priority: int = 0,
               deadline_s: float | None = None) -> int:
        """Admit-or-shed: returns the qid, or raises a typed
        AdmissionError (which also leaves a query_shed event and a
        shed_records entry — every rejection is accounted)."""
        q = self._queue(kind)           # validates kind first
        with self._lock:
            qid = self._next_qid
            self._next_qid += 1
        req = Request(qid=qid, kind=kind,
                      source=None if source is None else int(source),
                      reset=(None if reset is None
                             else np.asarray(reset, np.float32)),
                      t_enqueue=time.monotonic(), tenant=str(tenant),
                      priority=int(priority),
                      deadline_s=(None if deadline_s is None
                                  else float(deadline_s)),
                      # stamp + admission-ledger entry atomically
                      # (serve.admit_query): the pinned epoch must
                      # stay serveable until this query's
                      # exactly-once retirement (_accept) or
                      # post-admission shed — released there; an
                      # admission-time shed releases below
                      epoch=serve_mod.admit_query(self.live, kind))
        if self.metrics is not None:
            self.metrics.counter("serve_queries_total",
                                 kind=kind).inc()
        _emit("query_enqueue", qid=qid, query_kind=kind,
              source=req.source, tenant=req.tenant,
              priority=req.priority, queued=len(q))
        with self._lock:
            try:
                self._admission(req)
            except AdmissionError:
                if self.live is not None:
                    self.live.release()
                raise
            if self.journal is not None:
                # durable BEFORE visible: the admit record reaches
                # the platter (write+flush+fsync) before the query
                # enters a routing queue, so a crash can lose an
                # un-acknowledged submit but never an acknowledged
                # one — recover() re-dispatches exactly this set
                try:
                    self.journal.append_admit(req)
                except BaseException:
                    if self.live is not None:
                        self.live.release()
                    raise
                self._journaled.add(qid)
            self._qreq[qid] = req
            self._tenant_load[req.tenant] = \
                self._tenant_load.get(req.tenant, 0) + 1
            q.put(req)
        with self._wake:
            self._wake.notify_all()
        return qid

    def warm(self, kinds=None) -> int:
        """Compile EVERY (replica, kind) engine outside a measured
        load: one throwaway query per replica per kind, assigned
        DIRECTLY to each replica (load-spread routing would warm one
        replica and leave the others' runners cold, billing XLA
        compilation to the first measured queries that land there —
        the warm contract loadgen's single-server warm cannot keep
        for a fleet).  Returns the number of warm responses
        drained."""
        kinds = list(kinds or KINDS)
        for rep in self._replicas:
            if rep.state != "up":
                continue
            for k in kinds:
                with self._lock:
                    qid = self._next_qid
                    self._next_qid += 1
                req = Request(qid=qid, kind=k, source=0,
                              t_enqueue=time.monotonic(),
                              epoch=serve_mod.admit_query(self.live,
                                                          k),
                              no_cache=True)
                _emit("query_enqueue", qid=qid, query_kind=k,
                      source=0, tenant=req.tenant,
                      priority=req.priority, queued=0)
                with self._lock:
                    self._qreq[qid] = req
                    self._tenant_load[req.tenant] = \
                        self._tenant_load.get(req.tenant, 0) + 1
                self._assign(rep, req)
        return len(self.run())

    # -- dispatch / drain / failover -----------------------------------

    def _assign(self, rep, req: Request) -> None:
        if rep.remote:
            rep.dispatch(req)
        else:
            rep.collector(req.kind).put(req)

    def _accept(self, resp: Response) -> bool:
        """Exactly-once retirement: False (and dropped) when the qid
        already retired — the replayed-query guard."""
        with self._lock:
            if resp.qid in self._retired:
                self.dup_dropped += 1
                if self.metrics is not None:
                    self.metrics.counter("fleet_dup_dropped_total",
                                         kind=resp.kind).inc()
                return False
            self._retired.add(resp.qid)
            req = self._qreq.pop(resp.qid, None)
            if req is not None:
                self._tenant_load[req.tenant] = max(
                    0, self._tenant_load.get(req.tenant, 1) - 1)
                if self.live is not None:
                    # exactly-once: the pop above is the dedup gate,
                    # so a replayed answer can never double-release
                    self.live.release()
            if (self.journal is not None
                    and resp.qid in self._journaled):
                # the _retired gate above makes this exactly-once on
                # disk too: a replayed answer returns False before
                # reaching here, so no qid retires twice in the
                # journal (retire_dup is rot, not replay)
                self.journal.append_retire(resp.qid, "answered")
                self._journaled.discard(resp.qid)
        if self.metrics is not None and not resp.cached:
            # cache hits retire in ~0s and never touch an engine —
            # feeding them into the service-time histogram would
            # drag down the mean the deadline-admission projection
            # divides by, admitting queries that will actually wait
            # a full engine drain instead of shedding them typed
            self.metrics.histogram(
                "fleet_service_seconds", kind=resp.kind).observe(
                max(0.0, resp.latency_s - resp.wait_s))
        return True

    def _drain_inproc(self, rep, kind: str) -> list[Response]:
        runner = rep.runner(kind)
        n0 = len(runner.responses)
        err = None
        try:
            runner.drain(rep.collector(kind), deadline_s=0.0)
        except (heartbeat_mod.WorkerLostError,
                faults_mod.InjectedWorkerKill,
                faults_mod.InjectedDeviceLoss) as e:
            err = e
        out = [r for r in runner.responses[n0:] if self._accept(r)]
        if err is not None:
            self._mark_lost(rep, err)
        return out

    def _mark_lost(self, rep, err: BaseException) -> None:
        if rep.state == "lost":
            return
        rep.state = "lost"
        rep.error = err
        inflight: list[Request] = []
        if rep.remote:
            inflight = list(rep.inflight.values())
            rep.inflight.clear()
        else:
            for runner in rep._runners.values():
                for c, slot in enumerate(runner.slots):
                    if slot is not None:
                        inflight.append(slot.req)
                        runner.slots[c] = None
                        if runner.live is not None:
                            # the dead replica's resident queries no
                            # longer pin the generation; the
                            # re-dispatch pins again at _start
                            runner.live.unpin()
                # queries whose columns were freed at a boundary and
                # whose answers were never made (serve.py: the
                # boundary's second half runs behind the NEXT
                # dispatch, which the dead replica never made good)
                inflight += runner.unanswered()
            for coll in rep._collectors.values():
                # suppress the dead collector's metrics for this
                # drain: the requests are about to re-queue on a
                # survivor, and observing their partial wait HERE
                # would double-count serve_wait_seconds (the replica
                # is lost — its collectors are never used again)
                coll.metrics = None
                inflight += coll.collect(len(coll))
        inflight = [r for r in inflight if r.qid not in self._retired]
        # a dead replica's CANARY dies with it: the probe exists to
        # exercise THAT replica's engine — failing it over to a
        # survivor would answer a question nobody asked and pollute
        # run()'s responses with throwaway qids
        canaries = [r for r in inflight if r.qid in self._canaries]
        inflight = [r for r in inflight
                    if r.qid not in self._canaries]
        with self._lock:
            for r in canaries:
                self._retired.add(r.qid)
                if self._qreq.pop(r.qid, None) is not None:
                    self._tenant_load[r.tenant] = max(
                        0, self._tenant_load.get(r.tenant, 1) - 1)
                    if self.live is not None:
                        self.live.release()
                self._canaries.discard(r.qid)
        _emit("replica_lost", replica=rep.name,
              error=type(err).__name__, message=str(err)[:200],
              inflight=len(inflight))
        if self.metrics is not None:
            self.metrics.counter("fleet_replica_lost_total").inc()
        # self-healing bookkeeping BEFORE the failovers below: MTTR
        # counts from the first detection that degraded the fleet,
        # and the flap verdict decides whether this death schedules
        # a resurrection or trips the quarantine
        if self._t_degraded is None:
            self._t_degraded = float(self.heal_clock())
        deaths = self.flap.record(rep.name)
        if not rep.remote:
            # the verdict applies whether healing is automatic
            # (run-loop _heal) or manual (resurrect()): a flapping
            # name must stop consuming respawns either way
            if deaths >= self.flap.threshold:
                self._quarantine(rep, reason="flap", deaths=deaths)
            else:
                k = self._respawn_attempts.get(rep.name, 0)
                self._respawn_at[rep.name] = (
                    float(self.heal_clock())
                    + self.respawn_retry.delay_s(k))
        self._set_brownout()
        self._health_gauges()
        t_detect = time.monotonic()
        for req in sorted(inflight, key=lambda r: r.t_enqueue):
            self._failover(req, rep, t_detect=t_detect)

    def _set_brownout(self) -> None:
        """Recompute the brownout level from the CURRENT pool state
        — one level per replica not serving (lost or quarantined) —
        and emit the level-change event both ways: resurrection
        DECAYS the level as replicas rejoin (down to 0 when the pool
        is whole again), the round-24 contract the original
        lost-count-only computation could never express."""
        level = sum(1 for r in self._replicas if r.state != "up")
        if level != self._brownout:
            self._brownout = level
            total = max(1, len(self._replicas))
            _emit("brownout", level=level,
                  capacity_frac=round(len(self._healthy()) / total,
                                      4),
                  min_priority=self.brownout_min_priority)

    def _quarantine(self, rep, reason: str, deaths: int = 0) -> None:
        """Typed removal from the resurrection loop: the replica is
        neither routed to nor respawned until an operator replaces
        it.  ``reason`` is "flap" (threshold deaths inside the flap
        window) or "canary" (the warm-up probe answered WRONG — a
        replica that computes incorrect answers is strictly worse
        than a dead one)."""
        rep.state = "quarantined"
        self.quarantines += 1
        self._respawn_at.pop(rep.name, None)
        _emit("replica_quarantine", replica=rep.name, reason=reason,
              deaths=int(deaths),
              window_s=round(self.flap.window_s, 3))
        if self.metrics is not None:
            self.metrics.counter("fleet_quarantines_total").inc()
        self._set_brownout()
        self._health_gauges()

    # -- resurrection (round 24) ---------------------------------------

    def _heal(self) -> None:
        """Non-blocking supervisor tick (run-loop hook): respawn
        every lost in-process replica whose decorrelated-jitter
        backoff has expired.  Quarantined replicas are never
        touched."""
        if not self.heal:
            return
        now = float(self.heal_clock())
        for rep in list(self._replicas):
            if rep.state != "lost" or rep.remote:
                continue
            due = self._respawn_at.get(rep.name)
            if due is not None and now >= due:
                self._respawn(rep)

    def resurrect(self, wait: bool = True) -> list[str]:
        """Drive resurrection to QUIESCENCE outside a serve loop:
        respawn every lost in-process replica (waiting out each
        backoff when ``wait``), repeating while the respawns
        themselves die (the flap pattern), until every replica is
        either up or quarantined.  Returns the names that re-entered
        routing.  Works with ``heal=False`` too — manual healing
        between drains."""
        out: list[str] = []
        while True:
            targets = [r for r in self._replicas
                       if r.state == "lost" and not r.remote]
            if not targets:
                break
            for rep in targets:
                now = float(self.heal_clock())
                due = self._respawn_at.get(rep.name)
                if due is None:
                    k = self._respawn_attempts.get(rep.name, 0)
                    due = now + self.respawn_retry.delay_s(k)
                    self._respawn_at[rep.name] = due
                if due > now:
                    if not wait:
                        return out
                    self.respawn_retry.sleep(due - now)
                if self._respawn(rep):
                    out.append(rep.name)
        return out

    def _respawn(self, rep) -> bool:
        """One resurrection attempt: replace the dead replica with a
        fresh runner set under the SAME name/index, warm it up — the
        canary recompiles its engine over the CURRENT base
        (generation adoption: runners build from ``self.g``, which
        refresh_live keeps at ``live.base``; in-process replicas
        share the live handle, so the published delta needs no
        catch-up) — and gate routing re-entry on the canary
        answering its NumPy oracle exactly.  Returns True when the
        replica re-entered routing."""
        name = rep.name
        k = self._respawn_attempts.get(name, 0)
        self._respawn_attempts[name] = k + 1
        self._respawn_at.pop(name, None)
        new = _InProcessReplica(self, name, rep.index)
        new.state = "warming"       # invisible to _pick until canary
        self._replicas[rep.index] = new
        # the old replica's memory-trail closure prices dead runners
        self._mem_trails.pop(name, None)
        self.board.beat(name, status="warming", boundary=0)
        ok = self._run_canary(new)
        if new.state != "warming":
            # died mid-warm-up: _mark_lost already recorded the
            # death, and its flap verdict re-scheduled or
            # quarantined — nothing more to do here
            return False
        if not ok:
            # a replica that computes WRONG answers is strictly
            # worse than a dead one — never route to it
            self._quarantine(new, reason="canary",
                             deaths=self.flap.deaths(name))
            return False
        new.state = "up"
        self.respawns += 1
        self._respawn_attempts[name] = 0    # healthy: fresh incident
        _emit("replica_respawn", replica=name, attempt=k + 1,
              backoff_s=round(self.respawn_retry.delay_s(k), 4),
              canary_ok=True)
        if self.metrics is not None:
            self.metrics.counter("fleet_respawns_total").inc()
        self.board.beat(name, status="up", boundary=0)
        self._set_brownout()
        self._health_gauges()
        if (self._t_degraded is not None
                and all(r.state == "up" for r in self._replicas)):
            # MTTR: first loss detection -> pool whole again
            self.mttr_s = (float(self.heal_clock())
                           - self._t_degraded)
            self._t_degraded = None
            if self.metrics is not None:
                self.metrics.gauge("fleet_mttr_seconds").set(
                    round(self.mttr_s, 6))
        return True

    def _run_canary(self, rep, kind: str = "components") -> bool:
        """Oracle-checked warm-up probe: one throwaway query
        assigned DIRECTLY to the warming replica (like warm(), no
        routing — the probe must exercise THIS replica's engine).
        True iff the replica stayed up through the drain and the
        answer matches its NumPy oracle — live fleets at the
        canary's own admission epoch (check_live_answers), static
        fleets against the base graph.  The default kind is
        components: integer-labeled (bitwise comparison) and
        weight-agnostic, so one canary rule covers weighted and
        unweighted fleets."""
        with self._lock:
            qid = self._next_qid
            self._next_qid += 1
        req = Request(qid=qid, kind=kind, source=0,
                      t_enqueue=time.monotonic(),
                      epoch=serve_mod.admit_query(self.live, kind),
                      no_cache=True)
        with self._lock:
            self._qreq[qid] = req
            self._tenant_load[req.tenant] = \
                self._tenant_load.get(req.tenant, 0) + 1
            self._canaries.add(qid)
        # the canary is a PROBE, not traffic: suppress the runner's
        # SLO/latency metrics for its drain (slo_accounted over
        # loadgen traffic must not count warm-up probes), exactly
        # like _mark_lost suppresses a dead collector's wait metrics
        runner = rep.runner(kind)
        coll = rep.collector(kind)
        saved = (runner.metrics, coll.metrics)
        runner.metrics = coll.metrics = None
        self._assign(rep, req)
        resps: list[Response] = []
        try:
            while rep.state == "warming" and rep.pending(kind):
                resps += self._drain_inproc(rep, kind)
        finally:
            runner.metrics, coll.metrics = saved
        with self._lock:
            self._canaries.discard(qid)
        canary = next((r for r in resps if r.qid == qid), None)
        if rep.state != "warming" or canary is None:
            _emit("canary", replica=rep.name, qid=qid,
                  query_kind=kind, ok=False, reason="died")
            return False
        if self.live is not None:
            from lux_tpu import livegraph
            bad = livegraph.check_live_answers(self.live, [canary],
                                               self.weighted)
        else:
            bad = serve_mod._check_answers(self.g, [canary])
        ok = bad == 0
        _emit("canary", replica=rep.name, qid=qid, query_kind=kind,
              ok=ok,
              **({} if ok else {"reason": "oracle_mismatch"}))
        return ok

    def _failover(self, req: Request, from_rep,
                  t_detect: float | None = None) -> None:
        with self._lock:
            # the replayed-query guard: a query whose retirement
            # raced the loss detection must not run twice — checked
            # AND counted under the lock (a lock-free check here is
            # the stamp-then-admit window, lockcheck toctou-gate)
            if req.qid in self._retired:
                self.dup_dropped += 1
                if self.metrics is not None:
                    self.metrics.counter("fleet_dup_dropped_total",
                                         kind=req.kind).inc()
                return
        k = self._attempts.get(req.qid, 0)
        self._attempts[req.qid] = k + 1
        if k >= self.retry.retries:
            self._shed(req, SHED_RETRIES, raise_=False)
            return
        # each query's jittered delay is a NOT-BEFORE offset from the
        # detection instant, so a batch of failovers stalls the
        # dispatcher for at most the LARGEST single delay (not the
        # sum) — the survivors' queries must not be billed a serial
        # backoff chain, while each query still gets its own
        # attempt-indexed decorrelated delay
        d = self.retry.delay_s(k)
        waited = 0.0 if t_detect is None \
            else time.monotonic() - t_detect
        if d > waited:
            self.retry.sleep(d - waited)
        to = self._pick(req.kind)
        if to is None:
            self._shed(req, SHED_NO_CAPACITY, raise_=False)
            return
        self.failovers += 1
        if self.metrics is not None:
            self.metrics.counter("fleet_failovers_total",
                                 kind=req.kind).inc()
        _emit("failover", qid=req.qid, query_kind=req.kind,
              from_replica=from_rep.name, to_replica=to.name,
              attempt=k + 1, backoff_s=round(d, 4))
        self._assign(to, req)

    # -- subprocess answer path ----------------------------------------

    def _poll_remote(self) -> list[Response]:
        out: list[Response] = []
        for rep in self._replicas:
            if not rep.remote:
                continue
            try:
                names = sorted(os.listdir(rep.outdir))
            except OSError:
                continue
            for f in names:
                if not f.endswith(".json"):
                    continue
                jpath = os.path.join(rep.outdir, f)
                npath = jpath[:-5] + ".npy"
                try:
                    with open(jpath) as fh:
                        meta = json.load(fh)
                    answer = np.load(npath)
                except (OSError, ValueError, json.JSONDecodeError):
                    continue            # torn pair: retry next poll
                for p in (jpath, npath):
                    try:
                        os.remove(p)
                    except OSError:
                        pass
                qid = int(meta["qid"])
                req = rep.inflight.pop(qid, None) \
                    or self._qreq.get(qid)
                with self._lock:
                    # a late answer from a replica we already failed
                    # over: the exactly-once guard drops it — gate
                    # and counter share one acquisition (toctou)
                    dup = qid in self._retired or req is None
                    if dup:
                        self.dup_dropped += 1
                        if self.metrics is not None:
                            self.metrics.counter(
                                "fleet_dup_dropped_total",
                                kind=meta.get("kind", "?")).inc()
                if dup:
                    continue
                out.append(self._accept_remote(rep, req, meta,
                                               answer))
        return [r for r in out if r is not None]

    def _accept_remote(self, rep, req: Request, meta: dict,
                       answer) -> Response | None:
        now = time.monotonic()
        latency = max(0.0, now - req.t_enqueue)
        service = float(meta.get("service_s") or 0.0)
        resp = Response(
            qid=req.qid, kind=req.kind, source=req.source,
            answer=np.asarray(answer),
            iters=int(meta.get("iters", 0)),
            segments=int(meta.get("segments", 0)),
            latency_s=latency,
            wait_s=max(0.0, latency - service),
            converged=bool(meta.get("converged", True)))
        slo = {}
        slo_ms = self.slo_ms.get(req.kind)
        if slo_ms is not None:
            ok = resp.latency_s * 1e3 <= slo_ms
            slo = {"slo_ms": slo_ms, "slo_ok": ok}
        if self.metrics is not None:
            m = self.metrics
            m.histogram("serve_latency_seconds",
                        kind=req.kind).observe(resp.latency_s)
            m.counter("serve_retired_total", kind=req.kind).inc()
            if slo:
                m.counter("serve_slo_good_total" if slo["slo_ok"]
                          else "serve_slo_violation_total",
                          kind=req.kind).inc()
        if not self._accept(resp):
            return None
        _emit("query_done", qid=resp.qid, query_kind=resp.kind,
              col=-1, iters=resp.iters, segments=resp.segments,
              latency_s=round(resp.latency_s, 6),
              wait_s=round(resp.wait_s, 6),
              converged=resp.converged, replica=rep.name, **slo)
        return resp

    def _check_remote_health(self) -> None:
        for rep in self._replicas:
            if not rep.remote or rep.state != "up":
                continue
            rc = rep.proc.poll() if rep.proc is not None else None
            age = self.board.age(rep.name)
            if rc is not None and rc != 0:
                self._mark_lost(rep, heartbeat_mod.WorkerLostError(
                    [rep.index], -1, self.replica_deadline_s))
            elif age is not None and age > self.replica_deadline_s:
                self._mark_lost(rep, heartbeat_mod.WorkerLostError(
                    [rep.index], -1, self.replica_deadline_s))

    # -- the serve loop ------------------------------------------------

    def _pending_any(self) -> bool:
        if any(len(q) for q in self._queues.values()):
            return True
        for rep in self._healthy():
            if rep.pending_total():
                return True
        return False

    def run(self) -> list[Response]:
        """Serve until every admitted query retired (or shed): routes
        queued requests to the healthiest replicas, drains in-process
        replicas through continuous-batching refill, polls subprocess
        answers, and fails over on any replica death observed on the
        way.  Returns this call's responses in retirement order."""
        if self.live is not None and self.g is not self.live.base:
            # generation adoption is ENFORCED (serve.Server.run's
            # guard, fleet-wide): replica engines built over a stale
            # base would serve old-base + empty delta — a wrong
            # answer the torn-epoch audit cannot see
            raise RuntimeError(
                "live graph compacted to a new generation — call "
                "refresh_live() before serving")
        if self.cache is not None and self.live is not None:
            # invalidation on epoch advance (serve.Server.run's
            # sweep, fleet-wide: the cache is SHARED across replicas)
            self.cache.sweep({k: self._admission_epoch(k)
                              for k in KINDS})
        out: list[Response] = []
        while True:
            progressed = False
            got = self._poll_remote()
            if got:
                out += got
                progressed = True
            self._check_remote_health()
            if self.heal:
                self._heal()
            for kind in list(self._queues):
                q = self._queues[kind]
                if len(q):
                    if not self._healthy():
                        if self.heal and any(
                                r.state == "lost" and not r.remote
                                for r in self._replicas):
                            # a resurrection is scheduled: HOLD the
                            # queue instead of mass-shedding — the
                            # respawn either succeeds (queries route
                            # again) or the flap verdict quarantines
                            # the name (loop falls through to the
                            # shed below once nothing is lost)
                            continue
                        for req in q.collect(len(q)):
                            self._shed(req, SHED_NO_CAPACITY,
                                       raise_=False)
                        progressed = True
                        continue
                    reqs = q.collect(len(q))
                    leftover = []
                    for req in reqs:
                        if req.qid in self._retired:
                            continue
                        rep = self._pick(kind)
                        if rep is None:
                            leftover.append(req)
                            continue
                        self._assign(rep, req)
                        progressed = True
                    for req in leftover:
                        q.put(req)      # full remotes: wait, not shed
                for rep in list(self._replicas):
                    if (rep.state == "up" and not rep.remote
                            and rep.pending(kind)):
                        out += self._drain_inproc(rep, kind)
                        progressed = True
            if not self._pending_any():
                if not (self.heal and any(
                        r.state == "lost" and not r.remote
                        for r in self._replicas)):
                    break
                # heal-armed run() also restores the POOL before
                # returning: every lost in-process replica either
                # resurrects (canary-gated) or quarantines — so the
                # caller's next submit sees the healed capacity and
                # mttr_s is final, not still counting
            if not progressed:
                time.sleep(REMOTE_POLL_S)
        self._health_gauges()
        now = time.monotonic()
        if out and now - self._last_snapshot >= self.snapshot_every_s:
            self._last_snapshot = now
            self.emit_metrics_snapshot()
        return out

    # -- serve.Server duck-type surface --------------------------------

    def stop(self) -> None:
        """End ``serve()`` once nothing admitted is pending (any
        thread)."""
        with self._wake:
            self._stopping = True
            self._wake.notify_all()

    def serve(self, deliver) -> None:
        """serve.Server.serve's surface: each ``run()`` (a drain of
        everything admitted, failover and healing included) is
        handed to ``deliver`` as it ends; between two drains the
        caller blocks until a ``submit`` or ``stop()``, and once
        stopped it returns when nothing is pending."""
        try:
            while True:
                out = self.run()
                if out:
                    deliver(out)
                with self._wake:
                    self._wake.wait_for(
                        lambda: self._stopping or self._pending_any())
                    if not self._pending_any():
                        return
        finally:
            with self._wake:
                self._stopping = False

    def set_metrics(self, registry) -> None:
        self.metrics = registry
        for rep in self._replicas:
            if rep.remote:
                continue
            for coll in rep._collectors.values():
                coll.metrics = registry
            for runner in rep._runners.values():
                runner.metrics = registry

    def emit_metrics_snapshot(self, **extra):
        if self.metrics is None:
            return None
        return self.metrics.emit_snapshot(**extra)

    def close(self) -> None:
        for rep in self._replicas:
            if rep.remote:
                rep.stop()
        if self.journal is not None:
            self.journal.close()

    # -- whole-fleet crash recovery (round 24) --------------------------

    @classmethod
    def recover(cls, g, journal_path: str, /, *, resets=None,
                live=None, **kw) -> "FleetServer":
        """Restart a crashed fleet from its durable admission
        journal: replay the journal (truncating a torn tail in
        place, exactly like MutationLog.replay), seed the
        exactly-once retirement set from the persisted retire
        records, and RE-DISPATCH every admitted-unretired query so
        the next run() answers it at its ORIGINAL admission epoch
        (live fleets: ``livegraph.graph_at`` through the runners'
        epoch plumbing — bitwise-equal for integer apps).

        Recovery ordering is load-bearing (ARCHITECTURE.md
        "Self-healing fleet"): the caller replays the mutation WAL
        FIRST (``LiveGraph.recover``) and passes the recovered
        handle as ``live`` with ``g = live.base`` — journal
        re-dispatch needs the generation adopted before any epoch
        reproducibility verdict.

        Re-dispatch is unconditional (the queries already passed
        admission, durably) except for two typed, journal-retired
        sheds: ``reset_unavailable`` — a pagerank reset query whose
        vector is not in ``resets`` (a qid-keyed mapping; the
        journal persists only an 8-byte blake2b digest, and a
        mismatching vector is the same shed: recovery must never
        silently answer a DIFFERENT query than the one admitted) —
        and ``epoch_folded`` — a live fleet whose recovered base
        already folded past the record's admission epoch, so a
        bitwise answer at that epoch is unreachable.  Deadlines
        restart from re-dispatch (the crash consumed wall-clock the
        query never got).

        Remaining constructor keywords pass through ``**kw`` —
        ``journal_path`` must NOT be among them (the journal is
        resumed, not re-created; a second recover() on the same path
        replays the same open set minus what retired since)."""
        if "journal_path" in kw:
            raise ValueError(
                "recover() resumes the journal at journal_path; do "
                "not also pass journal_path= (that would O_EXCL-"
                "create over the evidence)")
        opens, retired, torn, jrnl = journal_mod.AdmissionJournal \
            .replay(journal_path, nv=g.nv)
        flt = cls(g, live=live, **kw)
        flt.journal = jrnl
        with flt._lock:
            flt._retired.update(retired)
            seen = [rec.qid for rec in opens] + list(retired)
            if seen:
                flt._next_qid = max(seen) + 1
        flt.journal_replayed = len(opens)
        _emit("journal_replay", path=journal_path,
              replayed=len(opens), retired=len(retired),
              torn_bytes=torn)
        if flt.metrics is not None:
            flt.metrics.counter("fleet_journal_replayed_total").inc(
                len(opens))
        resets = dict(resets or {})
        for rec in opens:
            reset = None
            if rec.digest is not None:
                reset = resets.get(rec.qid)
                if reset is not None:
                    reset = np.asarray(reset, np.float32)
                ok = (reset is not None
                      and journal_mod.reset_digest(reset)
                      == rec.digest)
                if not ok:
                    req = Request(qid=rec.qid, kind=rec.kind,
                                  source=None, reset=reset,
                                  t_enqueue=time.monotonic(),
                                  tenant=rec.tenant,
                                  priority=rec.priority,
                                  deadline_s=rec.deadline_s,
                                  epoch=None)
                    flt._journaled.add(rec.qid)
                    flt._shed(req, SHED_RESET_UNAVAILABLE,
                              raise_=False)
                    continue
            req = Request(qid=rec.qid, kind=rec.kind,
                          source=rec.source, reset=reset,
                          t_enqueue=time.monotonic(),
                          tenant=rec.tenant, priority=rec.priority,
                          deadline_s=rec.deadline_s, epoch=rec.epoch)
            if live is not None:
                if not serve_mod._epoch_reproducible(live, req):
                    flt._journaled.add(rec.qid)
                    flt._shed(req, SHED_EPOCH_FOLDED, raise_=False)
                    continue
                # take a fresh admission-ledger entry for the
                # re-dispatch (released at the exactly-once
                # retirement like any admit); the query still
                # ANSWERS at its original journaled epoch — the
                # entry only keeps the generation serveable
                live.admit(serve_mod._engine_family(rec.kind))
            with flt._lock:
                flt._journaled.add(rec.qid)
                flt._qreq[rec.qid] = req
                flt._tenant_load[req.tenant] = \
                    flt._tenant_load.get(req.tenant, 0) + 1
                flt._queue(rec.kind).put(req)
            _emit("query_enqueue", qid=rec.qid, query_kind=rec.kind,
                  source=req.source, tenant=req.tenant,
                  priority=req.priority,
                  queued=len(flt._queue(rec.kind)), recovered=True)
        return flt


# ---------------------------------------------------------------------
# subprocess replica worker

def _worker_env(ndev: int = 2) -> dict:
    """Worker env: a subprocess replica is a CPU replica, ALWAYS —
    ``JAX_PLATFORMS=cpu`` is pinned here, on the chip machine too.
    That is what the test harness and the chaos drills need, and it
    is the only thing that can work: a chip belongs to one process,
    and the parent that spawns workers already holds it.  Replicas
    that must run on the chip are in-process replicas.  The virtual
    device count scales with the worker's num_parts and other
    caller-set XLA flags are PRESERVED — overwriting them would cap
    a 4-part worker at 2 devices and misdiagnose the crash as a
    spawn-capability failure."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    pp = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([repo] + pp)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(
                 "--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count="
                 f"{max(2, int(ndev))}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def _graph_from_spec(spec: dict):
    """Rebuild the (deterministic, seeded) graph a subprocess replica
    serves — it must match the parent's bit-for-bit or the answers
    cannot be oracle-equal."""
    from lux_tpu.graph import Graph

    kind = spec.get("kind", "uniform")
    if kind == "uniform":
        from lux_tpu.convert import uniform_random_edges
        src, dst = uniform_random_edges(int(spec["nv"]),
                                        int(spec["ne"]),
                                        seed=int(spec.get("seed", 0)))
        return Graph.from_edges(src, dst, int(spec["nv"]))
    if kind == "rmat":
        from lux_tpu.convert import rmat_graph
        return rmat_graph(scale=int(spec["scale"]),
                          edge_factor=int(spec["ef"]),
                          seed=int(spec.get("seed", 0)))
    raise ValueError(f"unknown graph spec kind {spec!r}")


def _worker_main(spec_path: str) -> int:
    from lux_tpu import serve

    with open(spec_path) as f:
        spec = json.load(f)
    name = spec["name"]
    board = heartbeat_mod.ReplicaBoard(spec["board"])
    plan = None
    if spec.get("kill_boundary") is not None:
        plan = faults_mod.ReplicaKillPlan(
            {name: int(spec["kill_boundary"])}, hard_kill=True)
    state = {"boundary": 0}

    def on_boundary(runner):
        state["boundary"] += 1
        board.beat(name, status="up", boundary=state["boundary"])
        if plan is not None:
            plan.fire(name)

    g = _graph_from_spec(spec["graph"])
    srv = serve.Server(g, batch=int(spec["batch"]),
                       num_parts=int(spec["num_parts"]),
                       seg_iters=int(spec["seg_iters"]),
                       tol=float(spec.get("tol", 1e-8)),
                       weighted=bool(spec.get("weighted", False)),
                       metrics=False, on_boundary=on_boundary,
                       replica=name)
    inbox = os.path.join(spec["dir"], f"inbox_{name}")
    outdir = os.path.join(spec["dir"], f"out_{name}")
    stop = os.path.join(spec["dir"], "stop")
    qmap: dict[int, int] = {}
    board.beat(name, status="up", boundary=0)
    while not os.path.exists(stop):
        board.beat(name, status="up", boundary=state["boundary"])
        for f in sorted(os.listdir(inbox)):
            if not f.endswith(".json"):
                continue
            p = os.path.join(inbox, f)
            try:
                with open(p) as fh:
                    doc = json.load(fh)
            except (OSError, json.JSONDecodeError):
                continue
            reset = None
            if doc.get("reset"):
                rp = p[:-5] + ".reset.npy"
                try:
                    reset = np.load(rp)
                except (OSError, ValueError):
                    continue    # torn pair: json kept, retry next loop
            os.remove(p)
            if doc.get("reset"):
                try:
                    os.remove(rp)
                except OSError:
                    pass
            wq = srv.submit(doc["kind"], source=doc.get("source"),
                            reset=reset)
            qmap[wq] = int(doc["qid"])
        for r in srv.run():
            fq = qmap.pop(r.qid)
            base = os.path.join(outdir, f"q{fq:08d}")
            fd, tmp = tempfile.mkstemp(dir=spec["dir"],
                                       suffix=".npy.tmp")
            with os.fdopen(fd, "wb") as fh:
                # lockcheck: allow(durable-before-visible) same-host
                # answer spool, not a durability record: a lost
                # answer re-runs from the journal; fsync per answer
                # would serialize the drain on disk latency
                np.save(fh, r.answer)
            os.replace(tmp, base + ".npy")
            meta = {"qid": fq, "kind": r.kind, "source": r.source,
                    "iters": r.iters, "segments": r.segments,
                    "converged": r.converged,
                    "service_s": round(r.latency_s, 6)}
            fd, tmp = tempfile.mkstemp(dir=spec["dir"],
                                       suffix=".json.tmp")
            with os.fdopen(fd, "w") as fh:
                json.dump(meta, fh)
            # json LAST: its presence marks a complete answer pair
            os.replace(tmp, base + ".json")
        time.sleep(0.02)
    return 0


# ---------------------------------------------------------------------
# smoke: python -m lux_tpu.fleet

def main(argv=None) -> int:
    import argparse

    from lux_tpu import runtime
    runtime.use_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "-worker":
        return _worker_main(argv[1])

    ap = argparse.ArgumentParser(
        prog="python -m lux_tpu.fleet",
        description="serving-fleet chaos smoke: an oversubscribed "
                    "mixed-kind load across N replicas with one "
                    "replica killed mid-drain; every admitted answer "
                    "is oracle-checked, shed queries carry typed "
                    "rejections, and no qid retires twice")
    ap.add_argument("-scale", type=int, default=8)
    ap.add_argument("-ef", type=int, default=8)
    ap.add_argument("-batch", type=int, default=2)
    ap.add_argument("-replicas", type=int, default=2)
    ap.add_argument("-np", type=int, default=2, dest="num_parts")
    ap.add_argument("-queries", type=int, default=0,
                    help="total mixed queries (default 4B)")
    ap.add_argument("-kill-boundary", type=int, default=1,
                    dest="kill_boundary",
                    help="segment boundary of the last replica at "
                         "which the kill plan fires (-1 disables)")
    ap.add_argument("-seed", type=int, default=0)
    ap.add_argument("-events", default=None, metavar="FILE")
    args = ap.parse_args(argv)

    from lux_tpu import telemetry
    from lux_tpu.serve import _check_answers, _smoke_graph

    g = _smoke_graph(args.scale, args.ef, args.seed)
    rng = np.random.default_rng(args.seed + 1)
    n = args.queries or 4 * args.batch
    kinds = list(KINDS)
    ev = telemetry.EventLog(args.events) if args.events else \
        telemetry.EventLog()
    with telemetry.use(events=ev):
        ev.emit("run_start", schema=telemetry.SCHEMA, app="fleet",
                file=f"<rmat{args.scale}>", np=args.num_parts)
        flt = FleetServer(g, replicas=args.replicas,
                          batch=args.batch,
                          num_parts=args.num_parts,
                          retry=resilience.RetryPolicy(
                              retries=3, backoff_s=0.01,
                              max_backoff_s=0.05, jitter_seed=0))
        if args.kill_boundary >= 0 and args.replicas > 1:
            # arm the replica routing WILL pick (routing_target):
            # a fixed index is a coin flip on beat timing, and the
            # losing side is a kill that never fires (round 22)
            flt.set_fault(faults_mod.ReplicaKillPlan(
                {flt.routing_target(kinds[0]): args.kill_boundary}))
        for i in range(n):
            flt.submit(kinds[i % len(kinds)],
                       source=int(rng.integers(0, g.nv)))
        t0 = time.perf_counter()
        responses = flt.run()
        ev.emit("run_done",
                seconds=round(time.perf_counter() - t0, 6),
                iters=sum(r.iters for r in responses))
    ev.close()
    qids = [r.qid for r in responses]
    shed_qids = {e.qid for e in flt.shed_records}
    print(f"# served {len(responses)}/{n} queries across "
          f"{args.replicas} replica(s); failovers={flt.failovers} "
          f"shed={len(flt.shed_records)} dup_dropped="
          f"{flt.dup_dropped}")
    if len(set(qids)) != len(qids):
        print("error: duplicate retirement")
        return 1
    if set(qids) | shed_qids != set(range(n)) or \
            set(qids) & shed_qids:
        print("error: served + shed do not partition the admitted "
              "queries")
        return 1
    if args.kill_boundary >= 0 and args.replicas > 1 \
            and not flt.failovers and not flt.fault.fired:
        print("error: the kill plan never fired")
        return 1
    bad = _check_answers(g, responses)
    if bad:
        print(f"error: {bad} answer(s) mismatched their oracle")
        return 1
    print("# all served answers match their NumPy oracles")
    return 0


if __name__ == "__main__":
    sys.exit(main())
