"""Distributed heartbeat supervision for multi-process runs.

The reference inherits liveness from the Legion/Realm runtime (a dead
GASNet node takes the whole job down, reference README.md:33-38);
lux_tpu's substrate is jax.distributed, where a lost worker process
HANGS the survivors in their next collective — there is no runtime
above the program to notice.  This module is that runtime layer, kept
deliberately boring: a shared directory of per-worker heartbeat files
(a pod's shared filesystem, or any tmp dir on the single-machine test
harness), synchronized at SEGMENT boundaries — the places the
supervised drivers (lux_tpu/resilience.py) already stop at, and the
granularity segmented.DurationBudget already bounds, which is what
makes a wall-clock deadline a sound death detector: a live peer can
never legitimately be more than one segment (< the deadline) behind.

Protocol (per supervised run):

- ``sync(boundary)`` at every segment boundary: write own beat (atomic
  rename), then poll the peers until every one of them has reached
  ``boundary`` (or finished).  A peer whose newest beat is older than
  ``deadline_s`` is DEAD: sync raises a typed
  :class:`WorkerLostError` — classified TOPOLOGY by
  resilience.classify — BEFORE this worker enters the next segment's
  collective, which is the difference between a diagnosed degraded
  continuation and an indefinite hang.  A peer that is merely behind
  (but beating) is a STRAGGLER: one ``straggler`` telemetry event per
  boundary, then keep waiting.
- coordinated shrink: jax.distributed cannot drop a member
  in-process, so survivors agree on the new topology through the
  board (``propose_shrink``: the LOWEST surviving pid writes the
  agreed-topology file, everyone reads the same file — deterministic
  agreement with no extra consensus machinery) and then relaunch
  degraded; the relaunched run resumes from the shared checkpoint,
  whose global ``[P, vpad, ...]`` host view re-places onto any mesh
  whose size divides num_parts (checkpoint.py, resilience.py).

Clock and sleep are injectable so the detection logic is unit-tested
with a fake clock (tests/test_elastic.py); the 2-subprocess harness
(tests/test_worker_kill.py) exercises the real thing end-to-end.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
from typing import Callable


class WorkerLostError(RuntimeError):
    """One or more peer workers missed their heartbeat deadline.
    Carries ``lost`` (worker process ids) and ``boundary``;
    resilience.classify treats it as TOPOLOGY."""

    def __init__(self, lost, boundary: int, deadline_s: float):
        lost = tuple(int(p) for p in lost)
        super().__init__(
            f"worker(s) {list(lost)} missed the heartbeat deadline "
            f"({deadline_s:g} s) at segment boundary {boundary} — "
            f"presumed dead; survivors must agree on a shrunken "
            f"topology and re-place")
        self.lost = lost
        self.boundary = int(boundary)


@dataclasses.dataclass
class ReplicaBoard:
    """Name-keyed replica heartbeat board for the serving fleet
    (lux_tpu/fleet.py, round 18) — the same shared-dir,
    atomic-rename discipline as :class:`Heartbeat`, but keyed by
    replica NAME with free-form status fields and NO boundary
    barrier: the fleet dispatcher reads beat AGES (per-replica health
    gauges, and the only death detector a hard-killed subprocess
    replica leaves behind) instead of syncing at boundaries.  A
    replica whose newest beat is older than ``deadline_s`` is
    presumed dead; the dispatcher then fails its in-flight queries
    over to the survivors."""

    path: str
    deadline_s: float = 3.0
    now: Callable[[], float] = time.time

    def __post_init__(self):
        os.makedirs(self.path, exist_ok=True)

    def _file(self, name: str) -> str:
        return os.path.join(self.path, f"rb_{name}.json")

    def beat(self, name: str, **fields) -> None:
        """Record a replica's sign of life (atomic rename: a reader
        never sees a torn beat).  Extra fields (boundary, served,
        status) ride along for the board's diagnostics."""
        fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".rb.tmp")
        with os.fdopen(fd, "w") as f:
            json.dump({"replica": str(name), "t": self.now(),
                       **fields}, f)
        os.replace(tmp, self._file(name))

    def read(self, name: str) -> dict | None:
        try:
            with open(self._file(name)) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    def replicas(self) -> list[str]:
        """Names with a beat on the board, sorted."""
        out = []
        for f in os.listdir(self.path):
            if f.startswith("rb_") and f.endswith(".json"):
                out.append(f[3:-5])
        return sorted(out)

    def age(self, name: str) -> float | None:
        """Seconds since the replica's newest beat (None before its
        first one — the caller owns the launch grace)."""
        r = self.read(name)
        if r is None or not isinstance(r.get("t"), (int, float)):
            return None
        return max(0.0, self.now() - r["t"])

    def alive(self, name: str) -> bool:
        a = self.age(name)
        return a is not None and a <= self.deadline_s


@dataclasses.dataclass
class Heartbeat:
    """One worker's view of the shared heartbeat board.

    path        shared directory (pod filesystem / test tmp dir)
    pid         this worker's process index (0..nproc-1)
    nproc       total workers at launch
    deadline_s  staleness after which a peer is declared dead; the
                upper bound on one segment's legitimate silence.  The
                55 s default sits above DurationBudget's 45 s segment
                budget (both date from a ~55 s execution wall seen on
                the earlier installation, unverified on this machine)
    """

    path: str
    pid: int
    nproc: int
    deadline_s: float = 55.0
    poll_s: float = 0.05
    # a live-but-behind peer triggers ONE straggler event per
    # boundary once it lags this many seconds (default: half the
    # death deadline)
    straggler_s: float | None = None
    now: Callable[[], float] = time.time
    sleep: Callable[[float], None] = time.sleep
    _t_start: float = dataclasses.field(default=0.0, init=False)

    def __post_init__(self):
        os.makedirs(self.path, exist_ok=True)
        if self.straggler_s is None:
            self.straggler_s = self.deadline_s / 2
        self._t_start = self.now()

    # -- beat files ----------------------------------------------------

    def _file(self, pid: int) -> str:
        return os.path.join(self.path, f"hb_{pid}.json")

    def beat(self, boundary: int, done: bool = False) -> None:
        """Record that this worker reached ``boundary`` (atomic
        rename: a peer never reads a torn beat)."""
        fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".hb.tmp")
        with os.fdopen(fd, "w") as f:
            json.dump({"pid": self.pid, "boundary": int(boundary),
                       "t": self.now(), "done": bool(done)}, f)
        os.replace(tmp, self._file(self.pid))

    def read(self, pid: int) -> dict | None:
        """A peer's newest beat, or None before its first one."""
        try:
            with open(self._file(pid)) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    # -- boundary synchronization --------------------------------------

    def sync(self, boundary: int) -> None:
        """Beat, then wait for every peer to reach ``boundary`` (or
        finish).  Raises WorkerLostError when a peer's newest beat
        goes stale past ``deadline_s`` — checked HERE, before the next
        segment's collective, so a dead worker costs one deadline, not
        a hang."""
        from lux_tpu import telemetry

        t_sync = self.now()
        self.beat(boundary)
        warned = False
        while True:
            now = self.now()
            late = {}
            for p in range(self.nproc):
                if p == self.pid:
                    continue
                r = self.read(p)
                if r is not None and (r.get("done")
                                      or r.get("boundary", -1)
                                      >= boundary):
                    continue
                # age of the peer's newest sign of life (its launch is
                # its implicit first beat: a worker that never wrote
                # anything gets the same deadline from our start time)
                last = r["t"] if r is not None else self._t_start
                late[p] = now - last
            if not late:
                # one instant marker per reached boundary (round 13:
                # the tracing exporter renders these on the timeline,
                # so cross-process sync points are visible)
                telemetry.current().emit(
                    "heartbeat", boundary=int(boundary),
                    nproc=int(self.nproc),
                    waited_s=round(now - t_sync, 3))
                return
            dead = sorted(p for p, age in late.items()
                          if age > self.deadline_s)
            if dead:
                raise WorkerLostError(dead, boundary, self.deadline_s)
            if not warned and max(late.values()) > self.straggler_s:
                telemetry.current().emit(
                    "straggler", boundary=int(boundary),
                    peers=sorted(late),
                    behind_s=round(max(late.values()), 3))
                warned = True
            self.sleep(self.poll_s)

    def finish(self) -> None:
        """Mark this worker done: peers still syncing must not wait
        for boundaries a finished worker will never reach."""
        self.beat(boundary=-1, done=True)

    def survivors(self) -> list[int]:
        """Workers currently presumed alive (fresh or finished
        beats), always including self."""
        now = self.now()
        out = []
        for p in range(self.nproc):
            if p == self.pid:
                out.append(p)
                continue
            r = self.read(p)
            if r is None:
                if now - self._t_start <= self.deadline_s:
                    out.append(p)   # still within its launch grace
                continue
            if r.get("done") or now - r["t"] <= self.deadline_s:
                out.append(p)
        return out

    # -- coordinated shrink --------------------------------------------

    def _topo_file(self) -> str:
        return os.path.join(self.path, "topology.json")

    def propose_shrink(self, survivors, generation: int = 1) -> dict:
        """Agree on the degraded topology: the LOWEST surviving pid
        writes the agreed-topology file (atomic rename), every
        survivor polls until a record with this ``generation``
        appears, and all return the SAME dict — deterministic
        agreement, no consensus machinery.  The relaunch then runs
        ``len(survivors)`` processes (or one, resuming single-process)
        from the shared checkpoint."""
        from lux_tpu import telemetry

        survivors = sorted(int(p) for p in survivors)
        if self.pid == survivors[0]:
            fd, tmp = tempfile.mkstemp(dir=self.path,
                                       suffix=".topo.tmp")
            with os.fdopen(fd, "w") as f:
                json.dump({"generation": int(generation),
                           "survivors": survivors,
                           "nproc": len(survivors),
                           "t": self.now()}, f)
            os.replace(tmp, self._topo_file())
        t0 = self.now()
        while True:
            try:
                with open(self._topo_file()) as f:
                    topo = json.load(f)
            except (OSError, json.JSONDecodeError):
                topo = None
            if topo is not None and topo.get("generation") == generation:
                telemetry.current().emit(
                    "mesh_shrink", protocol="heartbeat",
                    from_nproc=int(self.nproc),
                    to_nproc=len(topo["survivors"]),
                    survivors=topo["survivors"],
                    generation=int(generation))
                return topo
            if self.now() - t0 > self.deadline_s:
                raise WorkerLostError(
                    [p for p in survivors if p != self.pid], -1,
                    self.deadline_s)
            self.sleep(self.poll_s)
