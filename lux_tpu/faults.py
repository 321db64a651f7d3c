"""Deterministic fault injection for the resilience layer.

Real failure modes — transient TPU worker death (on the earlier
installation a pagerank-mp sample collapsed 10x and one whole config
crashed during round 5), slow segments, and state corruption — do not
reproduce on demand, so the recovery paths that handle them would
otherwise ship untested.  This module injects synthetic versions of
those failures at SEGMENT BOUNDARIES on a deterministic schedule
(explicit, or derived from a seed), so the whole
classify/retry/resume path (lux_tpu/resilience.py) is exercised by
the CPU test suite.

Round 9 adds the data-plane corruption classes: type-appropriate
state corruption (NaN for float states, the program's
identity/sentinel for integer labels — all four apps are
corruption-testable) and on-disk checkpoint corruption (a zip-valid
bit flip only the per-leaf CRC can catch, and a truncation), each
followed by an injected crash so checkpoint.py's generation-fallback
resume path is exercised end-to-end by the CPU suite.

Round 11 adds the TOPOLOGY fault classes: DEVICE_LOSS (named mesh
devices become unavailable) and WORKER_KILL (a whole worker process
dies — simulated in-process via a typed raise, or REAL via
``hard_kill`` + os._exit for the multi-process heartbeat harness), so
the elastic degraded-mesh recovery path (resilience.supervised_run's
``elastic=``, lux_tpu/heartbeat.py) is deterministically exercised on
the 8-virtual-device CPU mesh and in the 2-subprocess harness.

Faults key on a global boundary COUNTER, not on iteration numbers:
after a crash-and-resume the counter has advanced past the fired
fault, so a schedule never re-fires and every supervised run
terminates.  The counter also persists across the supervisor's
retries, which is what makes a seeded schedule reproducible
end-to-end.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

CRASH = "crash"     # raise InjectedWorkerCrash (retryable)
DELAY = "delay"     # sleep delay_s (exercises slow-segment paths)
NAN = "nan"         # corrupt the first float leaf (NaN) — or, for
#                     integer-labeled programs, poke the program's
#                     identity/sentinel value (corrupt_state)
CKPT_BITFLIP = "ckpt_bitflip"    # flip a payload bit in the newest
#                                  checkpoint generation, then crash
CKPT_TRUNCATE = "ckpt_truncate"  # truncate the newest checkpoint
#                                  generation, then crash
DEVICE_LOSS = "device_loss"      # raise InjectedDeviceLoss naming the
#                                  mesh devices that "died" (TOPOLOGY
#                                  class: the elastic supervisor
#                                  shrinks the mesh over the survivors)
WORKER_KILL = "worker_kill"      # raise InjectedWorkerKill (a whole
#                                  worker process gone, its devices
#                                  with it) — or, with hard_kill=True,
#                                  REALLY kill this process (the
#                                  2-subprocess harness's genuine
#                                  death, detected by the peers'
#                                  heartbeat deadline)

# round 20 (live graphs, lux_tpu/livegraph.py): mutation-scoped
# actions for the crash-consistent mutation log — each exercises one
# leg of the WAL/compaction recovery contract
MUT_CRASH = "mut_crash"          # crash BEFORE the WAL append lands:
#                                  the mutation was never durable, so
#                                  replay must not show it
WAL_TORN = "wal_torn"            # crash MID-append: only a PREFIX of
#                                  the record's bytes reach disk — the
#                                  torn tail replay must detect (CRC
#                                  chain), truncate, and never replay
COMPACT_CRASH = "compact_crash"  # crash between COMPACT_START and the
#                                  atomic generation swap: recovery
#                                  resumes from the SURVIVING
#                                  generation (base + published delta)

# round 21 (mutation algebra): op-ASSERTING crash legs.  Each behaves
# like MUT_CRASH (die before the WAL record lands) but additionally
# validates that the mutation firing at that index really is the
# scheduled op — a drill that says "kill the 3rd mutation, which is a
# deletion" fails typed if the stream reordered, instead of silently
# testing the wrong op's recovery leg.
MUT_DELETE = "mut_delete"        # crash before a DELETE record lands
MUT_REWEIGHT = "mut_reweight"    # crash before a REWEIGHT record lands
RESEED_CRASH = "reseed_crash"    # crash MID-RE-SEED: between the
#                                  affected-cone computation and the
#                                  re-converge — recovery must come up
#                                  with the anti-monotone ops still
#                                  pending (admission stays capped; no
#                                  answer was produced from the
#                                  half-re-seeded state)

# round 24 (self-healing fleet, lux_tpu/fleet.py + journal.py): the
# whole-fleet and flapping-replica classes the resurrection /
# recovery paths must survive
FLEET_CRASH = "fleet_crash"      # the ENTIRE fleet dies at the named
#                                  replica's Nth boundary, coordinator
#                                  included (in-process: a typed
#                                  InjectedFleetCrash that propagates
#                                  out of FleetServer.run; hard_kill:
#                                  os._exit) — recovery restarts from
#                                  the admission journal + mutation WAL
REPLICA_FLAP = "replica_flap"    # kill the SAME replica at every
#                                  boundary from the scheduled index on
#                                  (re-fires, unlike every other plan
#                                  action): each resurrection dies
#                                  again until flap detection trips the
#                                  typed quarantine (fleet.py)


# exit code of a hard_kill WORKER_KILL: distinguishable from a crash
# (nonzero, outside the shell/signal ranges) in the harness's asserts
HARD_KILL_CODE = 113


class InjectedWorkerCrash(RuntimeError):
    """Synthetic analogue of a transient worker death;
    resilience.classify treats it as retryable."""


class InjectedDeviceLoss(RuntimeError):
    """Synthetic topology fault: named devices of the engine's mesh
    became unavailable.  Carries ``lost_devices`` (device ids);
    resilience.classify treats it as TOPOLOGY — retrying on the same
    mesh cannot help, but re-placement onto the survivors can."""

    def __init__(self, msg: str, lost_devices=()):
        super().__init__(msg)
        self.lost_devices = tuple(int(d) for d in lost_devices)


class InjectedWorkerKill(RuntimeError):
    """Synthetic topology fault: a whole worker process died, taking
    its devices with it (the message mimics the coordination-service
    heartbeat signature real deaths surface as).  Carries
    ``lost_devices`` like InjectedDeviceLoss; classified TOPOLOGY."""

    def __init__(self, msg: str, lost_devices=()):
        super().__init__(msg)
        self.lost_devices = tuple(int(d) for d in lost_devices)


class InjectedFleetCrash(BaseException):
    """Synthetic whole-fleet death (round 24): the coordinator AND
    every replica die at once — nothing survives to fail over to, so
    this is NOT retryable within the process and deliberately
    subclasses BaseException: no except-Exception recovery path in
    the dispatcher may swallow it (a real power loss is not
    swallowed either).  The only legitimate continuation is
    ``FleetServer.recover`` over the durable state (admission
    journal + mutation WAL + checkpoints).  Carries ``replica`` —
    the replica whose boundary the crash fired at."""

    def __init__(self, msg: str, replica: str = ""):
        super().__init__(msg)
        self.replica = replica


@dataclasses.dataclass
class FaultPlan:
    """A deterministic boundary-counter -> action schedule.

    ``fire(state)`` is called by the supervisor at every segment
    boundary.  It returns None (no state change), or a HOST-side
    corrupted copy of the state pytree (the caller re-places it on
    device); a scheduled CRASH raises InjectedWorkerCrash before the
    segment's checkpoint save; a scheduled DELAY sleeps.  ``fired``
    records what actually happened, for assertions.
    """

    schedule: dict
    delay_s: float = 0.0
    nan_count: int = 1
    # sentinel poked into integer-labeled states by a NAN action (the
    # supervisor passes the program identity per-call; this is the
    # standalone-use default)
    int_value: int | None = None
    # devices a DEVICE_LOSS/WORKER_KILL takes: an explicit tuple of
    # device ids, or an int N = the LAST N devices of the engine's
    # mesh (the supervisor passes the mesh's device ids per-call, so
    # the loss is deterministic for a given mesh)
    lose: int | tuple = 1
    # WORKER_KILL with hard_kill=True calls os._exit(HARD_KILL_CODE)
    # instead of raising — the 2-subprocess harness's REAL process
    # death, which peers can only see through the heartbeat deadline
    # (lux_tpu/heartbeat.py)
    hard_kill: bool = False
    boundaries: int = dataclasses.field(default=0, init=False)
    fired: list = dataclasses.field(default_factory=list, init=False)
    # newest checkpoint generation the CKPT_* actions corrupt; bound
    # by the resilience supervisor (bind_checkpoint)
    ckpt_path: str | None = dataclasses.field(default=None, init=False)

    @classmethod
    def seeded(cls, seed: int, n: int = 16, p_crash: float = 0.25,
               p_delay: float = 0.0, p_nan: float = 0.0,
               delay_s: float = 0.0, nan_count: int = 1) -> "FaultPlan":
        """Derive a schedule over the first ``n`` boundaries from a
        seed — same seed, same faults, every run."""
        rng = np.random.default_rng(seed)
        schedule = {}
        for i in range(n):
            r = float(rng.random())
            if r < p_crash:
                schedule[i] = CRASH
            elif r < p_crash + p_delay:
                schedule[i] = DELAY
            elif r < p_crash + p_delay + p_nan:
                schedule[i] = NAN
        return cls(schedule=schedule, delay_s=delay_s,
                   nan_count=nan_count)

    def bind_checkpoint(self, path: str) -> None:
        """Point the CKPT_* actions at a run's checkpoint file (the
        resilience supervisor calls this with its checkpoint path)."""
        self.ckpt_path = path

    def _lost_ids(self, device_ids) -> tuple:
        """The device ids a DEVICE_LOSS/WORKER_KILL takes, resolved
        against the caller's mesh device ids (``lose`` int = the last
        N of them; explicit tuples pass through)."""
        if isinstance(self.lose, (tuple, list)):
            return tuple(int(d) for d in self.lose)
        ids = tuple(int(d) for d in (device_ids or ()))
        n = max(0, int(self.lose))
        # max(0, ...): lose >= the whole mesh takes EVERY device (a
        # negative slice start would wrap and under-report the loss)
        return ids[max(0, len(ids) - n):] if n and ids else ()

    def fire(self, state, int_value: int | None = None,
             device_ids=None):
        import os

        i = self.boundaries
        self.boundaries += 1
        action = self.schedule.get(i)
        if action is None:
            return None
        self.fired.append((i, action))
        if action == CRASH:
            raise InjectedWorkerCrash(
                f"injected worker crash at segment boundary {i}")
        if action == DELAY:
            time.sleep(self.delay_s)
            return None
        if action == NAN:
            return corrupt_state(
                state, self.nan_count,
                int_value if int_value is not None else self.int_value)
        if action == DEVICE_LOSS:
            lost = self._lost_ids(device_ids)
            raise InjectedDeviceLoss(
                f"injected device loss at segment boundary {i}: "
                f"devices {list(lost)} unavailable", lost)
        if action == WORKER_KILL:
            lost = self._lost_ids(device_ids)
            if self.hard_kill:
                # a REAL death: no exception, no cleanup, no goodbye —
                # exactly what a preempted/killed worker looks like to
                # its peers (heartbeat deadline, lux_tpu/heartbeat.py)
                os._exit(HARD_KILL_CODE)
            raise InjectedWorkerKill(
                f"injected worker death at segment boundary {i}: "
                f"coordination service heartbeat to the worker "
                f"holding devices {list(lost)} timed out", lost)
        if action in (CKPT_BITFLIP, CKPT_TRUNCATE):
            # the torn-write scenario: the on-disk newest generation
            # is damaged AND the worker dies — the retry's resume must
            # detect the corruption (CRC) and fall back one generation
            if self.ckpt_path and os.path.exists(self.ckpt_path):
                if action == CKPT_BITFLIP:
                    bitflip_checkpoint(self.ckpt_path)
                else:
                    truncate_checkpoint(self.ckpt_path)
            raise InjectedWorkerCrash(
                f"injected worker crash after {action} at segment "
                f"boundary {i}")
        raise ValueError(f"unknown fault action {action!r}")


@dataclasses.dataclass
class ReplicaKillPlan:
    """Replica-scoped kill schedule for the serving fleet
    (lux_tpu/fleet.py, round 18): ``schedule`` maps a replica NAME to
    the replica's segment-boundary index at which it dies.  The
    fleet's per-replica boundary hook calls ``fire(name)`` at every
    segment boundary of every runner the replica owns (one shared
    counter per replica, all query kinds), so the kill lands
    MID-DRAIN with queries resident in the runner's columns — exactly
    the in-flight state the failover path must re-dispatch.

    ``action`` is WORKER_KILL (default: InjectedWorkerKill, or with
    ``hard_kill=True`` a REAL ``os._exit(HARD_KILL_CODE)`` for
    subprocess replica workers — the genuine death only the replica
    board's beat staleness can detect) or DEVICE_LOSS
    (InjectedDeviceLoss).  A fired entry never re-fires (the
    boundary counter advances past it), so a drained fleet always
    terminates; ``fired`` records what happened, for assertions.

    Round 24 adds the self-healing drill actions: FLEET_CRASH (the
    whole fleet dies at the named replica's boundary — the typed
    InjectedFleetCrash propagates out of FleetServer.run, or
    ``hard_kill`` really exits; recovery is FleetServer.recover over
    the journals) and REPLICA_FLAP, the ONE re-firing action: the
    named replica dies at EVERY boundary from the scheduled index on
    (capped by ``flap_count`` firings, None = unbounded), so each
    resurrection dies again until the fleet's flap detection trips
    the typed quarantine — which stops the replica's boundaries and
    therefore terminates the plan.  Arm every schedule via
    ``FleetServer.routing_target`` per the round-22 rule (routing is
    a positive-feedback loop; a fixed replica index is a coin
    flip)."""

    schedule: dict
    action: str = WORKER_KILL
    hard_kill: bool = False
    # REPLICA_FLAP only: stop re-firing after this many kills (None =
    # keep killing until quarantine stops the boundaries)
    flap_count: int | None = None
    boundaries: dict = dataclasses.field(default_factory=dict,
                                         init=False)
    fired: list = dataclasses.field(default_factory=list, init=False)

    def __post_init__(self):
        # validate at CONSTRUCTION: a typo'd action discovered at
        # the scheduled boundary would crash the run mid-measurement
        # instead of failing the plan before anything was spent
        if self.action not in (WORKER_KILL, DEVICE_LOSS, FLEET_CRASH,
                               REPLICA_FLAP):
            raise ValueError(
                f"ReplicaKillPlan action must be WORKER_KILL, "
                f"DEVICE_LOSS, FLEET_CRASH, or REPLICA_FLAP, got "
                f"{self.action!r}")

    def fire(self, replica: str) -> None:
        import os

        i = int(self.boundaries.get(replica, 0))
        self.boundaries[replica] = i + 1
        due = self.schedule.get(replica)
        if due is None:
            return
        if self.action == REPLICA_FLAP:
            # the one re-firing action: every boundary AT/PAST the
            # scheduled index kills again, so a resurrected replica
            # dies at its first post-canary boundary — exactly the
            # flapping pattern quarantine detection exists for
            if i < int(due):
                return
            shots = sum(1 for r, _, _ in self.fired if r == replica)
            if self.flap_count is not None and shots >= self.flap_count:
                return
            self.fired.append((replica, i, self.action))
            raise InjectedWorkerKill(
                f"injected replica flap on serving replica "
                f"{replica!r} at its boundary {i} (death "
                f"{shots + 1}): coordination service heartbeat to "
                f"the replica timed out", ())
        if i != int(due):
            return
        self.fired.append((replica, i, self.action))
        if self.action == FLEET_CRASH:
            if self.hard_kill:
                # the REAL whole-fleet death: coordinator exits with
                # every replica's state — only the fsync'd journals
                # survive
                os._exit(HARD_KILL_CODE)
            raise InjectedFleetCrash(
                f"injected fleet crash at replica {replica!r} "
                f"boundary {i}: coordinator and all replicas died — "
                f"recover from the admission journal + mutation WAL",
                replica)
        if self.action == DEVICE_LOSS:
            raise InjectedDeviceLoss(
                f"injected device loss on serving replica "
                f"{replica!r} at its boundary {i}: devices "
                f"unavailable", ())
        if self.hard_kill:
            # a REAL death, mid-drain: no exception, no cleanup —
            # the parent fleet can only see it through the replica
            # board's beat going stale (lux_tpu/heartbeat.py)
            os._exit(HARD_KILL_CODE)
        raise InjectedWorkerKill(
            f"injected worker death on serving replica {replica!r} "
            f"at its boundary {i}: coordination service heartbeat "
            f"to the replica timed out", ())


@dataclasses.dataclass
class MutationFaultPlan:
    """Mutation-scoped fault schedule for the live-graph subsystem
    (lux_tpu/livegraph.py, round 20).  Two independent deterministic
    counters:

    - ``schedule`` maps a MUTATION-append index to MUT_CRASH (crash
      before the WAL record lands — the mutation must be absent from
      any replay) or WAL_TORN (a torn mid-append write: only a prefix
      of the record's bytes reach disk, then the crash — replay must
      detect the broken CRC chain, truncate the tail, and recover the
      exact pre-append state).
    - ``compact_schedule`` maps a COMPACTION index to COMPACT_CRASH
      (crash after the WAL COMPACT_START marker but before the atomic
      generation swap — recovery must come up on the SURVIVING
      generation, base + published delta, with the half-built
      generation discarded).
    - round 21: ``schedule`` also accepts the op-asserting crash legs
      MUT_DELETE/MUT_REWEIGHT (MUT_CRASH semantics, but the firing
      mutation's op must match — a typed ValueError otherwise), and
      ``reseed_schedule`` maps a RE-SEED index to RESEED_CRASH (crash
      between the affected-cone computation and the re-converge:
      recovery must come up with the anti-monotone ops still pending
      and admission still capped).

    Like FaultPlan, fired entries never re-fire (the counters advance
    past them), so recovery always terminates; ``fired`` records what
    happened, for assertions."""

    schedule: dict = dataclasses.field(default_factory=dict)
    compact_schedule: dict = dataclasses.field(default_factory=dict)
    reseed_schedule: dict = dataclasses.field(default_factory=dict)
    mutations: int = dataclasses.field(default=0, init=False)
    compactions: int = dataclasses.field(default=0, init=False)
    reseeds: int = dataclasses.field(default=0, init=False)
    fired: list = dataclasses.field(default_factory=list, init=False)

    # the op each op-asserting crash action demands of the firing
    # mutation (MUT_CRASH/WAL_TORN stay op-agnostic)
    _OP_BY_ACTION = {MUT_DELETE: "delete", MUT_REWEIGHT: "reweight"}

    def __post_init__(self):
        for i, a in self.schedule.items():
            if a not in (MUT_CRASH, WAL_TORN, MUT_DELETE,
                         MUT_REWEIGHT):
                raise ValueError(
                    f"MutationFaultPlan schedule[{i}] must be "
                    f"MUT_CRASH, WAL_TORN, MUT_DELETE, or "
                    f"MUT_REWEIGHT, got {a!r}")
        for i, a in self.compact_schedule.items():
            if a != COMPACT_CRASH:
                raise ValueError(
                    f"MutationFaultPlan compact_schedule[{i}] must "
                    f"be COMPACT_CRASH, got {a!r}")
        for i, a in self.reseed_schedule.items():
            if a != RESEED_CRASH:
                raise ValueError(
                    f"MutationFaultPlan reseed_schedule[{i}] must "
                    f"be RESEED_CRASH, got {a!r}")

    def fire_append(self, wal, record: bytes,
                    op: str = "append") -> None:
        """Called by LiveGraph._publish BEFORE the record is written.
        MUT_CRASH raises with nothing on disk; WAL_TORN writes a
        strict prefix of ``record`` (the torn write) and then raises;
        MUT_DELETE/MUT_REWEIGHT assert ``op`` matches, then crash
        like MUT_CRASH.  ``wal`` may be None (un-logged LiveGraph):
        the crash still fires, there is just nothing to tear."""
        i = self.mutations
        self.mutations += 1
        action = self.schedule.get(i)
        if action is None:
            return
        want = self._OP_BY_ACTION.get(action)
        if want is not None and op != want:
            raise ValueError(
                f"MutationFaultPlan schedule[{i}] = {action} expects "
                f"a {want!r} mutation at index {i}, but a {op!r} "
                f"fired — the drill's mutation stream is not the one "
                f"the plan was written against")
        self.fired.append((i, action))
        if action == WAL_TORN and wal is not None:
            wal.write_torn(record)
        raise InjectedWorkerCrash(
            f"injected {action} at mutation {i} (op={op}): worker "
            f"died "
            f"{'mid-append (torn WAL write)' if action == WAL_TORN else 'before the WAL record landed'}")

    def fire_compact(self) -> None:
        """Called by LiveGraph.compact between the COMPACT_START WAL
        marker and the atomic generation swap."""
        i = self.compactions
        self.compactions += 1
        if self.compact_schedule.get(i) != COMPACT_CRASH:
            return
        self.fired.append((i, COMPACT_CRASH))
        raise InjectedWorkerCrash(
            f"injected compact_crash at compaction {i}: worker died "
            f"after COMPACT_START, before the generation swap")

    def fire_reseed(self) -> None:
        """Called by LiveGraph._revalidate_anti between the
        affected-cone computation and the re-converge."""
        i = self.reseeds
        self.reseeds += 1
        if self.reseed_schedule.get(i) != RESEED_CRASH:
            return
        self.fired.append((i, RESEED_CRASH))
        raise InjectedWorkerCrash(
            f"injected reseed_crash at re-seed {i}: worker died "
            f"after the affected-cone computation, before the "
            f"re-converge")


def nan_corrupt(state, count: int = 1):
    """Host copy of ``state`` with NaN poked into the first ``count``
    cells of its first floating leaf (what a corrupted segment output
    looks like to debug.check_finite)."""
    import jax

    leaves, treedef = jax.tree.flatten(state)
    out, done = [], False
    for leaf in leaves:
        arr = np.array(leaf)              # host copy, always writable
        if (not done and arr.size
                and np.issubdtype(arr.dtype, np.floating)):
            arr.reshape(-1)[:count] = np.nan
            done = True
        out.append(arr)
    if not done:
        raise ValueError(
            "no floating leaf to NaN-corrupt (integer-labeled "
            "programs: use int_corrupt / corrupt_state with the "
            "program's identity sentinel)")
    return jax.tree.unflatten(treedef, out)


def int_corrupt(state, count: int = 1, value: int | None = None):
    """Host copy of ``state`` with ``value`` poked into the first
    ``count`` cells of its first INTEGER (non-bool) leaf — the
    one-sentinel convention's corruption for integer-labeled programs
    (sssp hop counts, components ids): poke the program's
    identity/sentinel, i.e. a lost update, never out-of-band garbage
    a max-program would propagate."""
    import jax

    if value is None:
        raise ValueError(
            "int_corrupt needs the program's identity/sentinel value "
            "(e.g. sssp.HOP_INF, components' -1)")
    leaves, treedef = jax.tree.flatten(state)
    out, done = [], False
    for leaf in leaves:
        arr = np.array(leaf)
        if (not done and arr.size
                and np.issubdtype(arr.dtype, np.integer)):
            arr.reshape(-1)[:count] = arr.dtype.type(value)
            done = True
        out.append(arr)
    if not done:
        raise ValueError("no integer leaf to corrupt")
    return jax.tree.unflatten(treedef, out)


def corrupt_state(state, count: int = 1, int_value: int | None = None):
    """Type-appropriate state corruption: NaN into the first float
    leaf when one exists, else the sentinel ``int_value`` into the
    first integer leaf — what makes every app corruption-testable
    under a seeded ``p_nan`` plan (the old float-only nan_corrupt
    crashed the harness on sssp/components)."""
    import jax

    if any(np.issubdtype(np.asarray(x).dtype, np.floating)
           for x in jax.tree.leaves(state)):
        return nan_corrupt(state, count)
    return int_corrupt(state, count, int_value)


# -- checkpoint-file injectors (exercise checkpoint.py's CRC +
#    generation-fallback path deterministically) -----------------------

def bitflip_checkpoint(path: str, leaf: int = 0, bit: int = 0) -> None:
    """Flip one bit in ``leaf``'s payload INSIDE the npz container,
    rewriting the zip so its own member CRC stays consistent — the
    torn-but-well-formed corruption only checkpoint.py's per-leaf
    CRC32 can catch (a raw on-disk flip would already fail the zip
    layer).  The flipped bit is in the last payload byte, safely past
    the .npy header."""
    import io
    import zipfile

    name = f"leaf_{leaf}.npy"
    with zipfile.ZipFile(path, "r") as z:
        items = [(zi.filename, z.read(zi.filename))
                 for zi in z.infolist()]
    out = io.BytesIO()
    with zipfile.ZipFile(out, "w", zipfile.ZIP_STORED) as z:
        for fname, data in items:
            if fname == name:
                data = bytearray(data)
                data[-1] ^= (1 << (bit & 7))
                data = bytes(data)
            z.writestr(fname, data)
    with open(path, "wb") as f:
        f.write(out.getvalue())


def truncate_checkpoint(path: str, keep: float = 0.5) -> None:
    """Truncate the file to ``keep`` of its size — the torn-write /
    partial-download corruption (an unreadable container, caught by
    checkpoint.load's CorruptCheckpointError wrapping)."""
    import os

    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(1, int(size * keep)))


def tear_wal(path: str, keep_bytes: int = 7) -> None:
    """Append ``keep_bytes`` of a partial garbage record to a
    mutation log at rest — what a power loss mid-append leaves on
    disk (the torn tail scripts/fsck_lux.py and MutationLog.replay
    must diagnose via the CRC chain, never replay).  A mid-append
    tear is by definition a STRICT record prefix, so keep_bytes is
    clamped below the record size — a full-record-sized garbage
    tail would read as a complete record with a bad CRC, which
    MutationLog.scan rightly classifies as hard crc_chain
    corruption of a possibly-acknowledged mutation, not the
    recoverable torn tail this helper promises."""
    from lux_tpu import format as luxfmt

    with open(path, "ab") as f:
        f.write(b"\x7f" * min(max(1, int(keep_bytes)),
                              luxfmt.WAL_RECORD_SIZE - 1))
