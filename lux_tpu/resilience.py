"""Resilient run supervision: crash classification, retry/resume,
duration-budgeted segmentation, and bench sample screening.

The reference inherits fault tolerance from the Legion/Realm runtime
it sits on (SURVEY §1); lux_tpu's substrate is plain JAX, which has
none.  The failure modes seen so far (PERF_NOTES round 5, on the
earlier installation; none re-observed on this one yet) are transient
TPU worker death (one bench config crashed outright and a pagerank-mp
sample collapsed 10x) and a ~55 s single-execution duration wall.
This module is the recovery story:

- ``classify`` sorts failures into RETRYABLE (connection/worker death,
  injected crashes, NaN escapes caught by debug.check_finite — the
  last checkpoint predates the corruption, so resuming can help),
  TOPOLOGY (round 11: devices or worker processes GONE — device-
  unavailable / coordination-service-heartbeat signatures, injected
  device loss, heartbeat deadline misses — retrying on the same mesh
  replays the same dead topology, but re-placing onto the survivors
  can finish the run) and FATAL (too-large / OOM compile rejects,
  StallError livelocks, programming errors — deterministic, retrying
  reruns the same bug).  A deterministic divergence still surfaces:
  it recurs until the retry budget is exhausted and the last error
  propagates.
- ``supervise`` retries retryable failures with exponential backoff
  (decorrelated-jittered: synchronized backoff across worker
  processes is a retry stampede on the coordination service).
  TOPOLOGY failures route through an ``on_topology`` handler — the
  elastic re-placement path below — and are fatal without one.
- the ELASTIC path (``supervised_run(..., elastic=make_engine)``): a
  topology fault rebuilds the mesh over the surviving devices (parts
  P fixed — the largest device count dividing num_parts; checkpoints
  hold the global ``[P, vpad, ...]`` host view, so re-sharding is
  just ``eng.place`` on the new engine), resets the duration budget's
  learned rate, and resumes from the last checkpoint — bitwise-equal
  to an uninterrupted run on the smaller mesh.  Multi-process runs
  pair this with per-segment heartbeat supervision
  (lux_tpu/heartbeat.py): survivors detect the death at a segment
  boundary, agree on the shrunken topology, and relaunch degraded
  (jax.distributed cannot drop a member in-process).
- ``supervised_run`` / ``supervised_converge`` compose the retry loop
  with checkpoint.py's segmented paths: every segment checkpoints
  atomically, retries AUTO-RESUME from the last checkpoint instead of
  restarting, and optional fault injection (lux_tpu/faults.py) plus
  the debug.py finite guard run at each boundary.
- a ``seg_budget`` sizes segments with ``segmented.DurationBudget``
  so each XLA execution stays under a duration budget.
- ``screen_outliers`` is bench.py's discard-and-rerun rule for
  collapsed samples (>3x off the median).
"""

from __future__ import annotations

import dataclasses
import os
import re
import time
from statistics import median
from typing import Callable

import numpy as np

RETRYABLE = "retryable"
FATAL = "fatal"
TOPOLOGY = "topology"

# Topology-fault signatures: the mesh itself changed underneath the
# program (a chip died, a worker process left the coordination
# service).  Scanned BEFORE the fatal/transient word scans — a
# topology signature is strictly more specific than the generic
# "unavailable"/"heartbeat" transient words (which would wrongly
# retry on the same dead mesh) and misclassifying one as fatal aborts
# a run that re-placement could finish.
_TOPOLOGY_RE = re.compile(
    r"device(?:s)?\s+(?:\S+\s+)?(?:is\s+|are\s+)?unavailable|"
    r"DEVICE_UNAVAILABLE|"
    r"device\s+\S+\s+(?:lost|removed|failed)|"
    r"coordination\s+service|"
    r"heartbeat\s+(?:deadline|timeout|timed[\s_-]?out|missed)|"
    r"slice\s+health|task\s+\d+\s+(?:left|lost|missing)", re.I)

# Deterministic failures — retrying replays the same program into the
# same rejection.  Checked before the transient MESSAGE patterns: an
# XlaRuntimeError carrying an OOM must not match the worker
# signatures below.
_FATAL_RE = re.compile(
    r"too\s+large|resource.?exhausted|out of memory|"
    r"failed to allocate|program shape", re.I)

# Transient connection/worker signatures: connection loss, worker
# death, deadline blowouts — the things a fresh attempt can outlive.
_RETRYABLE_RE = re.compile(
    r"unavailable|connection|socket|deadline|timed?[\s_-]?out|"
    r"worker|terminated|cancell?ed|aborted|heartbeat|broken pipe|"
    r"reset by peer|transport", re.I)

_RETRYABLE_TYPES = (ConnectionError, TimeoutError, BrokenPipeError,
                    EOFError)

# Deterministic filesystem failures (a bad -resume path, a read-only
# checkpoint dir): OSError subclasses a retry cannot fix.
_FATAL_OSERRORS = (FileNotFoundError, NotADirectoryError,
                   IsADirectoryError, PermissionError, FileExistsError)


def classify(exc: BaseException) -> str:
    """RETRYABLE, TOPOLOGY or FATAL for one failure (see module
    docstring for the taxonomy).  Typed checks outrank every message
    scan (the PR-1 convention)."""
    from lux_tpu import checkpoint, debug, faults, health

    if isinstance(exc, (faults.InjectedDeviceLoss,
                        faults.InjectedWorkerKill)):
        return TOPOLOGY
    from lux_tpu import heartbeat
    if isinstance(exc, heartbeat.WorkerLostError):
        return TOPOLOGY        # a peer missed its heartbeat deadline:
        #                        its devices are gone with it
    if isinstance(exc, faults.InjectedWorkerCrash):
        return RETRYABLE
    from lux_tpu import fleet
    if isinstance(exc, fleet.AdmissionError):
        return FATAL            # an intentional shed is a DECISION,
        #                         not a failure: a supervisor that
        #                         retried it would re-admit a query
        #                         the serving tier chose to reject
        #                         (and its message says 'shed'/
        #                         'deadline', which must never hit
        #                         the retryable word scan below)
    from lux_tpu import audit
    if isinstance(exc, audit.AuditError):
        return FATAL            # a static-audit violation is a
        #                         property of the BUILD: retrying
        #                         re-traces the same program into the
        #                         same typed refusal (and the finding
        #                         text may mention 'worker'/'socket',
        #                         which must not hit the retryable
        #                         message scan below)
    if isinstance(exc, health.HealthError):
        return FATAL            # fatal-with-diagnosis: the watchdog
        #                         saw corruption in the STATE itself
        #                         (which check/part/iteration is on
        #                         the exception) — blind retry/resume
        #                         reruns into the same diagnosis
    if isinstance(exc, checkpoint.CorruptCheckpointError):
        return RETRYABLE        # the retry's resume goes through
        #                         load_any, which falls back one
        #                         GENERATION and replays the lost
        #                         segment — never the deterministic-
        #                         OSError fatal bucket below
    if isinstance(exc, debug.StallError):
        return FATAL
    if isinstance(exc, debug.DivergenceError):
        return RETRYABLE        # possible transient corruption;
        #                         deterministic NaN exhausts retries
    if isinstance(exc, _RETRYABLE_TYPES):
        return RETRYABLE        # typed transport errors outrank any
        #                         message scan ("...writing request
        #                         payload too large buffer" etc.)
    msg = f"{type(exc).__name__}: {exc}"
    if _TOPOLOGY_RE.search(msg):
        return TOPOLOGY        # XlaRuntimeError device-unavailable /
        #                        coordination-service signatures (the
        #                        raw form a real chip/worker loss
        #                        surfaces as through jax.distributed)
    if _FATAL_RE.search(msg):
        return FATAL
    if isinstance(exc, _FATAL_OSERRORS):
        return FATAL
    if isinstance(exc, OSError):
        return RETRYABLE        # transient I/O
    if _RETRYABLE_RE.search(msg):
        return RETRYABLE
    return FATAL


@dataclasses.dataclass
class RetryPolicy:
    """Backoff for retryable failures.  ``sleep`` is injectable so
    tests (and dry runs) never actually wait.

    Delays use DECORRELATED JITTER (delay_k drawn uniformly from
    [backoff_s, min(max, 3 * delay_{k-1})]): plain exponential
    backoff is synchronized across worker processes — after a shared
    transient (a coordination-service hiccup hits every worker at
    once) they all retry at the same instants, a retry stampede that
    re-knocks the service over.  The draw is SEEDED (default: derived
    from the pid, so workers decorrelate; pass ``jitter_seed`` for
    bit-deterministic tests) and cached per failure index, so
    ``delay_s(k)`` is stable within one policy instance.
    ``jitter=0`` restores the exact exponential schedule."""

    retries: int = 3
    backoff_s: float = 1.0
    backoff_factor: float = 2.0
    max_backoff_s: float = 60.0
    sleep: Callable[[float], None] = time.sleep
    jitter: float = 1.0
    jitter_seed: int | None = None
    _delays: dict = dataclasses.field(default_factory=dict, init=False,
                                      repr=False, compare=False)
    _rng: object = dataclasses.field(default=None, init=False,
                                     repr=False, compare=False)

    def delay_s(self, failure_index: int) -> float:
        k = int(failure_index)
        exp = min(self.backoff_s * self.backoff_factor ** k,
                  self.max_backoff_s)
        if not self.jitter:
            return exp
        if k in self._delays:
            return self._delays[k]
        if self._rng is None:
            seed = (self.jitter_seed if self.jitter_seed is not None
                    else (os.getpid() * 2654435761) & 0xFFFFFFFF)
            self._rng = np.random.default_rng(seed)
        prev = self._delays.get(k - 1, self.backoff_s)
        lo = self.backoff_s
        hi = min(self.max_backoff_s, max(lo, 3.0 * prev))
        frac = float(self._rng.random()) * min(1.0, max(0.0,
                                                        self.jitter))
        d = min(self.max_backoff_s, lo + (hi - lo) * frac)
        self._delays[k] = d
        return d


@dataclasses.dataclass
class FlapDetector:
    """Deaths-in-a-window flap detection (round 24, the self-healing
    fleet's quarantine trigger).  A replica that keeps dying right
    after resurrection is burning respawn/recompile/canary work and
    churning the routing table — past ``threshold`` deaths inside
    ``window_s`` the supervisor should stop resurrecting it and
    quarantine typed (lux_tpu/fleet.py) instead of flapping forever.
    ``clock`` is injectable so tests drive the window
    deterministically."""

    threshold: int = 3
    window_s: float = 60.0
    clock: Callable[[], float] = time.monotonic
    _deaths: dict = dataclasses.field(default_factory=dict, init=False,
                                      repr=False, compare=False)

    def record(self, name: str) -> int:
        """Record one death of ``name`` now; returns the death count
        inside the rolling window (>= threshold means flapping)."""
        now = float(self.clock())
        ds = [t for t in self._deaths.get(name, ())
              if now - t <= self.window_s]
        ds.append(now)
        self._deaths[name] = ds
        return len(ds)

    def deaths(self, name: str) -> int:
        now = float(self.clock())
        return sum(1 for t in self._deaths.get(name, ())
                   if now - t <= self.window_s)

    def flapping(self, name: str) -> bool:
        return self.deaths(name) >= self.threshold


@dataclasses.dataclass
class RunReport:
    """What the supervisor did: for logs and bench JSON lines."""

    attempts: int = 0
    failures: list = dataclasses.field(default_factory=list)
    #           ^ (exception type name, message[:200], classification)
    resumed_from: list = dataclasses.field(default_factory=list)
    #           ^ checkpoint iteration counter at each resume
    initial_resume: int | None = None
    #           ^ iteration a PRE-EXISTING checkpoint supplied to the
    #             first attempt (explicit resume=True only) — in-run
    #             retry resumes redo work this run already did and
    #             are deliberately NOT counted here
    total_iters: int = 0
    segments: int = 0
    counters: dict | None = None
    #           ^ device-side iteration-counter digest
    #             (telemetry.IterStats.summary()) when the run was
    #             supervised under an active iter-stats handle
    topology: list = dataclasses.field(default_factory=list)
    #           ^ one {from_ndev, to_ndev, lost_devices} per elastic
    #             mesh shrink (round 11) — a run that finished
    #             degraded says so on its report

    def as_dict(self) -> dict:
        return dict(attempts=self.attempts, segments=self.segments,
                    resumed_from=list(self.resumed_from),
                    initial_resume=self.initial_resume,
                    failures=[list(f) for f in self.failures],
                    total_iters=self.total_iters,
                    counters=self.counters,
                    topology=[dict(t) for t in self.topology])


def _flight_dump(exc: BaseException, kind: str) -> None:
    """Crash flight recorder hook (lux_tpu/tracing.py, round 13):
    dump the recent-event ring + last health word + placement
    metadata to FLIGHT.json when a recorder is installed.  The dump
    is best-effort by design — a postmortem writer must never mask
    the fault it is recording."""
    try:
        from lux_tpu import tracing
        tracing.flight_dump(
            reason=f"{type(exc).__name__}: {exc}"[:300],
            classification=kind)
    except Exception:           # noqa: BLE001 — see docstring
        pass


def supervise(attempt: Callable, policy: RetryPolicy | None = None,
              report: RunReport | None = None, on_topology=None):
    """Run ``attempt(k)`` (k = 0-based attempt index) under classified
    retries: retryable failures back off and retry, fatal ones (and
    retry-budget exhaustion) re-raise.  Returns (result, report).

    ``on_topology(exc)`` handles TOPOLOGY-classified failures (device
    or worker loss): it re-places the run onto a surviving topology
    and returns True, after which the next attempt proceeds WITHOUT
    backoff (the fault is structural, not congestion — idling the
    survivors buys nothing).  Returning False — or having no handler
    — makes the topology fault fatal: retrying on the same dead mesh
    replays the same failure."""
    from lux_tpu import telemetry

    policy = policy or RetryPolicy()
    report = report or RunReport()
    tel = telemetry.current()
    for k in range(max(0, policy.retries) + 1):
        report.attempts += 1
        try:
            return attempt(k), report
        except Exception as e:      # noqa: BLE001 — classified below
            kind = classify(e)
            report.failures.append(
                (type(e).__name__, str(e)[:200], kind))
            handled = False
            if (kind == TOPOLOGY and on_topology is not None
                    and k < policy.retries):
                handled = bool(on_topology(e))
            if kind == TOPOLOGY:
                tel.emit("topology_fault", attempt=k,
                         error=type(e).__name__, message=str(e)[:200],
                         handled=handled)
                # flight recorder (round 13): a topology transition —
                # handled or not — is postmortem-worthy; the dump
                # happens AFTER the event so the ring includes it
                _flight_dump(e, kind)
            fatal = (kind == FATAL
                     or (kind == TOPOLOGY and not handled)
                     or k >= policy.retries)
            if fatal:
                tel.emit("failure", attempt=k,
                         error=type(e).__name__, message=str(e)[:200],
                         classification=kind)
                if kind != TOPOLOGY:      # topology already dumped
                    _flight_dump(e, kind)
                raise
            if kind == TOPOLOGY:
                continue            # re-placed: retry immediately
            d = policy.delay_s(k)
            tel.emit("retry", attempt=k, error=type(e).__name__,
                     message=str(e)[:200], classification=kind,
                     backoff_s=round(d, 3))
            policy.sleep(d)
    raise AssertionError("unreachable")


def _make_segment(segment, seg_budget, per_size_compile=True):
    if seg_budget:
        from lux_tpu.segmented import DurationBudget
        return DurationBudget(float(seg_budget),
                              per_size_compile=per_size_compile)
    return segment


def _mesh_device_ids(eng):
    """Device ids of the engine's mesh (None for single-device
    engines) — what fault plans resolve DEVICE_LOSS/WORKER_KILL
    against."""
    if getattr(eng, "mesh", None) is None:
        return None
    return [d.id for d in eng.mesh.devices.flat]


def _mesh_after_loss(eng, exc):
    """The surviving-device mesh after a topology fault, or None when
    no shrink is possible: single-device engines have no topology to
    shrink; multi-host local-parts builds re-place by coordinated
    relaunch (lux_tpu/heartbeat.py), not in-process; and a fault that
    names no losses (and the backend re-probe shows everything alive)
    leaves nothing to shrink away.

    Parts P stay FIXED — the new mesh is the largest surviving device
    count dividing num_parts (graph.compatible_mesh_sizes), so the
    padded layout, every program shape, and the checkpointed global
    ``[P, vpad, ...]`` view are all reusable unchanged; only the
    part -> device mapping moves."""
    import jax

    from lux_tpu.parallel.mesh import make_mesh

    if getattr(eng, "mesh", None) is None:
        return None
    if eng.sg.local_parts is not None:
        return None
    devs = list(eng.mesh.devices.flat)
    lost = getattr(exc, "lost_devices", None)
    if lost:
        gone = {int(d) for d in lost}
        survivors = [d for d in devs if d.id not in gone]
    else:
        # no named losses: re-probe the backend and keep the mesh
        # devices the runtime still lists
        alive = {d.id for d in jax.devices()}
        survivors = [d for d in devs if d.id in alive]
    if len(survivors) == len(devs):
        return None
    sizes = eng.sg.compatible_mesh_sizes(len(survivors))
    if not sizes:
        return None
    return make_mesh(devices=survivors[:sizes[0]])


def _elastic_handler(box, make_engine, segment, report):
    """The supervise() on_topology hook for elastic runs: shrink the
    mesh over the survivors, rebuild the engine (``make_engine(mesh)``
    — engines compile per-mesh automatically since graph arrays are
    jit arguments), and reset the duration budget's learned rate (a
    per-segment rate measured on 8 devices is stale on 4 and would
    blow the duration wall on the first post-shrink segment).  The
    actual data movement happens on the retry's checkpoint resume:
    checkpoint.py re-shards the global host view via the NEW engine's
    ``place`` and emits the ``replace`` event."""

    def on_topology(exc):
        from lux_tpu import telemetry
        from lux_tpu.segmented import DurationBudget

        eng = box["eng"]
        mesh = _mesh_after_loss(eng, exc)
        if mesh is None:
            return False
        old = int(eng.mesh.devices.size)
        new = int(mesh.devices.size)
        lost = sorted(getattr(exc, "lost_devices", ()) or ())
        t0 = time.perf_counter()
        neweng = make_engine(mesh)
        if neweng.sg.num_parts != eng.sg.num_parts:
            raise ValueError(
                f"elastic engine factory changed num_parts "
                f"({eng.sg.num_parts} -> {neweng.sg.num_parts}); "
                f"re-placement keeps parts FIXED and changes only "
                f"the device mapping")
        box["eng"] = neweng
        if isinstance(segment, DurationBudget):
            segment.reset_rate(reason="mesh_shrink")
        report.topology.append(
            {"from_ndev": old, "to_ndev": new,
             "lost_devices": [int(d) for d in lost]})
        telemetry.current().emit(
            "mesh_shrink", from_ndev=old, to_ndev=new,
            lost=[int(d) for d in lost],
            parts=int(eng.sg.num_parts), error=type(exc).__name__,
            rebuild_seconds=round(time.perf_counter() - t0, 3))
        return True

    return on_topology


def _int_sentinel(eng):
    """The integer identity/sentinel value of the engine's program (the
    one-sentinel convention: faults.corrupt_state pokes it into
    integer-labeled states — sssp hop counts, components ids — so a
    seeded NAN plan can corrupt all four apps instead of crashing on
    the float-only nan_corrupt).  None for float programs."""
    ident = getattr(getattr(eng, "program", None), "identity", None)
    if ident is None:
        return None
    ident = np.asarray(ident)
    return int(ident) if np.issubdtype(ident.dtype, np.integer) else None


def _record_resume(path, report):
    from lux_tpu import checkpoint

    if checkpoint.any_generation(path):
        try:
            # generation-fallback-aware: records the iteration the
            # resume will ACTUALLY continue from (the .prev one when
            # the newest file is corrupt — a meta-only peek would
            # misreport the corrupt file's own counter, so this pays
            # the verifying load).  load_any QUARANTINES a corrupt
            # newest, so the fallback detection, its event and its
            # CRC cost all happen ONCE here; the attempt's resume
            # then reads the good generation directly.
            _leaves, meta, _used = checkpoint.load_any(path)
            report.resumed_from.append(int(meta.get("iter", 0)))
        except Exception:           # noqa: BLE001 — all gens corrupt
            pass                    # the attempt itself will surface it


def supervised_run(eng, num_iters: int, path: str, *,
                   policy: RetryPolicy | None = None,
                   segment=50, seg_budget: float | None = None,
                   resume: bool = False, faults=None,
                   guard: bool = True, report: RunReport | None = None,
                   elastic=None, heartbeat=None):
    """Supervised pull-engine fixed-iteration run: segmented +
    checkpointed to ``path``, with classified retries resuming from
    the last atomic checkpoint.  Returns (state, report).

    resume=False starts fresh (a stale file at ``path`` is removed so
    a crash before the first save cannot resurrect it); retries within
    the run always resume.  ``faults`` (faults.FaultPlan) and the
    finite ``guard`` run at each segment boundary BEFORE the save, so
    injected/real corruption never reaches a checkpoint.

    ``elastic`` (round 11): an engine FACTORY ``make_engine(mesh) ->
    engine`` — a TOPOLOGY-classified failure then rebuilds the mesh
    over the surviving devices and resumes on it instead of dying
    (see _elastic_handler).  ``heartbeat`` (lux_tpu/heartbeat.py): a
    Heartbeat board multi-process runs sync at every segment boundary
    — a dead peer raises a TOPOLOGY-classified WorkerLostError there
    instead of hanging the next collective."""
    from lux_tpu import checkpoint, debug

    report = report or RunReport()
    if not resume:
        checkpoint.remove(path)     # BOTH generations: a stale .prev
        #                             must not resurrect either
    if faults is not None and hasattr(faults, "bind_checkpoint"):
        faults.bind_checkpoint(path)
    # ONE segment sizer for the whole supervised run (not per
    # attempt): the duration budget's learned rate survives plain
    # retries and is explicitly reset on a topology change
    seg = _make_segment(segment, seg_budget)
    box = {"eng": eng}

    def hook(s, done):
        report.segments += 1
        out = None
        if faults is not None:
            res = faults.fire(s, int_value=_int_sentinel(box["eng"]),
                              device_ids=_mesh_device_ids(box["eng"]))
            if res is not None:
                s = out = box["eng"].place(res)
        if heartbeat is not None:
            heartbeat.sync(report.segments - 1)
        if guard:
            debug.check_finite(
                s, f"supervised pull run @ iteration {done}")
        return out

    # eng.run DONATES its state buffers, so a consumed state cannot
    # feed a second attempt — but a resuming attempt whose checkpoint
    # exists only reads the pytree STRUCTURE (checkpoint.py), so a
    # spent state (or an abstract eval_shape stub on a fresh-process
    # resume) serves as structure donor and the attempt skips
    # re-placing a fresh multi-hundred-MB state on device.  The
    # structure is mesh-independent, so it survives a re-placement.
    state0 = None

    def attempt(k):
        nonlocal state0
        cur = box["eng"]
        do_resume = resume or k > 0
        if do_resume:
            _record_resume(path, report)
            if k == 0 and report.resumed_from:
                report.initial_resume = report.resumed_from[0]
        will_load = do_resume and checkpoint.any_generation(path)
        if will_load and state0 is None:
            import jax
            try:                    # structure-only: no placement
                state0 = jax.eval_shape(cur.init_state)
            except Exception:       # noqa: BLE001 — untraceable init
                state0 = cur.init_state()
        elif not will_load:
            state0 = cur.init_state()
        return checkpoint.run_checkpointed(
            cur, state0, num_iters, path,
            segment=seg, resume=do_resume, on_segment=hook)

    on_topology = (None if elastic is None
                   else _elastic_handler(box, elastic, seg, report))
    state, report = supervise(attempt, policy, report,
                              on_topology=on_topology)
    if heartbeat is not None:
        heartbeat.finish()
    report.total_iters = num_iters
    _attach_counters(report)
    return state, report


def _attach_counters(report):
    """Fold the active iter-stats digest (device-side per-iteration
    counters accumulated by the segmented drivers) into the report, so
    RunReport.as_dict() carries the counter summary."""
    from lux_tpu import telemetry

    st = telemetry.current().iter_stats
    if st is not None:
        report.counters = st.summary()


def supervised_converge(eng, path: str, *,
                        policy: RetryPolicy | None = None,
                        segment=50, seg_budget: float | None = None,
                        resume: bool = False,
                        max_iters: int | None = None, faults=None,
                        guard: bool = True,
                        report: RunReport | None = None,
                        elastic=None, heartbeat=None):
    """Supervised push-engine convergence: segmented + checkpointed to
    ``path``, with classified retries resuming from the last atomic
    checkpoint.  Returns (label, active, total_iters, report).

    The boundary guard runs check_finite(allow_inf=True) — +inf is the
    legitimate unreached sentinel; NaN raises DivergenceError, which
    classifies retryable (the checkpoint predates the corruption).

    ``elastic`` / ``heartbeat``: same degraded-mesh recovery contract
    as supervised_run (engine factory re-placement on TOPOLOGY
    failures; per-segment heartbeat sync for multi-process runs)."""
    from lux_tpu import checkpoint, debug

    report = report or RunReport()
    if not resume:
        checkpoint.remove(path)
    if faults is not None and hasattr(faults, "bind_checkpoint"):
        faults.bind_checkpoint(path)
    seg = _make_segment(segment, seg_budget, per_size_compile=False)
    box = {"eng": eng}

    def hook(lbl, act, total, cnt):
        report.segments += 1
        out = None
        if faults is not None:
            res = faults.fire((lbl, act),
                              int_value=_int_sentinel(box["eng"]),
                              device_ids=_mesh_device_ids(box["eng"]))
            if res is not None:
                lbl, act = box["eng"].place(
                    *[np.asarray(x) for x in res])
                out = (lbl, act)
        if heartbeat is not None:
            heartbeat.sync(report.segments - 1)
        if guard:
            debug.check_finite(
                lbl, f"supervised converge @ iteration {total}",
                allow_inf=True)
        return out

    def attempt(k):
        do_resume = resume or k > 0
        if do_resume:
            _record_resume(path, report)
            if k == 0 and report.resumed_from:
                report.initial_resume = report.resumed_from[0]
        return checkpoint.converge_checkpointed(
            box["eng"], path, segment=seg,
            resume=do_resume, max_iters=max_iters, on_segment=hook)

    on_topology = (None if elastic is None
                   else _elastic_handler(box, elastic, seg, report))
    (label, active, total), report = supervise(
        attempt, policy, report, on_topology=on_topology)
    if heartbeat is not None:
        heartbeat.finish()
    report.total_iters = total
    _attach_counters(report)
    return label, active, total, report


def screen_outliers(samples, rerun: Callable[[], float] | None,
                    factor: float = 3.0):
    """bench.py's discard-and-rerun rule (round-5 VERDICT #7): a
    sample more than ``factor``x off the median of its batch is a
    collapse (pagerank-mp once sampled [0.1116, 0.0107, 0.1118] on
    the earlier installation), not a measurement — it is discarded and re-run ONCE, and the
    discards are reported so the JSON line cannot silently median
    over a collapse.

    Returns (kept_samples, discarded, attempts) where ``attempts``
    counts every timed run (original batch + reruns).  factor<=0
    disables screening.
    """
    from lux_tpu import telemetry

    tel = telemetry.current()
    samples = list(samples)
    attempts = len(samples)
    if len(samples) < 2 or not factor or factor <= 0:
        return samples, [], attempts
    m = median(samples)

    def is_outlier(s):
        return s < m / factor or s > m * factor

    kept = [s for s in samples if not is_outlier(s)]
    discarded = [s for s in samples if is_outlier(s)]
    if not kept:        # mutual disagreement: nothing to trust more
        return samples, [], attempts
    for d in list(discarded):
        tel.emit("outlier_discard", sample=round(d, 6),
                 median=round(m, 6), factor=factor)
        if rerun is None:
            continue
        s = rerun()
        attempts += 1
        if is_outlier(s):
            discarded.append(s)     # the rerun ALSO collapsed: record
            #                         it, never median it (reruns get
            #                         one chance — no retry loops)
        else:
            kept.append(s)
        tel.emit("outlier_rerun", sample=round(s, 6),
                 kept=not is_outlier(s))
    return kept, discarded, attempts
