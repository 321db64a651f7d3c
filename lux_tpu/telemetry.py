"""In-loop run telemetry: structured events + device-side counters.

The reference's only observability is ``-verbose`` wall clocks and
per-part phase prints (reference sssp_gpu.cu:513-518,
pagerank.cc:108-118); nothing a tool can consume, and nothing visible
INSIDE a run.  This module is the shared telemetry layer the engines,
the segmented drivers (segmented.py / checkpoint.py), the resilience
supervisor (resilience.py), the CLI and bench.py all emit into:

- ``EventLog``: a structured JSONL event sink (one JSON object per
  line: ``{"t": ..., "kind": ..., ...}``).  Segment start/stop with
  measured rates, checkpoint save/resume, classified retries, outlier
  discards and duration-budget decisions all become events instead of
  ad-hoc prints — round 9 adds the guarded-execution events:
  ``health`` (per-run watchdog digest), ``health_trip`` (the
  diagnosis of a tripped watchdog: checks, iteration, part) and
  ``checkpoint_fallback`` (a corrupt newest generation replaced by
  ``.prev``); round 11 adds the elastic-recovery trail
  (lux_tpu/resilience.py, heartbeat.py): ``topology_fault`` (a
  TOPOLOGY-classified failure, handled or not), ``mesh_shrink`` (the
  decision: from/to device count, lost devices — or the heartbeat
  protocol's from/to process count), ``replace`` (a checkpoint
  written at one device count resumed on another), ``budget_reset``
  (the duration budget's learned rate discarded on a topology
  change) and ``straggler`` (a live-but-behind heartbeat peer).
  ``scripts/events_summary.py`` renders a log into the
  reference-style loadTime/compTime/updateTime table and
  ``scripts/check_bench.py`` validates the schema.
- ``IterStats``: the host-side accumulator for DEVICE-SIDE iteration
  counters.  Engines accumulate per-iteration scalars *inside* their
  fused fori_loop/while_loop (push: frontier size + frontier out-edges
  relaxed per iteration; pull: state residual + changed-vertex count)
  into fixed-shape ``[stats_cap]`` buffers, fetched ONCE per run or
  segment boundary — a few KB independent of graph size, the same
  O(1)-style discipline as ``timing.fence``.  The hot loop gains no
  host syncs and no extra gathers.  Round 13 extends the same
  variants with PER-PART counters (``[stats_cap, P]`` buffers:
  push frontier/out-edges per part, pull residual/changed per part),
  the measured skew signal ROADMAP item 4's locality-aware
  partitioner optimizes: sum-over-parts bitwise-equals the scalar
  series (integer sums; the pull residual is a max, whose
  max-over-parts equals the scalar), and the derived IMBALANCE index
  (max/mean per-part work) rides ``summary()`` into events, bench
  metric lines (``telemetry.imbalance``) and RunReport.
- a contextvar-scoped ``Telemetry`` handle (``use()``/``current()``)
  so the cross-cutting run paths (CLI supervised runs, bench configs,
  checkpointed segments) light up without threading parameters
  through every signature.  The default is a null handle: emitting is
  a no-op and engines build their counter-free programs.

- ``span(name, **counts)`` / ``mark(name, **counts)`` / ``spans()``:
  the ONE way the program times a host region.  A span is a context
  manager that opens ``profiling.annotation("lux:" + name)`` (so in
  any profiler capture it lies on the ``/host:CPU`` plane, on the
  device trace's clock, beside the idle gap it explains), reads
  ``time.perf_counter()`` on entry and exit, and appends ONE record
  ``{id, parent, name, t0, t1, counts}`` to a process-wide ring of
  ``SPAN_RING`` records (oldest dropped).  ``parent`` is the enclosing
  span on this thread (0 = none).  Counts live ON the records —
  there is no counter registry: ``sp.count(bytes=n)`` fills them
  inside the block, ``mark`` leaves a zero-length record for a count
  with no duration of its own.  A count may be an un-fetched device
  scalar; it is fetched when ``spans()`` takes a snapshot (or, once
  it is ready, when a later record brings device counts of its own),
  never by waiting at the call, so no site gains a host sync.  The ring is always on: no
  flag, no sampling.  A span never fences — where its work is
  asynchronous the site's doc line says so.  With an event sink or an
  observer installed the record also goes out once as a ``span``
  event (``-events`` log, flight recorder, ``tracing.trace_export``).
  ``under(sp)`` makes the records inside it children of ``sp`` after
  ``sp`` has closed (``under(None)``: roots), and a ``sp.count(...)``
  after the close still lands on its record in the ring (not in the
  event that went out).

Counter semantics (what the buffers mean, engine by engine):

- push classic (``PushEngine.converge_stats``): ``frontier[i]`` is the
  global active count AFTER iteration i — exactly the series the
  stepwise ``-verbose`` path prints; ``edges[i]`` is the out-edge
  count of the frontier ENTERING iteration i (the relax work done by
  that iteration, full-graph out-degrees even when pair-lane delivery
  splits the dense arrays).
- push delta-stepping: ``frontier[i]`` is the bucket-front size
  entering relax step i (bucket advances relax nothing and are not
  iterations), ``edges[i]`` the front's out-edges.
- pull (``PullEngine.run_stats`` / ``run_until_stats``):
  ``residual[i]`` is the max-abs state change of iteration i (the
  same scalar ``run_until`` converges on), ``changed[i]`` the number
  of vertices whose state changed.
"""

from __future__ import annotations

import binascii
import collections
import contextlib
import contextvars
import dataclasses
import itertools
import json
import os
import threading
import time

from lux_tpu.profiling import annotation

SCHEMA = 1

# One id per PROCESS, minted at import: heartbeat drills append
# multiple processes' events into one shared file, and wall clocks
# ("t") skew across hosts while monotonic clocks ("tm") only order
# within a process — (session, pid) is the merge key that makes the
# combined log unambiguous (scripts/events_summary.py groups on it).
# The observatory's calibration fingerprint (lux_tpu/observe.py)
# embeds the same id, so a bench metric line, its event trail and its
# PERFLEDGER records all name the same session.
_SESSION = binascii.hexlify(os.urandom(6)).decode()


def session_id() -> str:
    """This process's 12-hex-char telemetry session id."""
    return _SESSION

# engines size their counter buffers with this unless overridden;
# int32+uint32 per entry -> 32 KB fetched per run at the default
DEFAULT_STATS_CAP = 4096

# Event observers (lux_tpu/tracing.py's flight recorder): every event
# built by EventLog.emit — or by a sink-less Telemetry.emit while an
# observer is installed — is offered to each observer.  Observer
# failures are swallowed: a postmortem ride-along must never be able
# to fail the run it exists to diagnose.
_OBSERVERS: list = []


def add_observer(fn) -> None:
    if fn not in _OBSERVERS:
        _OBSERVERS.append(fn)


def remove_observer(fn) -> None:
    if fn in _OBSERVERS:
        _OBSERVERS.remove(fn)


def make_event(kind: str, fields: dict) -> dict:
    """One wire-format event dict.  tm (monotonic) orders events
    WITHIN a process; t (wall) only roughly aligns processes.
    pid+session disambiguate multi-process logs sharing one file
    (heartbeat drills)."""
    return {"t": round(time.time(), 6),
            "tm": round(time.monotonic(), 6),
            "pid": os.getpid(), "session": _SESSION,
            "kind": str(kind), **fields}


def _notify(ev: dict) -> None:
    for fn in list(_OBSERVERS):
        try:
            fn(ev)
        except Exception:       # noqa: BLE001 — see _OBSERVERS note
            pass


class EventLog:
    """Append-only structured event sink.

    Events are always kept in memory (``self.events``); with ``path``
    set, each event is also written immediately as one JSON line (so a
    crashed run still leaves its trail on disk).  On-disk appends are
    LINE-ATOMIC under concurrent multi-process writers (heartbeat
    drills share one file): the fd is opened O_APPEND and each event
    goes down as ONE ``os.write`` of one serialized buffer, so two
    processes' lines can never interleave mid-line (POSIX appends are
    atomic per write; buffered ``file.write`` may split a line across
    syscalls).

    ``rotate_bytes`` (round 17) bounds the on-disk JSONL for
    long-lived serving processes: when an append would push the live
    file past the threshold it is renamed to ``.1`` (existing
    generations shift ``.1 -> .2``, the oldest beyond ``generations``
    drops) and a fresh live file opens, stamped with a ``log_rotate``
    event.  The line-atomic contract survives concurrency: rotation
    runs under an flock'd ``<path>.lock`` sidecar, a writer that
    lost the race just follows the rename (its fd still points at a
    complete, un-torn generation; the path/inode check re-opens the
    new live file on its next emit), and every write remains ONE
    O_APPEND ``os.write`` to whichever generation the fd holds.
    ``rotated_paths`` lists the generation set oldest-first —
    scripts/events_summary.py and lux_tpu/tracing.py consume the
    whole set as one stream.  Rotation also bounds the IN-MEMORY
    ``self.events`` (trimmed to the newest ``MEM_KEEP`` at each
    rotation — a log big enough to rotate is too big to keep whole
    in RAM); index-stable ``self.events`` slicing is therefore
    guaranteed only for non-rotating logs (bench.py's
    ``config_telemetry`` relies on it and never rotates)."""

    # in-memory events kept across a rotation (rotation cadence keeps
    # RSS bounded at ~max(events-per-rotate_bytes, MEM_KEEP))
    MEM_KEEP = 4096

    def __init__(self, path: str | None = None,
                 rotate_bytes: int | None = None,
                 generations: int = 2):
        if rotate_bytes is not None and rotate_bytes <= 0:
            raise ValueError(f"rotate_bytes must be > 0, got "
                             f"{rotate_bytes}")
        if generations < 1:
            raise ValueError(f"generations must be >= 1, got "
                             f"{generations}")
        self.path = path
        self.rotate_bytes = rotate_bytes
        self.generations = int(generations)
        self.rotations = 0
        self.events: list[dict] = []
        self._closed = False
        # one event is built, kept and written under this lock: two
        # threads emitting into one log (fleet replicas, span events)
        # must not write their ``tm`` out of order.  Re-entrant: a
        # rotation notifies observers while it is held, and an
        # observer may emit
        self._lock = threading.RLock()
        self._fd = self._open() if path else None

    def _open(self) -> int:
        return os.open(self.path, os.O_WRONLY | os.O_CREAT
                       | os.O_APPEND, 0o644)

    def _swap_fd(self) -> None:
        """Close the held fd and reopen the live path, keeping
        ``self._fd`` VALID-OR-NONE at every step: a failed reopen
        must leave None (the next emit retries the open), never a
        stale closed descriptor that a later write would hit with
        EBADF — or worse, that a reused descriptor number would turn
        into silent writes to an unrelated file."""
        fd, self._fd = self._fd, None
        if fd is not None:
            try:
                os.close(fd)
            except OSError:
                pass
        self._fd = self._open()

    def _maybe_rotate(self) -> None:
        """Size-triggered rotation check run BEFORE the next event is
        built (so the ``log_rotate`` stamp's monotonic ``tm`` stays
        ordered before it; the live file may overshoot the threshold
        by one line).  Three jobs: recover a sink lost to an earlier
        failed reopen, follow a rotation another process performed
        (path no longer names our inode -> reopen the new live
        file), and rotate ourselves when the live file has crossed
        ``rotate_bytes`` — shift generations, reopen, stamp the new
        file with a ``log_rotate`` event."""
        import fcntl
        try:
            if self._fd is None:
                if not self._closed:
                    self._fd = self._open()   # recover a lost sink
                return
            mine = os.fstat(self._fd)
            try:
                cur = os.stat(self.path)
            except FileNotFoundError:
                cur = None
            if cur is None or (cur.st_dev, cur.st_ino) != \
                    (mine.st_dev, mine.st_ino):
                # someone else rotated: follow to the new live file
                self._swap_fd()
                return
            if mine.st_size <= self.rotate_bytes:
                return
            lfd = os.open(self.path + ".lock",
                          os.O_WRONLY | os.O_CREAT, 0o644)
            try:
                fcntl.flock(lfd, fcntl.LOCK_EX)
                # re-check under the lock: a racing writer may have
                # rotated while we waited
                mine = os.fstat(self._fd)
                try:
                    cur = os.stat(self.path)
                except FileNotFoundError:
                    cur = None
                rotated = False
                if cur is not None \
                        and (cur.st_dev, cur.st_ino) == \
                            (mine.st_dev, mine.st_ino) \
                        and mine.st_size > self.rotate_bytes:
                    for g in range(self.generations - 1, 0, -1):
                        src = f"{self.path}.{g}"
                        if os.path.exists(src):
                            os.replace(src, f"{self.path}.{g + 1}")
                    os.replace(self.path, f"{self.path}.1")
                    rotated = True
                self._swap_fd()
            finally:
                fcntl.flock(lfd, fcntl.LOCK_UN)
                os.close(lfd)
            if rotated:
                self.rotations += 1
                if len(self.events) > self.MEM_KEEP:
                    self.events = self.events[-self.MEM_KEEP:]
                rot = make_event("log_rotate", {
                    "path": self.path, "rotation": self.rotations,
                    "rotate_bytes": self.rotate_bytes,
                    "generations": self.generations})
                self.events.append(rot)
                os.write(self._fd, (json.dumps(rot) + "\n").encode())
                _notify(rot)
        except OSError:
            # rotation is best-effort: a filesystem hiccup must never
            # fail the emit (events always land in memory; _swap_fd
            # guarantees the sink is valid-or-None for the write
            # guard below)
            pass

    def emit(self, kind: str, **fields) -> dict:
        with self._lock:
            if self.path is not None and self.rotate_bytes is not None:
                self._maybe_rotate()
            ev = make_event(kind, fields)
            self.events.append(ev)
            if self._fd is not None:
                # ONE buffer, ONE write: the line-atomicity contract
                os.write(self._fd, (json.dumps(ev) + "\n").encode())
        _notify(ev)
        return ev

    def counts(self) -> dict:
        """{kind: occurrences} over everything emitted so far."""
        out: dict[str, int] = {}
        for ev in list(self.events):
            out[ev["kind"]] = out.get(ev["kind"], 0) + 1
        return out

    def close(self) -> None:
        with self._lock:
            self._closed = True
            if self._fd is not None:
                os.close(self._fd)
                self._fd = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def rotated_paths(path: str) -> list[str]:
    """The on-disk generation set of a (possibly rotated) event log,
    OLDEST FIRST: [path.N, ..., path.1, path] for whichever
    generations exist — concatenating them in this order reproduces
    one stream whose per-process monotonic ``tm`` ordering holds.
    A never-rotated log returns [path]."""
    n = 1
    while os.path.exists(f"{path}.{n}"):
        n += 1
    return [f"{path}.{g}" for g in range(n - 1, 0, -1)] + [path]


class IterStats:
    """Host-side accumulator for device-side per-iteration counters.

    ``extend_push``/``extend_pull`` append one segment's fetched
    counter buffers (the single per-boundary fetch); ``begin_run``
    resets, so one-shot timed helpers record only their LAST timed
    run while segmented drivers accumulate across segments."""

    def __init__(self):
        self.kind: str | None = None
        self.frontier: list[int] = []
        self.edges: list[int] = []
        self.residual: list[float] = []
        self.changed: list[int] = []
        # per-part series (round 13): one [P] row per iteration, from
        # the engines' [stats_cap, P] buffers; empty when the run
        # predates the per-part variants or passed no part buffers
        self.frontier_parts: list[list[int]] = []
        self.edges_parts: list[list[int]] = []
        self.residual_parts: list[list[float]] = []
        self.changed_parts: list[list[int]] = []
        self.truncated = False

    def __len__(self):
        return len(self.frontier) if self.kind == "push" \
            else len(self.residual)

    def begin_run(self) -> None:
        self.kind = None
        self.frontier, self.edges = [], []
        self.residual, self.changed = [], []
        self.frontier_parts, self.edges_parts = [], []
        self.residual_parts, self.changed_parts = [], []
        self.truncated = False

    def _fetch(self, buf, n: int):
        """Fetch the first ``n`` rows of a counter buffer.  The slice
        happens BEFORE the host fetch, so only the live prefix ships
        to the host — a [stats_cap, P] per-part buffer fetched
        whole would be cap*P*8 bytes per segment; the prefix keeps the
        per-boundary cost O(iters x P), i.e. KB for real segments."""
        import numpy as np

        from lux_tpu.timing import fetch
        cap = buf.shape[0]
        if n > cap:
            self.truncated = True
        return np.asarray(fetch(buf[:min(int(n), cap)]))

    def extend_push(self, frontier_buf, edges_buf, n: int,
                    frontier_parts=None, edges_parts=None) -> None:
        """Append ``n`` iterations from a push engine's counter
        buffers (frontier int32 [cap], edges uint32 [cap]; the
        optional per-part buffers are int32/uint32 [cap, P])."""
        self.kind = "push"
        self.frontier += [int(x) for x in self._fetch(frontier_buf, n)]
        self.edges += [int(x) for x in self._fetch(edges_buf, n)]
        if frontier_parts is not None:
            self.frontier_parts += [
                [int(x) for x in row]
                for row in self._fetch(frontier_parts, n)]
        if edges_parts is not None:
            self.edges_parts += [
                [int(x) for x in row]
                for row in self._fetch(edges_parts, n)]

    def extend_pull(self, residual_buf, changed_buf, n: int,
                    residual_parts=None, changed_parts=None) -> None:
        """Append ``n`` iterations from a pull engine's counter
        buffers (residual float32 [cap], changed uint32 [cap]; the
        optional per-part buffers are float32/uint32 [cap, P])."""
        self.kind = "pull"
        self.residual += [float(x) for x in self._fetch(residual_buf, n)]
        self.changed += [int(x) for x in self._fetch(changed_buf, n)]
        if residual_parts is not None:
            self.residual_parts += [
                [float(x) for x in row]
                for row in self._fetch(residual_parts, n)]
        if changed_parts is not None:
            self.changed_parts += [
                [int(x) for x in row]
                for row in self._fetch(changed_parts, n)]

    # -- per-part attribution (round 13) -------------------------------

    def num_parts(self) -> int:
        rows = (self.edges_parts if self.kind == "push"
                else self.changed_parts)
        return len(rows[0]) if rows else 0

    def part_totals(self) -> list[int] | None:
        """Per-part WORK totals over the run — frontier out-edges for
        push (the relax work each part contributed), changed-vertex
        counts for pull.  Sums over parts bitwise-equal the scalar
        ``edges_sum``/``changed_sum`` (integer sums of the same
        device-side values, reduced part-first instead of all at
        once; on graphs past 2^32 edges per iteration the scalar's
        device uint32 wraps while these host totals stay exact — the
        validators compare mod 2^32).  None without per-part data."""
        rows = (self.edges_parts if self.kind == "push"
                else self.changed_parts)
        if not rows:
            return None
        return [sum(r[p] for r in rows) for p in range(len(rows[0]))]

    def imbalance(self) -> float | None:
        """The imbalance index: max/mean of the per-part work totals
        (1.0 = perfectly balanced) — the measured skew signal the
        locality-aware partitioner (ROADMAP item 4) optimizes.  None
        without per-part data or with zero total work."""
        totals = self.part_totals()
        if not totals or sum(totals) == 0:
            return None
        mean = sum(totals) / len(totals)
        return max(totals) / mean

    def imbalance_digest(self) -> dict | None:
        """The ``telemetry.imbalance`` field of a bench metric line
        (scripts/check_bench.py validates it against the counter
        digest): {kind, index, parts} or None."""
        totals = self.part_totals()
        imb = self.imbalance()
        if totals is None or imb is None:
            return None
        return {"kind": self.kind, "index": round(imb, 4),
                "parts": totals}

    def parts_lines(self):
        """Human per-part attribution table (CLI -iter-stats replay /
        events_summary's rendering source)."""
        totals = self.part_totals()
        if totals is None:
            return
        metric = "edges" if self.kind == "push" else "changed"
        tot = sum(totals) or 1
        imb = self.imbalance()
        yield (f"per-part {metric} (imbalance "
               f"{'n/a' if imb is None else f'{imb:.3f}'} max/mean):")
        for p, v in enumerate(totals):
            yield f"  part {p}: {v} ({v / tot * 100:.1f}%)"

    def summary(self) -> dict | None:
        """Compact digest for event logs / bench JSON lines /
        resilience.RunReport."""
        if self.kind is None:
            return None
        out = {"kind": self.kind, "iters": len(self),
               "truncated": bool(self.truncated)}
        if self.kind == "push":
            if self.frontier:
                out.update(frontier_last=self.frontier[-1],
                           frontier_max=max(self.frontier),
                           frontier_sum=sum(self.frontier),
                           edges_sum=sum(self.edges))
        elif self.residual:
            out.update(residual_first=self.residual[0],
                       residual_last=self.residual[-1],
                       changed_last=self.changed[-1],
                       changed_sum=sum(self.changed))
        totals = self.part_totals()
        if totals is not None:
            imb = self.imbalance()
            out["parts"] = len(totals)
            out["parts_edges" if self.kind == "push"
                else "parts_changed"] = totals
            if imb is not None:
                out["imbalance"] = round(imb, 4)
        return out

    def replay_lines(self):
        """Per-iteration lines in the stepwise -verbose format (push)
        or residual form (pull) — what made 'verbose forces the slow
        stepwise path' unnecessary."""
        if self.kind == "push":
            for i, (f, e) in enumerate(zip(self.frontier, self.edges),
                                       1):
                yield f"iter {i}: frontier={f} edges={e}"
        elif self.kind == "pull":
            for i, (r, c) in enumerate(zip(self.residual, self.changed),
                                       1):
                yield f"iter {i}: residual={r:.6e} changed={c}"
        if self.truncated:
            yield (f"... counters truncated (buffer filled before the "
                   f"run finished)")


@dataclasses.dataclass(frozen=True)
class Telemetry:
    """The pair of sinks a run path consults.  Either may be None;
    ``emit`` is then a no-op and engines skip their counter variants."""

    events: EventLog | None = None
    iter_stats: IterStats | None = None

    def emit(self, kind: str, **fields):
        if self.events is not None:
            return self.events.emit(kind, **fields)
        if _OBSERVERS:
            # no event sink, but a flight recorder (or other observer)
            # is installed: the ring still sees the trail
            ev = make_event(kind, fields)
            _notify(ev)
            return ev
        return None


_NULL = Telemetry()
_current: contextvars.ContextVar[Telemetry] = contextvars.ContextVar(
    "lux_tpu_telemetry", default=_NULL)

# per-kind occurrence counters behind emit_sampled (process-global:
# the sampler kinds it throttles are process-wide trails)
_SAMPLED: dict = {}
_SAMPLED_LOCK = threading.Lock()


def emit_sampled(kind: str, every: int = 1, **fields):
    """Throttled ``current().emit`` for high-frequency observability
    kinds (round 22: the memory sampler fires at EVERY segment
    boundary, and a long converge would otherwise swamp the event log
    with ``mem_sample`` lines).  Emits occurrence 0, every, 2*every,
    ... of ``kind`` and drops the rest; each emitted event carries
    ``sampled_skipped`` (events suppressed since the last emitted
    one) so a reader can tell throttling from a silent sampler.
    ``every=1`` is a plain emit with ``sampled_skipped=0``."""
    every = max(1, int(every))
    with _SAMPLED_LOCK:
        n = _SAMPLED.get(kind, 0)
        _SAMPLED[kind] = n + 1
    if n % every:
        return None
    return current().emit(kind, sampled_skipped=min(n, every - 1),
                          **fields)


def current() -> Telemetry:
    """The active Telemetry handle (a null no-op one by default)."""
    return _current.get()


@contextlib.contextmanager
def use(events: EventLog | None = None,
        iter_stats: IterStats | None = None):
    """Scope a Telemetry handle: every run path entered inside the
    block (engines, segmented drivers, supervisor, timing helpers)
    emits into it."""
    tel = Telemetry(events=events, iter_stats=iter_stats)
    token = _current.set(tel)
    try:
        yield tel
    finally:
        _current.reset(token)


# ---- spans: the one way the program times a host region ---------------

SPAN_RING = 16384

_RING: collections.deque = collections.deque(maxlen=SPAN_RING)
# records whose counts still hold device scalars, oldest first: a
# later such record settles the ones that have become ready, so a
# long-lived server pins a handful of device buffers, not a ring full
_UNSETTLED: collections.deque = collections.deque()
_SETTLE_LOCK = threading.Lock()
_SPAN_IDS = itertools.count(1)
# id of the enclosing span on this thread (0 = none): a new thread
# starts with a fresh context, so its spans are roots
_enclosing: contextvars.ContextVar[int] = contextvars.ContextVar(
    "lux_tpu_span", default=0)
_HOST_SCALARS = (int, float, bool, str, type(None))


def _record(sid: int, parent: int, name: str, t0: float, t1: float,
            counts: dict) -> None:
    rec = {"id": sid, "parent": parent, "name": name,
           "t0": t0, "t1": t1, "counts": counts}
    _RING.append(rec)
    if not all(isinstance(v, _HOST_SCALARS) for v in counts.values()):
        _settle(wait=False)
        _UNSETTLED.append(rec)
    tel = _current.get()
    if tel.events is not None or _OBSERVERS:
        # a device scalar still in flight goes out as null: emitting
        # must not become the host sync the ring avoids
        ready = {k: (v if isinstance(v, _HOST_SCALARS)
                     else v.item() if _is_ready(v) else None)
                 for k, v in counts.items()}
        tel.emit("span", name=name, id=sid, parent=parent,
                 t0=round(t0, 6), t1=round(t1, 6),
                 seconds=round(t1 - t0, 6), counts=ready)


def _is_ready(v) -> bool:
    """True where reading ``v`` needs no waiting (a jax.Array says
    so itself; anything that cannot say counts as in flight)."""
    ready = getattr(v, "is_ready", None)
    return ready is not None and ready()


def _settle(wait: bool) -> None:
    """Replace device-scalar counts of earlier records by their host
    values, oldest first: all of them (``wait``: a snapshot), or
    those that need no waiting (a later record with device counts).
    A scalar that cannot be read — its device was lost — becomes
    None instead of failing every reader of the ring."""
    with _SETTLE_LOCK:
        while _UNSETTLED:
            counts = _UNSETTLED[0]["counts"]
            late = {k: v for k, v in counts.items()
                    if not isinstance(v, _HOST_SCALARS)}
            if not wait and not all(map(_is_ready, late.values())):
                return
            for k, v in late.items():
                try:
                    counts[k] = v.item()
                except Exception:       # noqa: BLE001
                    counts[k] = None
            _UNSETTLED.popleft()


class Span:
    """One open host region (see the module docstring); use through
    ``span()``.  ``id`` is set on entry."""

    __slots__ = ("name", "counts", "id", "parent", "t0", "_ann",
                 "_token")

    def __init__(self, name: str, counts: dict):
        self.name = name
        self.counts = counts
        self.id = 0

    def count(self, **counts) -> None:
        """Add counts to the record this span will leave."""
        self.counts.update(counts)

    def __enter__(self):
        self.id = next(_SPAN_IDS)
        self.parent = _enclosing.get()
        self._token = _enclosing.set(self.id)
        self._ann = annotation("lux:" + self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _enclosing.reset(self._token)
        _record(self.id, self.parent, self.name, self.t0, t1,
                self.counts)
        return False


def span(name: str, **counts) -> Span:
    """``with telemetry.span("build.pair_plan") as sp: ...;
    sp.count(rows=n)``.  Never fences: a span round asynchronous work
    ends at dispatch."""
    return Span(name, counts)


@contextlib.contextmanager
def under(parent: Span | None):
    """Records made inside are children of ``parent``, which may have
    CLOSED already: work that belongs to a region and runs after it
    (lux_tpu/serve.py: the answers of a segment boundary, fetched
    behind the next segment's dispatch) stays where the readers of
    ``parent``'s children look for it.  ``None``: they are roots,
    whatever span is open."""
    token = _enclosing.set(0 if parent is None else parent.id)
    try:
        yield
    finally:
        _enclosing.reset(token)


def mark(name: str, seconds: float = 0.0, **counts) -> None:
    """A record that ends now.  Zero-length for a count with no
    duration of its own (a cache hit, a loop's iteration counts);
    ``seconds`` is for a duration someone else measured (JAX's own
    compile timers, runtime.watch_compiles)."""
    t = time.perf_counter()
    _record(next(_SPAN_IDS), _enclosing.get(), name, t - seconds, t,
            counts)


def spans() -> list[dict]:
    """Snapshot of the ring, oldest first.  Device-scalar counts that
    are still un-fetched are fetched HERE (and written back, so each
    is fetched once); one whose device is gone reads None."""
    _settle(wait=True)
    return [{**rec, "counts": dict(rec["counts"])}
            for rec in list(_RING)]
