"""Session calibration and the bench ledger: what ``bench.py``,
``chip_smoke.py``, ``cli -calibrate`` and ``comms -project`` read.

1. **Session calibration** (``calibrate``): a fixed-cost reference
   probe — the canonical small-table gather, a pair-dot MXU
   microkernel and a paged-gather row at PINNED shapes, measured with
   the trusted recipe (loop-dependent inputs, scalar outputs, one jit,
   host-fetch fence; ``timing.loop_bench``) — runs once per process
   and yields a ``Fingerprint``: measured ns/elem vs the canonical
   PERF_NOTES figures, platform/backend, device count, session id and
   a static audit of the probe programs.  Every bench metric line and
   ledger record carries its digest, so a session far off the canon is
   DETECTED AND LABELED ("degraded") instead of silently polluting
   the trajectory; ``scripts/check_bench.py`` rejects metric lines
   from non-"canonical" sessions.

2. **Link calibration** (``calibrate_links``, lux_tpu/comms.py):
   ppermute/all_to_all payload sweeps on the same loop_bench recipe,
   feeding ``scalemodel.set_measured_link`` on canonical platforms
   only.

3. **Bench ledger** (``PerfLedger``): an append-only JSONL (default
   ``PERFLEDGER.jsonl``, written by ``bench.py -ledger``) of
   calibrated samples, each stamped with the session fingerprint.
   It is NOT the benchmark's record: that is ``PERF_LEDGER.jsonl``,
   which the driver of ``benchmarks/`` keeps.

Where an iteration's time goes is not answered here: it is read from
the named scopes of the program that runs, in a device trace
(lux_tpu/profiling.py, ``benchmarks/trace_reduce.py``).  Measurements
still owed on the chip are rows of PERF.md section 7.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from statistics import median

import numpy as np

from lux_tpu import scalemodel, telemetry
from lux_tpu.timing import loop_bench

SCHEMA = 1
LEDGER_DEFAULT = "PERFLEDGER.jsonl"
LEDGER_KINDS = ("probe", "phase", "bench", "debt")

# Platforms the canonical figures apply to.
CANONICAL_PLATFORMS = frozenset({"tpu"})

# Probe shapes are PINNED: a calibration figure is only comparable
# across sessions if every session measures the identical program.
PROBE_GATHER_LOGV = 18        # 1 MB f32 table — small-table regime
PROBE_GATHER_N = 1 << 20      # 1M indices per step
PROBE_DOT_ROWS = 256          # pair-dot rows per step
PROBE_DOT_K = 20              # colfilter's K (the modeled 5.5 ns/K)
PROBE_PAGE_ROWS = 2048        # paged-gather delivery rows per step
PROBE_PAGE_TABLE = 256        # pages in the probe's page buffer
PROBE_LOOP_K = 8              # steps inside the one jitted loop
DEVIATION_BOUND = 3.0         # outside [1/3, 3]x of canon = degraded

# Canonical figures (ns per unit) for the probe kernels.  The gather
# figure is MEASURED (PERF_NOTES round 2, 8.96 ns/elem v5e small
# table) and is the figure that grades a session; the pair-dot figure
# is the round-8 MODEL (5.5 ns/K per row), not pinned by an on-device
# sweep — it is recorded for trajectory but never gates.
CANONICAL = {
    "gather_small_ns": scalemodel.GATHER_SMALL_NS,
    "pair_dot_row_ns": scalemodel.PAIR_DOT_ROW_K_NS * PROBE_DOT_K,
    # paged-gather delivery row (ops/pagegather.py): row fetch + the
    # 128-lane shuffle + the compare-reduce, composed from MEASURED
    # primitive figures (PERF_NOTES round 2: 24 ns/row static fetch,
    # 0.38 ns/elem shuffle, the 150 ns pair-row machinery the paged
    # row shares) — scalemodel.PAGED_ROW_NS.  A model until an
    # on-device A/B lands (PERF.md section 7); recorded for
    # trajectory and the paged phase pricing, never grading.
    "page_gather_row_ns": scalemodel.PAGED_ROW_NS,
}


# ---------------------------------------------------------------------
# robust statistics

def median_mad(xs):
    """(median, median-absolute-deviation) — the variance-aware pair
    every observatory comparison uses instead of mean/stdev (timing
    outliers are heavy-tailed; one 10x sample must not drag the
    estimate, PERF_NOTES round 5)."""
    xs = list(xs)
    if not xs:
        raise ValueError("median_mad of an empty sample set")
    m = median(xs)
    return m, median(abs(x - m) for x in xs)


# ---------------------------------------------------------------------
# session calibration

@dataclasses.dataclass(frozen=True)
class Fingerprint:
    """One process's calibration: measured probe rates vs canon.

    ``grade``: "canonical" (canonical platform, gather probe within
    ``DEVIATION_BOUND`` of the PERF_NOTES figure), "degraded"
    (canonical platform, outside the bound in EITHER direction — a
    chip faster than the canon lands here too, so read ``deviation``
    before calling it a fault), "uncalibrated" (a platform with no
    canonical figures,
    e.g. the CPU test mesh — measured rates recorded, never compared
    into the trajectory)."""

    schema: int
    session: str              # telemetry.session_id()
    pid: int
    backend: str              # jax.default_backend()
    platform: str             # jax.devices()[0].platform
    ndev: int
    probe: dict               # measured {name_ns, name_mad_ns}
    canonical: dict           # the figures of record (CANONICAL)
    deviation: float          # gather probe / canonical gather
    grade: str
    audit: dict               # static audit digest of the probe jaxprs

    def digest(self) -> dict:
        """The compact JSON field metric lines and ledger records
        carry (scripts/check_bench.py validates it)."""
        return {
            "schema": self.schema, "session": self.session,
            "platform": self.platform, "backend": self.backend,
            "ndev": self.ndev, "grade": self.grade,
            "deviation": round(self.deviation, 4),
            "probe": {k: round(v, 3) for k, v in self.probe.items()},
            "audit": {"errors": self.audit.get("errors", 0),
                      "warnings": self.audit.get("warnings", 0)},
        }


def _gather_probe_carry():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)            # pinned seed: one program
    v = 1 << PROBE_GATHER_LOGV
    table = jnp.asarray(rng.random(v, np.float32))
    idx = jnp.asarray(
        rng.integers(0, v, PROBE_GATHER_N).astype(np.int32))
    return table, idx


def _gather_probe_step(carry):
    import jax.numpy as jnp
    table, idx = carry
    sv = jnp.sum(jnp.take(table, idx, axis=0))
    return sv, (table + sv * 1e-30, idx)


def _page_resolve_method() -> str:
    """The paged resolution formulation this platform runs: the
    Pallas lane-shuffle kernel on real TPUs, the plain XLA
    take_along_axis everywhere else (matching the engines'
    resolve_reduce_method split, engine/delivery.py)."""
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _page_probe_carry():
    import jax.numpy as jnp
    rng = np.random.default_rng(2)           # pinned seed: one program
    table = jnp.asarray(
        rng.random((PROBE_PAGE_TABLE, 128), np.float32))
    slot = rng.integers(0, PROBE_PAGE_TABLE, PROBE_PAGE_ROWS)
    lane = rng.integers(0, 128, (PROBE_PAGE_ROWS, 128))
    sl = (slot[:, None].astype(np.uint32) << np.uint32(7)) \
        | lane.astype(np.uint32)
    rel = rng.integers(0, 128, (PROBE_PAGE_ROWS, 128)).astype(np.int8)
    return table, jnp.asarray(sl), jnp.asarray(rel)


def _page_probe_step(carry):
    """One paged DELIVERY row pipeline per row: page-row fetch, lane
    shuffle, compare-reduce — the full composed primitive the engines
    run per row (ops/pagegather.paged_partial), so the session scale
    this probe yields prices paged phases in THIS session's ns."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.ops.pagegather import lane_resolve
    from lux_tpu.ops.tiled import chunk_partials
    table, sl, rel = carry
    row_slot = jax.lax.shift_right_logical(
        sl[:, 0], jnp.uint32(7)).astype(jnp.int32)
    rows = jnp.take(table, row_slot, axis=0)
    vals = lane_resolve(rows, sl, _page_resolve_method())
    vals = jax.lax.optimization_barrier(vals)
    partials = chunk_partials(vals, rel, 128, "sum")
    sv = jnp.sum(partials)
    return sv, (table + sv * 1e-30, sl, rel)


def _dot_probe_carry(kdim: int = PROBE_DOT_K):
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    shape = (PROBE_DOT_ROWS, 128, kdim)
    s = jnp.asarray(rng.random(shape, np.float32))
    t = jnp.asarray(rng.random(shape, np.float32))
    return s, t


def _dot_probe_step(carry):
    import jax.numpy as jnp
    s, t = carry
    # the pair-dot delivery's MXU core: D = S @ T^T per row
    d = jnp.einsum("rik,rjk->rij", s, t)
    sv = jnp.sum(d)
    return sv, (s + sv * 1e-30, t)


def _audit_probe_programs():
    """Static audit of the probe jaxprs (lux_tpu/audit.py): the
    calibration subsystem must satisfy the same structural invariants
    it exists to referee — a probe with a hoistable loop body or a
    baked-in multi-MB constant would measure nothing."""
    import jax
    import jax.numpy as jnp

    from lux_tpu import audit

    findings = []
    for name, step, carry in (
            ("gather", _gather_probe_step, _gather_probe_carry()),
            ("pair_dot", _dot_probe_step, _dot_probe_carry()),
            ("page_gather", _page_probe_step, _page_probe_carry())):
        def run(c0, _step=step):
            def body(_, c):
                acc, cur = c
                sv, cur = _step(cur)
                return acc + sv, cur
            return jax.lax.fori_loop(0, PROBE_LOOP_K, body,
                                     (jnp.float32(0), c0))[0]
        closed = jax.make_jaxpr(run)(carry)
        findings += audit.audit_jaxpr(closed,
                                      where=f"observe.probe_{name}")
    return audit.digest(findings, mode="error"), findings


def _grade(platform: str, deviation: float,
           bound: float = DEVIATION_BOUND) -> str:
    if platform not in CANONICAL_PLATFORMS:
        return "uncalibrated"
    if deviation > bound or deviation < 1.0 / bound:
        return "degraded"
    return "canonical"


_FP: Fingerprint | None = None


def calibrate(force: bool = False, clock=time.perf_counter,
              repeats: int = 3) -> Fingerprint:
    """Run the reference probe ONCE per process (cached; ``force``
    re-runs, e.g. after a suspected mid-session slowdown)
    and return the session Fingerprint.  Cost: two tiny jits + a few
    warm re-executions — O(100 ms) on-chip, a couple of seconds on
    the CPU test mesh.  ``clock`` is injectable for deterministic
    tests."""
    global _FP
    if _FP is not None and not force:
        return _FP
    import jax

    gather_s, _ = loop_bench(_gather_probe_step, _gather_probe_carry(),
                             PROBE_LOOP_K, repeats=repeats, clock=clock)
    dot_s, _ = loop_bench(_dot_probe_step, _dot_probe_carry(),
                          PROBE_LOOP_K, repeats=repeats, clock=clock)
    page_s, _ = loop_bench(_page_probe_step, _page_probe_carry(),
                           PROBE_LOOP_K, repeats=repeats, clock=clock)
    g_m, g_mad = median_mad(gather_s)
    d_m, d_mad = median_mad(dot_s)
    p_m, p_mad = median_mad(page_s)
    probe = {
        "gather_small_ns": g_m / PROBE_GATHER_N * 1e9,
        "gather_small_mad_ns": g_mad / PROBE_GATHER_N * 1e9,
        "pair_dot_row_ns": d_m / PROBE_DOT_ROWS * 1e9,
        "pair_dot_row_mad_ns": d_mad / PROBE_DOT_ROWS * 1e9,
        "page_gather_row_ns": p_m / PROBE_PAGE_ROWS * 1e9,
        "page_gather_row_mad_ns": p_mad / PROBE_PAGE_ROWS * 1e9,
    }
    deviation = probe["gather_small_ns"] / CANONICAL["gather_small_ns"]
    platform = jax.devices()[0].platform
    audit_digest, _findings = _audit_probe_programs()
    fp = Fingerprint(
        schema=SCHEMA, session=telemetry.session_id(), pid=os.getpid(),
        backend=jax.default_backend(), platform=platform,
        ndev=len(jax.devices()), probe=probe, canonical=dict(CANONICAL),
        deviation=deviation, grade=_grade(platform, deviation),
        audit=audit_digest)
    telemetry.current().emit("calibration", **fp.digest())
    _FP = fp
    return fp


def fingerprint_digest(fp: Fingerprint | None = None) -> dict:
    """The ``calibration`` field for a metric line: digest of ``fp``
    (or of this process's cached/fresh calibration)."""
    return (fp or calibrate()).digest()


def session_scale(fp: Fingerprint) -> float:
    """Factor rescaling the scalemodel's canonical-TPU constants into
    THIS session's nanoseconds: the measured gather probe over the
    canonical figure.  ~1.0 where the chip reproduces the canon;
    whatever the host costs on the CPU mesh."""
    return fp.probe["gather_small_ns"] / fp.canonical["gather_small_ns"]


# ---------------------------------------------------------------------
# measured link calibration (round 19, lux_tpu/comms.py)

# payload sizes (f32 elems PER DEVICE) for the link sweep: small
# enough that the CPU mesh finishes in ~a second, large enough that
# the top size amortizes launch overhead into a bandwidth figure
LINK_PAYLOAD_ELEMS = (1 << 12, 1 << 16, 1 << 20)

# tier -> measured record of THIS session ({"bytes_per_s", "prim",
# "payload_bytes", "sweep"}); None until calibrate_links ran
_LINKS: dict = {}


def _link_step(mesh, prim: str):
    """One collective launch per loop step, payload riding the carry
    (the loop_bench contract: loop-dependent, never hoistable).  The
    probe measures the wire, so the collective lives HERE rather than
    in ops/ — the scope lint is deliberately waived."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    nd = int(mesh.devices.size)
    axis = mesh.axis_names[0]
    perm = [(j, (j + 1) % nd) for j in range(nd)]

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(axis),
                       out_specs=P(axis))
    def hop(v):
        if prim == "ppermute":
            # audit: allow(collective-scope) — the link probe IS the
            # measurement; there is no engine program to ride
            return jax.lax.ppermute(v, axis, perm)
        blk = v.reshape(nd, -1)
        # audit: allow(collective-scope) — link probe (see above)
        return jax.lax.all_to_all(blk, axis, split_axis=0,
                                  concat_axis=0,
                                  tiled=True).reshape(v.shape)

    def step(carry):
        y = hop(carry)
        sv = jnp.sum(y.reshape(-1)[:8].astype(jnp.float32))
        return sv, y

    return step


def calibrate_links(payload_elems=LINK_PAYLOAD_ELEMS,
                    repeats: int = 3,
                    clock=time.perf_counter) -> dict:
    """Measure this session's link rate with ppermute-ring and
    all_to_all payload sweeps on the trusted ``timing.loop_bench``
    recipe (one jit, loop-dependent carry, scalar-fetch fence).
    Returns {tier: record} — empty when fewer than 2 devices are
    visible.  The headline ``bytes_per_s`` is the peak measured
    ppermute rate (per-device wire bytes over seconds/step).  On a
    CANONICAL platform the figure is fed into
    ``scalemodel.set_measured_link`` so the mesh projections price
    from the measurement (the round-19 replacement for the hardcoded
    ICI_BYTES_PER_S); elsewhere it is recorded and labeled, never fed
    — a CPU-mesh memcpy rate must not price a pod."""
    import jax

    if len(jax.devices()) < 2:
        return {}
    from lux_tpu import comms, scalemodel
    from lux_tpu.parallel.mesh import make_mesh

    nd = len(jax.devices())
    mesh = make_mesh(nd)
    tier = comms.mesh_tier(mesh)
    platform = jax.devices()[0].platform
    sweep = {}
    best = (0.0, None, 0)
    for prim in ("ppermute", "all_to_all"):
        step = _link_step(mesh, prim)
        for elems in payload_elems:
            rng = np.random.default_rng(11)
            carry = rng.random(nd * int(elems), np.float32)
            samples, _ = loop_bench(step, carry, PROBE_LOOP_K,
                                    repeats=repeats, clock=clock)
            m, mad = median_mad(samples)
            payload = int(elems) * 4       # per-device f32 bytes
            wire = comms.shipped_bytes(prim, payload, nd)
            rate = wire / m if m > 0 else 0.0
            sweep[f"{prim}@{payload}"] = {
                "s_per_step": round(m, 6),
                "mad_s": round(mad, 6),
                "bytes_per_s": round(rate, 1)}
            if prim == "ppermute" and rate > best[0]:
                best = (rate, prim, payload)
    rec = {"tier": tier, "bytes_per_s": best[0], "prim": best[1],
           "payload_bytes": best[2], "ndev": nd,
           "platform": platform, "sweep": sweep,
           "fed_scalemodel": platform in CANONICAL_PLATFORMS}
    _LINKS[tier] = rec
    if rec["fed_scalemodel"] and best[0] > 0:
        scalemodel.set_measured_link(tier, best[0])
    telemetry.current().emit(
        "link_calibration", tier=tier, ndev=nd, platform=platform,
        bytes_per_s=round(best[0], 1), prim=best[1],
        payload_bytes=best[2], fed_scalemodel=rec["fed_scalemodel"])
    return dict(_LINKS)


def link_rate(tier: str = "ici") -> float | None:
    """This session's measured link rate for ``tier`` (bytes/s), or
    None when calibrate_links never measured one."""
    rec = _LINKS.get(tier)
    return rec["bytes_per_s"] if rec else None


# ---------------------------------------------------------------------
# the scalemodel's per-phase model of one engine (bench.py's model_ns)

def _engine_kind(eng) -> str:
    return "push" if hasattr(eng, "converge") else "pull"


def _engine_model(eng, scale: float,
                  page_scale: float | None = None) -> dict:
    """scalemodel.phase_model priced from the engine's OWN layout
    stats (pair coverage/inflation, owner chunk inflation, K-dim,
    the paged plan's page ratio/fill) — the same stats the engines
    already report."""
    cov, row_infl = 0.0, 1.0
    if eng.pairs is not None:
        cov = float(eng.pairs.stats["coverage"])
        row_infl = max(1.0, float(eng.pairs.stats["inflation"]))
    chunk_infl = 1.2
    owner = getattr(eng, "owner", None)
    if owner is not None and getattr(owner, "stats", None):
        chunk_infl = max(1.0, float(owner.stats["chunk_inflation"]))
    state_bytes = getattr(eng.program, "state_bytes", None) or 4
    kdim = max(1, int(state_bytes) // 4)
    dot = getattr(eng.program, "edge_value_from_dot", None) is not None
    pp = getattr(eng, "page_plan", None)
    paged = pp is not None
    # MXU reduce pricing (round 23): the engine's RESOLVED use_mxu
    # flag and its K x B payload width — with it the "reduce" phase
    # gets a modeled figure instead of None (unmodeled)
    from lux_tpu.engine.delivery import mxu_wide_of
    return scalemodel.phase_model(
        engine=_engine_kind(eng), exchange=eng.exchange,
        ne=int(eng.sg.ne), nv=int(eng.sg.nv), kdim=kdim,
        pair_coverage=cov, pair_row_inflation=row_infl,
        chunk_inflation=chunk_infl,
        state_bytes_per_vertex=int(state_bytes), dot=dot, scale=scale,
        use_mxu=bool(getattr(eng, "use_mxu", False)),
        mxu_wide=mxu_wide_of(eng.program),
        reduce_kind=getattr(eng.program, "reduce", "sum"),
        paged=paged,
        page_ratio=float(pp.stats["page_ratio"]) if paged else 0.0,
        page_fill=float(pp.stats.get("padded_fill",
                                     pp.stats["fill"]))
        if paged else 128.0,
        page_scale=page_scale,
        page_mode=pp.mode if paged else "paged",
        page_g_fill=float(pp.stats.get("padded_g_fill", 128.0))
        if paged else 128.0)


# ---------------------------------------------------------------------
# the bench ledger

class PerfLedger:
    """Append-only JSONL of calibrated measurement records.

    One record per line: {"schema", "t", "kind", "session",
    "calibration", ...payload}.  Kinds: "probe" (a calibration run),
    "phase" (a per-phase decomposition), "bench" (one bench.py
    metric line), "debt" (a collected carried debt); "phase" and
    "debt" have had no writer since PR 42 and are kept for files on
    disk.  Records are never
    rewritten — a degraded session's records stay, labeled by their
    fingerprint, which is the whole point."""

    def __init__(self, path: str = LEDGER_DEFAULT):
        self.path = path

    def append(self, kind: str, payload: dict,
               fingerprint: Fingerprint | None = None) -> dict:
        if kind not in LEDGER_KINDS:
            raise ValueError(f"unknown ledger kind {kind!r} "
                             f"(one of {LEDGER_KINDS})")
        fp = fingerprint or calibrate()
        rec = {"schema": SCHEMA, "t": round(time.time(), 6),
               "kind": kind, "session": fp.session,
               "calibration": fp.digest(), **payload}
        with open(self.path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        return rec


def iter_ledger(path: str):
    """Yield (lineno, record|None, error|None) per ledger line."""
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                yield i, None, f"unparseable JSON ({e})"
                continue
            if not isinstance(rec, dict):
                yield i, None, "record is not a JSON object"
                continue
            yield i, rec, None


def validate_ledger(path: str) -> list[str]:
    """Schema audit of a PERFLEDGER.jsonl; returns error strings
    (empty = clean).  Every record must carry schema/kind/session and
    a calibration digest whose grade is a known label — an unlabeled
    sample in the trajectory is exactly what the observatory exists
    to prevent."""
    errs = []
    n = 0
    for i, rec, err in iter_ledger(path):
        if err:
            errs.append(f"line {i}: {err}")
            continue
        n += 1
        if rec.get("schema") != SCHEMA:
            errs.append(f"line {i}: schema={rec.get('schema')!r} "
                        f"(expected {SCHEMA})")
        kind = rec.get("kind")
        if kind not in LEDGER_KINDS:
            errs.append(f"line {i}: unknown kind {kind!r}")
        if not isinstance(rec.get("session"), str) \
                or not rec.get("session"):
            errs.append(f"line {i}: missing session id")
        cal = rec.get("calibration")
        if not isinstance(cal, dict):
            errs.append(f"line {i}: missing calibration digest")
        else:
            if cal.get("grade") not in ("canonical", "degraded",
                                        "uncalibrated"):
                errs.append(f"line {i}: calibration.grade="
                            f"{cal.get('grade')!r} unknown")
            dev = cal.get("deviation")
            if not isinstance(dev, (int, float)) \
                    or isinstance(dev, bool) or not dev == dev \
                    or dev <= 0:
                errs.append(f"line {i}: calibration.deviation="
                            f"{dev!r} must be a finite positive "
                            f"number")
        if kind == "phase" and not isinstance(rec.get("phases"), list):
            errs.append(f"line {i}: phase record without a phases "
                        f"list")
        if kind == "bench" and not isinstance(rec.get("metric"), str):
            errs.append(f"line {i}: bench record without a metric "
                        f"name")
        if kind == "debt" and not isinstance(rec.get("debt"), str):
            errs.append(f"line {i}: debt record without a debt id")
    if n == 0 and not errs:
        errs.append("empty ledger")
    return errs


# ---------------------------------------------------------------------
# the four apps at a small R-MAT shape (tracing.run_smoke)

def _build_app_engine(app: str, scale: int, ef: int, num_parts: int,
                      pair_threshold: int | None,
                      gather: str = "flat"):
    from lux_tpu.convert import rmat_graph

    g = rmat_graph(scale=scale, edge_factor=ef, seed=0)
    # per-app graph prep FIRST (cc symmetrizes, colfilter weights),
    # then one relabel of the graph that will actually run
    if app == "cc":
        from lux_tpu.apps import components
        from lux_tpu.graph import Graph
        s, dst = components.symmetrize(*g.edge_arrays())
        g = Graph.from_edges(s, dst, g.nv)
    elif app == "colfilter":
        rng = np.random.default_rng(1)
        g.weights = rng.integers(1, 6, size=g.ne).astype(np.int32)
    elif app not in ("pagerank", "sssp"):
        raise ValueError(f"unknown app {app!r}")
    if pair_threshold is not None:
        from lux_tpu.graph import pair_relabel
        g, _perm, starts = pair_relabel(g, num_parts,
                                        pair_threshold=pair_threshold)
    else:
        starts = None
    kw = dict(num_parts=num_parts, pair_threshold=pair_threshold,
              starts=starts, gather=gather)
    if app == "pagerank":
        from lux_tpu.apps import pagerank
        return pagerank.build_engine(g, **kw)
    if app == "cc":
        from lux_tpu.apps import components
        return components.build_engine(g, **kw)
    if app == "sssp":
        from lux_tpu.apps import sssp
        return sssp.build_engine(g, start_vertex=0, **kw)
    from lux_tpu.apps import colfilter
    return colfilter.build_engine(g, **kw)
