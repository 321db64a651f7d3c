"""Performance observatory: calibrated measurement as a subsystem.

The repo's numbers had been produced by ~20 one-off
``scripts/profile_*.py`` runs (gone since PR 28; git history up to
3651475) and hand-assembled bench artifacts,
while PERF_NOTES documents standing measurement traps — run-to-run
variance between processes, XLA loop-invariant hoisting, timing an
asynchronous dispatch instead of the work — that have each burned a
round.  This
module makes trustworthy measurement a first-class capability with
three pillars (the microbenchmark-driven methodology of the IPU
dissection paper, PAPERS.md, is the exemplar):

1. **Session calibration** (``calibrate``): a fixed-cost reference
   probe — the canonical small-table gather and a pair-dot MXU
   microkernel at PINNED shapes, measured with the trusted recipe
   (loop-dependent inputs, scalar outputs, one jit, host-fetch fence;
   ``timing.loop_bench``) — runs once per process and yields a
   ``Fingerprint``: measured ns/elem vs the canonical PERF_NOTES
   figures, platform/backend, device count, session id and a static
   audit of the probe programs.  Every bench metric line and ledger
   record carries its digest, so a session far off the canon is
   DETECTED AND LABELED ("degraded") instead of silently polluting
   the trajectory; ``scripts/check_bench.py`` rejects metric lines
   from non-"canonical" sessions.

2. **Phase-cost attribution** (``decompose``): the
   profile_cliff/profile_true/profile_owner methodology as a library
   API — one engine iteration split into its ``timed_phases`` phases
   (exchange / gather / reduce / apply, owner ``gen_exchange``, push
   relax/update, dot_reduce), each phase measured median-of-k with a
   MAD noise estimate and compared against ``scalemodel.phase_model``
   predictions RESCALED to this session's measured primitive rate
   (``session_scale``).  Divergence beyond the variance-aware bound
   becomes a typed drift verdict (``drift_slow``/``drift_fast``) and
   a ``drift`` telemetry event; phases without a measured constant
   are honestly ``unmodeled``.

3. **Persistent perf ledger** (``PerfLedger``): an append-only JSONL
   (default ``PERFLEDGER.jsonl``) of calibrated samples — probe
   figures, phase decompositions, bench metric lines, collected
   debts — each stamped with the session fingerprint, plus a
   carried-debt registry (``DEBTS``) encoding the ROADMAP's owed
   on-device measurements so any on-chip session can
   ``collect_debts`` for whichever match its topology.

Round 19 grows the COMM side of each pillar (lux_tpu/comms.py): a
measured link calibration (``calibrate_links`` — ppermute/all_to_all
payload sweeps on the same loop_bench recipe, feeding
``scalemodel.set_measured_link`` on canonical platforms only), a
per-app comm-attribution verdict inside ``decompose`` (the engine's
oracle-checked byte ledger vs the measured exchange phases — the
wire time is a LOWER bound, so a phase beating its own bytes is the
contradiction), and the ici/dcn bandwidth debts.

CLI: ``python -m lux_tpu.observe`` emits a calibrated
phase-decomposition report for all four apps with drift verdicts
(CPU-runnable; tier-1 smoke in tests/test_observe.py).

Reference anchor: the reference's only measurement is -verbose wall
clocks (reference sssp_gpu.cu:513-518); this subsystem is what a
claims-bearing TPU port needs instead.
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
# is the round-8 MODEL (5.5 ns/K per row), carried as a debt below
# until the on-device sweep pins it — it is recorded for trajectory
# but never gates.
CANONICAL = {
    "gather_small_ns": scalemodel.GATHER_SMALL_NS,
    "pair_dot_row_ns": scalemodel.PAIR_DOT_ROW_K_NS * PROBE_DOT_K,
    # paged-gather delivery row (ops/pagegather.py): row fetch + the
    # 128-lane shuffle + the compare-reduce, composed from MEASURED
    # primitive figures (PERF_NOTES round 2: 24 ns/row static fetch,
    # 0.38 ns/elem shuffle, the 150 ns pair-row machinery the paged
    # row shares) — scalemodel.PAGED_ROW_NS.  A model until the
    # on-device A/B lands (DEBTS "paged-gather-ab"); recorded for
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


def drift_verdict(samples, predicted_s, bound: float = DEVIATION_BOUND):
    """Compare measured seconds against a model prediction with a
    variance-aware bound: the base ``bound`` ratio widens by the
    samples' relative MAD (a noisy phase must diverge FURTHER before
    it is called drift — 1.4826*MAD estimates sigma for normal noise).
    Returns "ok" | "drift_slow" | "drift_fast" | "unmodeled"."""
    if predicted_s is None or predicted_s <= 0:
        return "unmodeled"
    m, mad = median_mad(samples)
    if m <= 0:
        return "unmodeled"
    eff = bound * (1.0 + 3.0 * 1.4826 * mad / m)
    ratio = m / predicted_s
    if ratio > eff:
        return "drift_slow"
    if ratio < 1.0 / eff:
        return "drift_fast"
    return "ok"


# ---------------------------------------------------------------------
# pillar 1: session calibration

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
    whatever the host costs on the CPU mesh — which is exactly
    what lets a CPU phase decomposition carry meaningful verdicts."""
    return fp.probe["gather_small_ns"] / fp.canonical["gather_small_ns"]


# ---------------------------------------------------------------------
# pillar 1b: measured link calibration (round 19, lux_tpu/comms.py)

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
# pillar 2: phase-cost attribution

# timed_phases report keys that are counters, not phase seconds
META_KEYS = ("frontier", "bucket", "advances")


@dataclasses.dataclass(frozen=True)
class PhaseCost:
    phase: str
    samples: tuple            # seconds, one per measured iteration
    median_s: float
    mad_s: float
    predicted_s: float | None  # session-scaled model; None = unmodeled
    ratio: float | None        # median / predicted
    verdict: str               # ok | drift_slow | drift_fast | unmodeled


@dataclasses.dataclass(frozen=True)
class AppDecomposition:
    app: str
    engine: str               # "pull" | "push"
    exchange: str
    ne: int
    nv: int
    iters: int
    session: str
    scale: float              # session_scale applied to the model
    phases: tuple             # PhaseCost, report order
    comm: dict | None = None  # round-19 comm attribution (ledger
    #                           bytes, measured exchange phase vs the
    #                           wire lower bound, verdict)

    def as_dict(self) -> dict:
        return {
            "app": self.app, "engine": self.engine,
            "exchange": self.exchange, "ne": self.ne, "nv": self.nv,
            "iters": self.iters, "session": self.session,
            "scale": round(self.scale, 4),
            "comm": self.comm,
            "phases": [{
                "phase": p.phase,
                "median_s": round(p.median_s, 6),
                "mad_s": round(p.mad_s, 6),
                "predicted_s": (None if p.predicted_s is None
                                else round(p.predicted_s, 6)),
                "ratio": (None if p.ratio is None
                          else round(p.ratio, 3)),
                "verdict": p.verdict,
            } for p in self.phases],
        }


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
    # gets a modeled figure instead of None (unmodeled), so decompose
    # grades the contraction's drift like every other phase
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


def decompose(eng, app: str, iters: int = 3,
              fingerprint: Fingerprint | None = None,
              bound: float = DEVIATION_BOUND) -> AppDecomposition:
    """Measure one engine's per-iteration phase split (median-of-
    ``iters`` + MAD per phase) and attribute each phase against the
    session-scaled scalemodel prediction.

    Instrumentation is a pure observer: phases run on their own state
    copies (``timed_phases``), the engine's compiled programs and
    graph arrays are untouched, and a run after ``decompose`` is
    bitwise identical to one without it (tests/test_observe.py, the
    audit no-op proof pattern).  Emits one ``phase_cost`` event per
    phase and a ``drift`` event per non-ok verdict."""
    fp = fingerprint or calibrate()
    scale = session_scale(fp)
    page_scale = None
    if "page_gather_row_ns" in fp.probe:
        page_scale = (fp.probe["page_gather_row_ns"]
                      / fp.canonical["page_gather_row_ns"])
    model = _engine_model(eng, scale, page_scale=page_scale)
    kind = _engine_kind(eng)
    tel = telemetry.current()

    def run_phases(n):
        if kind == "push":
            label, active = eng.init_state()
            _l, _a, rep = eng.timed_phases(label, active, n)
        else:
            _s, rep = eng.timed_phases(eng.init_state(), n)
        return rep

    # Warm with the SAME full iteration trajectory that will be
    # measured: push engines switch sparse->dense phase programs as
    # the frontier evolves, so a one-iteration warmup would leave
    # later phase programs to compile INSIDE the measured window
    # (both runs start from init_state, so the trajectories — and
    # therefore the compiled-program coverage — are identical).
    run_phases(iters)
    report = run_phases(iters)

    # the raw per-iteration report rides the event trail in the CLI's
    # ``phases`` shape (lux_tpu/cli.py), so tracing renders phase
    # spans — and, with the comm_ledger event below, subdivides the
    # exchange phases into per-collective spans — from a decompose
    # run's log exactly like from a CLI -phases run
    tel.emit("phases", app=app, iters=len(report),
             report=[{k: (v if k in META_KEYS else round(float(v), 6))
                      for k, v in entry.items()} for entry in report])

    by_phase: dict[str, list] = {}
    for entry in report:
        for k, v in entry.items():
            if k not in META_KEYS:
                by_phase.setdefault(k, []).append(float(v))

    phases = []
    for name, samples in by_phase.items():
        m, mad = median_mad(samples)
        pred_ns = model.get(name)
        pred = None if pred_ns is None else pred_ns * 1e-9
        verdict = drift_verdict(samples, pred, bound=bound)
        ratio = None if not pred else m / pred
        pc = PhaseCost(phase=name, samples=tuple(samples), median_s=m,
                       mad_s=mad, predicted_s=pred, ratio=ratio,
                       verdict=verdict)
        phases.append(pc)
        tel.emit("phase_cost", app=app, phase=name,
                 median_s=round(m, 6), mad_s=round(mad, 6),
                 predicted_s=None if pred is None else round(pred, 6),
                 verdict=verdict)
        if verdict.startswith("drift"):
            tel.emit("drift", app=app, phase=name, verdict=verdict,
                     measured_s=round(m, 6), predicted_s=round(pred, 6),
                     ratio=round(m / pred, 3), session=fp.session)
    comm = _comm_attribution(eng, app, phases, tel)
    return AppDecomposition(
        app=app, engine=kind, exchange=eng.exchange, ne=int(eng.sg.ne),
        nv=int(eng.sg.nv), iters=iters, session=fp.session,
        scale=scale, phases=tuple(phases), comm=comm)


def _comm_attribution(eng, app: str, phases, tel) -> dict:
    """Round-19 comm verdict: the engine's per-collective byte ledger
    (lux_tpu/comms.ledger_for — oracle- and audit-cross-checked, a
    broken build raises its typed CommLedgerError through here) vs
    the measured exchange-family phases.  The wire time
    (ledger bytes / this session's MEASURED link rate) is a LOWER
    bound on the exchange phase — generation/apply compute rides the
    same phase, so only a phase FASTER than its own bytes is a
    contradiction (``drift_fast``); with no measured link rate the
    verdict is honestly ``unmodeled``, and off-mesh it is
    ``no-comm``."""
    from lux_tpu import comms

    led = comms.ledger_for(eng)
    exch_names = getattr(eng, "COMM_PHASES",
                         ("exchange", "gen_exchange"))
    exch = [p for p in phases if p.phase in exch_names]
    measured = sum(p.median_s for p in exch) if exch else None
    rate = link_rate(led.tier) if led.tier != "local" else None
    pred = None
    if rate and led.bytes_per_iter:
        pred = led.bytes_per_iter / rate
    if led.bytes_per_iter == 0:
        verdict = "no-comm"
    elif pred is None or measured is None:
        verdict = "unmodeled"
    elif measured < pred / DEVIATION_BOUND:
        verdict = "drift_fast"
    else:
        verdict = "ok"
    comm = {
        "bytes_per_iter": led.bytes_per_iter,
        "bytes_per_edge": round(led.bytes_per_edge, 6),
        "messages": led.messages, "tier": led.tier,
        "per_collective": led.per_collective(),
        "audit_eqns": led.audit_eqns,
        "measured_s": None if measured is None else round(measured, 6),
        "predicted_s": None if pred is None else round(pred, 9),
        "verdict": verdict,
    }
    tel.emit("comm_ledger", app=app, exchange=eng.exchange,
             ndev=led.ndev, ne=led.ne, **comm)
    return comm


def render_report(decomps, fp: Fingerprint) -> str:
    """Human report: fingerprint header + one measured-vs-model table
    per app (the consolidated profile_cliff view)."""
    lines = [
        f"session {fp.session}  platform={fp.platform} "
        f"backend={fp.backend} ndev={fp.ndev}  grade={fp.grade}",
        f"probe: gather {fp.probe['gather_small_ns']:.2f} ns/elem "
        f"(canon {fp.canonical['gather_small_ns']:.2f}, "
        f"deviation {fp.deviation:.2f}x)  pair-dot "
        f"{fp.probe['pair_dot_row_ns']:.0f} ns/row "
        f"(modeled canon {fp.canonical['pair_dot_row_ns']:.0f})",
    ]
    for d in decomps:
        lines.append("")
        lines.append(f"== {d.app} ({d.engine}, exchange={d.exchange}, "
                     f"ne={d.ne}, nv={d.nv}, {d.iters} iters, model "
                     f"x{d.scale:.2f}) ==")
        lines.append(f"{'phase':14s} {'median':>10s} {'mad':>9s} "
                     f"{'model':>10s} {'ratio':>7s}  verdict")
        for p in d.phases:
            pred = ("-" if p.predicted_s is None
                    else f"{p.predicted_s * 1e3:9.2f}ms")
            ratio = "-" if p.ratio is None else f"{p.ratio:6.2f}x"
            lines.append(
                f"{p.phase:14s} {p.median_s * 1e3:8.2f}ms "
                f"{p.mad_s * 1e3:7.2f}ms {pred:>10s} {ratio:>7s}  "
                f"{p.verdict}")
        if d.comm is not None:
            c = d.comm
            wire = ("-" if c["predicted_s"] is None
                    else f"{c['predicted_s'] * 1e3:.3f}ms wire")
            lines.append(
                f"comm: {c['bytes_per_iter']} B/iter over "
                f"{c['messages']} collective(s) [{c['tier']}] "
                f"{wire}  {c['verdict']}")
    return "\n".join(lines)


# ---------------------------------------------------------------------
# pillar 3: persistent perf ledger + carried-debt registry

class PerfLedger:
    """Append-only JSONL of calibrated measurement records.

    One record per line: {"schema", "t", "kind", "session",
    "calibration", ...payload}.  Kinds: "probe" (a calibration run),
    "phase" (an AppDecomposition), "bench" (one bench.py metric
    line), "debt" (a collected carried debt).  Records are never
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


@dataclasses.dataclass(frozen=True)
class Debt:
    """One owed on-device measurement (ROADMAP "carried hardware
    debts").  ``needs`` gates on the session fingerprint;
    ``auto`` names an implemented probe ``collect_debts`` can run,
    else the debt is listed as manual with its pointer."""
    id: str
    title: str
    pointer: str              # where the owed number is documented
    platform: str = "tpu"     # "tpu" (canonical platforms) | "any"
    min_ndev: int = 1
    auto: str | None = None   # name of an _debt_* probe, or None


DEBTS = (
    Debt("netflix-pair-run",
         "NetFlix colfilter pair run on device (locality-rich "
         "coverage datapoint)", "PERF_NOTES round-8 pointer 1"),
    Debt("pair-dot-row-k-sweep",
         "sweep PAIR_DOT_ROW_K_NS over K (replaces the modeled "
         "5.5 ns/K)", "PERF_NOTES round 8 (modeled, not swept)",
         auto="_debt_pair_dot_sweep"),
    Debt("fused-exchange-ici-ab",
         "ring_reduce_scatter fused min/max owner exchange A/B over "
         "real ICI — price both sides from the ici-bandwidth-probe's "
         "measured bytes/s against the comm ledger's per-mode byte "
         "counts (lux_tpu/comms.py: the ring ships (ndev-1) x "
         "[P/ndev, ntw] rows, the all_to_all (ndev-1)/ndev x "
         "[P, ntw] + an ndev-way local reduce)",
         "PERF_NOTES round-8 pointers; round 19 (comm observatory)",
         min_ndev=2),
    Debt("ici-bandwidth-probe",
         "measured ICI link rate: ppermute-ring + all_to_all payload "
         "sweeps on the loop_bench recipe (observe.calibrate_links); "
         "on a canonical session the figure FEEDS "
         "scalemodel.set_measured_link, replacing the hardcoded "
         "ICI_BYTES_PER_S in every mesh projection",
         "PERF_NOTES round 19 (comm observatory)", platform="any",
         min_ndev=2, auto="_debt_ici_bandwidth_probe"),
    Debt("dcn-bandwidth-probe",
         "measured inter-slice DCN link rate (the 10-100x thinness "
         "ROADMAP item 3 prices blind today): the same link sweep on "
         "a mesh whose axis crosses slice boundaries — gated until a "
         "session actually spans >= 2 slices",
         "PERF_NOTES round 19 (comm observatory); ROADMAP item 3",
         min_ndev=2, auto="_debt_dcn_bandwidth_probe"),
    Debt("watchdog-ab",
         "health watchdog on/off A/B on the chip",
         "PERF_NOTES round-9 pointer 1"),
    Debt("pod-direct-probe",
         ">60 s single-execution duration probe (does the ~55 s "
         "wall seen on the earlier installation exist on a directly "
         "attached chip?)", "PERF_NOTES round-8 pointer 4"),
    Debt("elastic-shrink-drill",
         "on-device DEVICE_LOSS shrink drill (recompile + "
         "re-shard upload)", "PERF_NOTES round-11 pointer 1",
         min_ndev=2),
    Debt("part-counters-ab",
         "per-part counter variants (round 13, lux_tpu/tracing.py "
         "era) on/off A/B on the chip — CPU A/B is within "
         "noise; the on-device all_gather cost is unmeasured",
         "PERF_NOTES round 13", min_ndev=2),
    Debt("paged-gather-ab",
         "on-device paged-vs-flat delivered-rate A/B at the pinned "
         "probe shapes (ops/pagegather.py): the modeled "
         "~0.57-2 ns/edge paged rate vs the measured 8.96 flat "
         "gather — the round-15 break-even model "
         "(scalemodel.page_gather_ns) is primitive-derived, not yet "
         "measured end-to-end on device",
         "PERF_NOTES round 15 (paged gather)",
         auto="_debt_paged_gather_ab"),
    Debt("reorder-fill-ab",
         "page-aware reorder fill A/B (round 16, lux_tpu/reorder.py "
         "+ native/reorder.cc): measured page_fill none vs "
         "native/hillclimb on the locality-rich community shape plus "
         "the modeled delivered ns/edge both ways — the fill side is "
         "HOST-measured (the probe runs anywhere); the on-device "
         "delivered-GTEPS confirmation rides `bench.py -config "
         "gather-ab -shape community -reorder hillclimb` on the "
         "chip", "PERF_NOTES round 16 (locality harvest)",
         platform="any", auto="_debt_reorder_fill_ab"),
    Debt("pagemajor-route-ab",
         "page-major routed delivery A/B on a real mesh (round 16, "
         "ops/pagegather.pagemajor_owner_deliver): the modeled "
         "full-fill gather rows + all_to_all row routing + "
         "virtual-row reduce (scalemodel.pagemajor_gather_ns / "
         "pagemajor_route_ns) vs the owner scan and the plain paged "
         "path — the split constants (VROW_REDUCE_NS, the ICI row "
         "rate) are primitive-derived, not yet measured end-to-end",
         "PERF_NOTES round 16 (page-major routing)", min_ndev=2),
    Debt("serve-slo-on-device",
         "bench.py -config serve-slo (open-loop Poisson load vs the "
         "continuous-batching Server, scripts/loadgen.py) on the "
         "chip: the latency-vs-offered-rate curve, the saturation "
         "knee and the SLO good fraction are CPU-mesh-measured only; "
         "on-device per-query latency (and the knee's position vs "
         "the ~9/B ns/edge amortization) is unmeasured",
         "PERF_NOTES round 17 (serving observability)"),
    Debt("serve-chaos-on-device",
         "bench.py -config serve-chaos (replicated FleetServer under "
         "open-loop load with a ReplicaKillPlan armed, "
         "lux_tpu/fleet.py) on the chip: the kill-under-load "
         "drill — detect -> re-dispatch -> first retired answer "
         "failover cost, the SLO burn through a real replica loss, "
         "and the brownout shed fraction at the saturation knee are "
         "CPU-mesh-measured only (PERF_NOTES round 18); on-device "
         "the failover also pays recompile/placement for the "
         "survivor's refilled columns, which nothing has measured",
         "PERF_NOTES round 18 (serving resilience)"),
    Debt("batch-sweep-on-device",
         "bench.py -config batch-sweep (B in {1,8,64} k-source SSSP "
         "+ personalized PageRank) on the chip: the modeled "
         "~9/B per-query amortization (scalemodel.per_query_edge_ns, "
         "BATCH_LANE_NS wide-row lane rate) is CPU-A/B'd only (both "
         "runners turn columns over on the device: push PR 25, "
         "pull PR 27)",
         "PERF_NOTES round 14 (query batching)"),
    Debt("live-mutation-on-device",
         "bench.py -config serve-live (live-graph serving: mutation "
         "stream + delta-relax boundaries + epoch-keyed cache + "
         "compaction, lux_tpu/livegraph.py) on the chip: the "
         "per-boundary delta-relax cost (modeled "
         "count x GATHER_SMALL_NS, the compact_economics drag term), "
         "the WAL fsync cadence vs the execution time, and the "
         "compaction pause under real traffic are CPU-measured only "
         "(PERF_NOTES round 20); the incremental-vs-full "
         "revalidation sweep (scripts/sweep_live.py) also wants the "
         "on-device crossover point",
         "PERF_NOTES round 20 (live graphs)"),
    Debt("live-deletion-on-device",
         "the anti-monotone re-seed (lux_tpu/livegraph.py "
         "_revalidate_anti) computes the deletion cone — forward "
         "reachability from every pending anti op's destination — "
         "on the HOST and re-places the re-seeded state; the "
         "deletion sweep (scripts/sweep_live.py -mode delete, "
         "PERF_NOTES round 21) measured that machinery 3-12x "
         "SLOWER than full recompute at CPU scales because RMAT "
         "cones reach 30-70% of the graph from one deleted "
         "destination, so the cone cap's full-recompute fallback "
         "is doing the serving; a device-side cone (frontier BFS "
         "inside one jit) + in-place re-seed is the open lever, "
         "and the crossover wants measuring on the chip",
         "PERF_NOTES round 21 (mutation algebra)"),
    Debt("hbm-watermark-on-device",
         "the round-22 memory observatory's MEASURED leg "
         "(lux_tpu/memwatch.py): every CPU sample wears grade "
         "'modeled' because the CPU backend exposes no "
         "device.memory_stats(); on a session that does, run one "
         "BASELINE ledger config, read the real per-device "
         "peak_bytes_in_use watermark and verdict it against the "
         "unified byte ledger — the first measured-grade "
         "watermark-vs-ledger drift datapoint (and the XLA "
         "temp/padding overhead figure the modeled tolerance only "
         "bounds)",
         "PERF_NOTES round 22 (memory observatory)", platform="tpu",
         auto="_debt_hbm_watermark"),
    Debt("mxu-core-ab",
         "on-device MXU-vs-VPU compare-reduce A/B at the pinned "
         "probe shapes (round 23, ops/tiled.py): the one-hot "
         "contraction sum + the bit-serial tournament max vs the "
         "fused VPU masked reduce at a wide=8 payload — the "
         "scalemodel constants behind use_mxu='auto' and the bench "
         "mxu-ab pair (ONEHOT_TILE_NS, MXU_TILE_NS) are "
         "primitive-derived, and a CPU einsum says nothing about "
         "the systolic array; the measured per-row step-change and "
         "the sum-vs-tournament gap both want a live MXU",
         "PERF_NOTES round 23 (MXU compute core)", platform="tpu",
         auto="_debt_mxu_core_ab"),
)


def match_debts(fp: Fingerprint):
    """Debts this session's topology could collect."""
    out = []
    for d in DEBTS:
        if d.platform == "tpu" and fp.platform not in CANONICAL_PLATFORMS:
            continue
        if fp.ndev < d.min_ndev:
            continue
        out.append(d)
    return out


def _debt_pair_dot_sweep(fp: Fingerprint, clock=time.perf_counter):
    """The PAIR_DOT_ROW_K_NS sweep: the pair-dot probe across K,
    ns/row each — on a canonical platform this replaces the modeled
    5.5 ns/K constant (PERF_NOTES round 8)."""
    sweep = {}
    for k in (1, 4, 8, 16, 20, 32):
        samples, _ = loop_bench(_dot_probe_step, _dot_probe_carry(k),
                                PROBE_LOOP_K, repeats=3, clock=clock)
        m, mad = median_mad(samples)
        sweep[str(k)] = {
            "row_ns": round(m / PROBE_DOT_ROWS * 1e9, 3),
            "mad_ns": round(mad / PROBE_DOT_ROWS * 1e9, 3)}
    return {"debt": "pair-dot-row-k-sweep", "rows": PROBE_DOT_ROWS,
            "sweep": sweep}


def _debt_paged_gather_ab(fp: Fingerprint, clock=time.perf_counter):
    """Paged-vs-flat A/B at the pinned probe shapes: the same
    PROBE_PAGE_ROWS x 128 delivered edges served by (a) the flat
    per-edge gather and (b) the paged row-fetch + lane shuffle —
    ns/edge for both plus the speedup, the number the round-15
    break-even model owes from a live device."""
    import jax.numpy as jnp

    import jax

    from lux_tpu.ops.tiled import chunk_partials

    edges = PROBE_PAGE_ROWS * 128
    rng = np.random.default_rng(3)
    flat_table = jnp.asarray(
        rng.random(PROBE_PAGE_TABLE * 128, np.float32))
    idx = jnp.asarray(rng.integers(
        0, PROBE_PAGE_TABLE * 128,
        (PROBE_PAGE_ROWS, 128)).astype(np.int32))
    rel = jnp.asarray(rng.integers(
        0, 128, (PROBE_PAGE_ROWS, 128)).astype(np.int8))

    def flat_step(carry):
        # the flat side runs the SAME downstream compare-reduce, so
        # the A/B isolates exactly the delivery-stage swap
        t, i, r = carry
        vals = jax.lax.optimization_barrier(jnp.take(t, i, axis=0))
        sv = jnp.sum(chunk_partials(vals, r, 128, "sum"))
        return sv, (t + sv * 1e-30, i, r)

    flat_s, _ = loop_bench(flat_step, (flat_table, idx, rel),
                           PROBE_LOOP_K, repeats=3, clock=clock)
    page_s, _ = loop_bench(_page_probe_step, _page_probe_carry(),
                           PROBE_LOOP_K, repeats=3, clock=clock)
    f_m, f_mad = median_mad(flat_s)
    p_m, p_mad = median_mad(page_s)
    flat_ns = f_m / edges * 1e9
    paged_ns = p_m / edges * 1e9
    return {"debt": "paged-gather-ab", "edges": edges,
            "flat_ns_per_edge": round(flat_ns, 4),
            "flat_mad_ns": round(f_mad / edges * 1e9, 4),
            "paged_ns_per_edge": round(paged_ns, 4),
            "paged_mad_ns": round(p_mad / edges * 1e9, 4),
            "speedup": round(flat_ns / max(paged_ns, 1e-12), 3),
            "method": _page_resolve_method()}


def _debt_reorder_fill_ab(fp: Fingerprint, clock=time.perf_counter):
    """The locality-harvest fill A/B (round 16): build the scrambled
    community shape, measure the plan builder's page_fill under
    none / native / hillclimb reorders (HOST numpy — the objective
    is device-free by construction) and record the modeled delivered
    ns/edge each implies (scalemodel.page_gather_ns), plus what
    ``gather="auto"`` resolves to.  The on-device GTEPS confirmation
    is the gather-ab bench family; this probe pins the fill trail a
    session can always collect."""
    from lux_tpu.convert import community_graph
    from lux_tpu.graph import ShardedGraph
    from lux_tpu.ops.pagegather import plan_paged_stats, resolve_gather
    from lux_tpu.reorder import page_reorder
    from lux_tpu.scalemodel import page_gather_ns

    g = community_graph(scale=14, edge_factor=8, community_scale=8,
                        seed=0)
    out = {"debt": "reorder-fill-ab", "shape": "community14x8",
           "ne": int(g.ne), "orders": {}}
    for method in ("none", "native", "hillclimb"):
        t0 = clock()
        g2, _perm, rep = page_reorder(g, method=method)
        sg = ShardedGraph.build(g2, 1, vpad_align=128)
        st = plan_paged_stats(sg)
        out["orders"][method] = {
            "page_fill": round(float(st["padded_fill"]), 3),
            "page_ratio": round(float(st["page_ratio"]), 4),
            "modeled_ns_per_edge": round(page_gather_ns(
                st["page_ratio"], st["padded_fill"]), 3),
            "auto_resolves": resolve_gather(
                "auto", st, 4 * sg.num_parts * sg.vpad),
            "reorder_s": round(clock() - t0, 2)}
    return out


def _debt_mxu_core_ab(fp: Fingerprint, clock=time.perf_counter):
    """The round-23 MXU A/B at the pinned probe shapes: the SAME
    [rows, 128, 8] wide payload reduced by (a) the fused VPU masked
    reduce and (b) the MXU path — one-hot contraction for sum, the
    bit-serial tournament for max — ns per chunk row for both plus
    the speedup, next to the scalemodel rates the bench mxu-ab pair
    is read against.  Runs on any backend (the CPU figures are the
    honest-negative baseline; only a chip session prices the
    systolic array, hence platform='tpu' on the debt)."""
    import jax.numpy as jnp

    from lux_tpu.ops.tiled import chunk_partials
    from lux_tpu.scalemodel import mxu_reduce_row_ns, vpu_reduce_row_ns

    rows, wide = PROBE_PAGE_ROWS, 8
    rng = np.random.default_rng(23)
    vals = jnp.asarray(rng.random((rows, 128, wide), np.float32))
    rel = jnp.asarray(rng.integers(0, 128, (rows, 128)).astype(np.int8))

    out = {"debt": "mxu-core-ab", "rows": rows, "wide": wide,
           "kinds": {}}
    for kind in ("sum", "max"):
        rec = {}
        for label, um in (("vpu", False), ("mxu", True)):
            def step(carry, _um=um, _kind=kind):
                v, r = carry
                s = jnp.sum(chunk_partials(v, r, 128, _kind,
                                           use_mxu=_um))
                return s, (v + s * 1e-30, r)

            samples, _ = loop_bench(step, (vals, rel), PROBE_LOOP_K,
                                    repeats=3, clock=clock)
            m, mad = median_mad(samples)
            rec[f"{label}_row_ns"] = round(m / rows * 1e9, 3)
            rec[f"{label}_mad_ns"] = round(mad / rows * 1e9, 3)
        rec["speedup"] = round(
            rec["vpu_row_ns"] / max(rec["mxu_row_ns"], 1e-12), 3)
        rec["modeled_vpu_row_ns"] = round(vpu_reduce_row_ns(wide), 2)
        rec["modeled_mxu_row_ns"] = round(
            mxu_reduce_row_ns(wide, kind), 2)
        out["kinds"][kind] = rec
    return out


def collect_debts(fp: Fingerprint, ledger: PerfLedger | None,
                  only=None, clock=time.perf_counter):
    """Run every matched debt with an implemented probe, appending a
    "debt" record per collection; manual debts are returned as
    skipped with their pointer, and a probe returning a STRING is a
    gated probe declining this session (e.g. the DCN probe on a
    single-slice mesh) — skipped with the probe's stated reason, no
    record appended.  Returns (collected records, [(debt_id, reason)
    skipped])."""
    collected, skipped = [], []
    for d in match_debts(fp):
        if only is not None and d.id not in only:
            continue
        if d.auto is None:
            skipped.append((d.id, f"manual: {d.pointer}"))
            continue
        payload = globals()[d.auto](fp, clock=clock)
        if isinstance(payload, str):
            skipped.append((d.id, payload))
            continue
        if ledger is not None:
            collected.append(ledger.append("debt", payload, fp))
        else:
            collected.append(payload)
        telemetry.current().emit("debt_collected", debt=d.id)
    return collected, skipped


def _debt_hbm_watermark(fp: Fingerprint, clock=time.perf_counter):
    """The measured-watermark debt: one BASELINE ledger config run
    on a backend that exposes device.memory_stats(), its real peak
    watermark verdicted against the unified byte ledger
    (memwatch.drift_verdict, grade ``measured``).  Declines on CPU
    sessions — a modeled number recorded under this debt
    would be exactly the grade-masquerade the observatory's grade
    labels exist to prevent."""
    from lux_tpu import audit, memwatch

    if memwatch.device_memory_stats() is None:
        return ("gated: backend exposes no memory_stats "
                "(CPU session) — the measured watermark "
                "needs a real device")
    cfgs = [(label, build) for label, build, led
            in audit.matrix_configs() if led]
    if not cfgs:
        return "gated: no ledger-grade matrix config on this session"
    label, build = cfgs[0]
    eng = build()
    ledger = memwatch.MemoryLedger.for_engine(eng, label)
    trail = memwatch.MemoryTrail(clock=clock)
    jitted, args_thunk = eng.audit_programs()["step"]
    import jax
    out = jitted(*args_thunk())
    jax.block_until_ready(out)
    s = trail.sample(where=f"debt:{label}")
    if s.grade != memwatch.GRADE_MEASURED:
        return "gated: memory_stats vanished between probe and sample"
    v = memwatch.drift_verdict(s.peak_bytes, ledger.total_bytes,
                               grade=s.grade, where=label)
    return {"debt": "hbm-watermark-on-device", "config": label,
            **v}


def _debt_ici_bandwidth_probe(fp: Fingerprint,
                              clock=time.perf_counter):
    """The measured-link debt: run the payload sweeps and record the
    headline rate (fed into scalemodel on canonical platforms by
    calibrate_links itself)."""
    links = calibrate_links(clock=clock)
    if not links:
        return "gated: fewer than 2 devices visible"
    rec = links.get("ici")
    if rec is None:
        # a multi-slice session's all-device mesh measures the DCN
        # bottleneck — recording that under the ICI debt would be the
        # mirror image of the mislabeling the DCN probe gates against
        return ("gated: the all-device mesh axis crosses slices "
                "(tier dcn) — collect dcn-bandwidth-probe instead")
    return {"debt": "ici-bandwidth-probe", **rec}


def _debt_dcn_bandwidth_probe(fp: Fingerprint,
                              clock=time.perf_counter):
    """The inter-slice link debt: only collectable when the visible
    devices actually span >= 2 slices (ROADMAP item 3's pod
    topology); gated otherwise so a single-slice session never
    records an "ICI rate wearing a DCN label"."""
    import jax

    slices = {getattr(d, "slice_index", 0) or 0
              for d in jax.devices()}
    if len(slices) < 2:
        return ("gated: single-slice session — the DCN probe needs "
                "a mesh whose axis crosses slice boundaries")
    links = calibrate_links(clock=clock)
    rec = links.get("dcn")
    if rec is None:
        return "gated: link sweep measured no cross-slice axis"
    return {"debt": "dcn-bandwidth-probe", **rec}


# ---------------------------------------------------------------------
# CLI: python -m lux_tpu.observe

APPS = ("pagerank", "cc", "sssp", "colfilter")


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


def main(argv=None) -> int:
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        prog="python -m lux_tpu.observe",
        description="calibrated phase-decomposition report: session "
                    "probe, per-app measured-vs-scalemodel phase "
                    "costs with drift verdicts, perf-ledger append")
    ap.add_argument("-scale", type=int, default=12,
                    help="RMAT scale of the probe graphs (default 12 "
                         "— attribution reads relative weights, not "
                         "GTEPS, so small graphs suffice on CPU)")
    ap.add_argument("-ef", type=int, default=8, help="edges/vertex")
    ap.add_argument("-np", type=int, default=1, help="partitions")
    ap.add_argument("-pair", type=int, default=None, metavar="T",
                    help="pair-lane threshold (with degree relabel)")
    ap.add_argument("-gather", default="flat",
                    choices=["flat", "paged", "pagemajor", "auto"],
                    help="state-table delivery: 'paged' runs the "
                         "page-binned two-level gather "
                         "(ops/pagegather.py), 'pagemajor' the "
                         "full-row page-major layout (round 16), "
                         "'auto' arbitrates by the scalemodel "
                         "break-even on the plan's measured "
                         "unique-page ratio / fills")
    ap.add_argument("-iters", type=int, default=3,
                    help="measured iterations per phase (median + "
                         "MAD)")
    ap.add_argument("-apps", nargs="+", default=list(APPS),
                    choices=APPS, metavar="APP",
                    help=f"subset of {', '.join(APPS)}")
    ap.add_argument("-events", default=None, metavar="FILE",
                    help="append telemetry events as JSONL")
    ap.add_argument("-ledger", default=LEDGER_DEFAULT, metavar="FILE",
                    help=f"perf ledger path (default "
                         f"{LEDGER_DEFAULT})")
    ap.add_argument("-no-ledger", action="store_true",
                    dest="no_ledger", help="do not append the ledger")
    ap.add_argument("-debts", action="store_true",
                    help="list carried debts matched by this "
                         "session's topology and exit")
    ap.add_argument("-collect-debts", action="store_true",
                    dest="collect_debts",
                    help="run the matched debts with implemented "
                         "probes and append their records")
    args = ap.parse_args(argv)

    events = telemetry.EventLog(args.events) if args.events else None
    ledger = None if args.no_ledger else PerfLedger(args.ledger)
    with telemetry.use(events=events):
        fp = calibrate()
        if fp.grade == "degraded":
            print(f"# WARNING: degraded session — gather probe "
                  f"{fp.deviation:.2f}x off canonical; samples will "
                  f"be labeled, not trusted", file=sys.stderr)
        # the probe record lands in the ledger only when the command
        # MEASURES something (report or debt collection) — a pure
        # -debts listing is read-only
        if ledger is not None and not (args.debts
                                       and not args.collect_debts):
            ledger.append("probe", {"probe": fp.probe}, fp)

        if args.debts or args.collect_debts:
            matched = match_debts(fp)
            if not matched:
                print(f"no carried debts match this session "
                      f"(platform={fp.platform}, ndev={fp.ndev})")
            for d in matched:
                auto = f"auto ({d.auto})" if d.auto else "manual"
                print(f"debt {d.id}: {d.title} [{auto}; {d.pointer}]")
            if args.collect_debts:
                collected, skipped = collect_debts(fp, ledger)
                for rec in collected:
                    print(f"collected {rec['debt']}: "
                          f"{json.dumps(rec.get('sweep', rec))}")
                for did, reason in skipped:
                    print(f"skipped {did}: {reason}")
            if events is not None:
                events.close()
            return 0

        decomps = []
        for app in args.apps:
            if args.gather == "pagemajor" and app == "colfilter":
                # typed engine refusal (K-dim programs keep 'paged');
                # skip loudly instead of failing the whole report
                print(f"# skipping {app}: gather='pagemajor' does "
                      f"not serve K-dim (SDDMM) programs")
                continue
            eng = _build_app_engine(app, args.scale, args.ef, args.np,
                                    args.pair, gather=args.gather)
            d = decompose(eng, app, iters=args.iters, fingerprint=fp)
            decomps.append(d)
            if ledger is not None:
                ledger.append("phase", d.as_dict(), fp)
        print(render_report(decomps, fp))
    if events is not None:
        events.close()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
