"""Communication observatory: the per-collective byte ledger.

ROADMAP item 3 (pod-scale meshes: quantized owner exchange +
resharding over DCN) was blocked on a measurement gap: scalemodel
priced comm with an admitted 2-4x error margin ("comm is permille"),
``phase_model`` left exchange/reduce honestly unmodeled, and the
collective call sites across the engines were audited only for
schedule *shape* (lux_tpu/audit.py collective-schedule), never for
*bytes*.  This module makes communication a measured, cross-checked
quantity, mirroring the PR-7 observatory pattern (calibrate /
attribute / persist) in three pillars:

1. **Static comm ledger** (``ledger_for``): trace the exact per-
   iteration program each engine registered via
   ``engine/auditable.py`` (the "step" variant — the same registry
   the auditor's collective-schedule check consumes), walk the jaxpr
   for every collective eqn (ppermute / all_to_all / psum_scatter /
   reduce_scatter / all_gather / psum / pmin / pmax) and price its
   wire bytes: per-device operand payload x the ring-algorithm hop
   factor x per-iteration multiplicity (scan lengths), classified by
   link tier (intra-slice ICI vs inter-slice DCN from the mesh's
   device slice topology).  The result is cross-checked BOTH against
   an independent NumPy message-count oracle (``oracle_for``:
   predicts the collective multiset from the engine's own layout
   config, never reading the jaxpr) AND against the audit's
   collective-schedule expectations (``audit.engine_spec``) —
   disagreement raises the typed ``CommLedgerError``.

2. **Measured link calibration** (lux_tpu/observe.py
   ``calibrate_links``): ppermute-ring and all_to_all payload sweeps
   on the trusted ``timing.loop_bench`` recipe feed measured link
   bytes/s into ``scalemodel.set_measured_link``, replacing the
   hardcoded ICI_BYTES_PER_S in the mesh projections.

3. **Pod-scale forecaster** (``python -m lux_tpu.comms -project``):
   the item-3 decision table — per flagship shape, comm/compute
   ratio at 1-hop ICI vs a DCN thinness sweep (10-100x), including
   the projected int8/bf16 quantized-exchange savings
   (scalemodel.QUANT_FACTORS, the EQuARX-style block-scaled encoding,
   PAPERS.md) so the quantized-exchange build lands against a priced
   target, not a guess.

Byte convention (documented in ARCHITECTURE.md "Communication
observatory"; the oracle implements the same arithmetic
independently):

  per-device wire bytes of one collective launch, payload X = the
  per-device operand bytes as seen inside shard_map, over an
  ``ndev``-device axis (ring algorithms, the TPU lowering):

    ppermute                        X            (one hop per eqn)
    all_gather                      X * (ndev-1)           (X = shard)
    psum_scatter / reduce_scatter   X * (ndev-1) // ndev
    all_to_all                      X * (ndev-1) // ndev
    psum / pmin / pmax              2 * X * (ndev-1) // ndev   (RS+AG)

``bytes_per_iter`` is the per-DEVICE steady-state wire bytes of one
iteration: unconditional eqns plus, per cond, the heaviest branch
(the sparse/dense switch of the push engines makes branches genuine
alternatives; the ledger prices the worst case and reports every
branch in the breakdown).  ``bytes_per_edge`` is the aggregate wire
cost per edge: bytes_per_iter * ndev / ne.

CLI: ``python -m lux_tpu.comms`` emits one JSON ledger line per
config of the repo audit matrix (CPU-runnable, tracing only — no
compile, no execution); ``-project`` renders the pod forecast table.

Reference anchor: the reference's comm accounting is Legion's region
requirements (reference pull_model.inl:454-461) — declared, never
priced; this module is the pricing the TPU port's mesh claims rest
on.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np

__all__ = [
    "CommLedgerError", "CollectiveEntry", "CommLedger", "ledger_for",
    "ledger_of_jaxpr", "oracle_for", "cross_check", "mesh_tier",
    "shipped_bytes", "bench_digest", "comm_fraction",
    "forecast_table", "main",
]

# collective primitive names as they appear in traced jaxprs; the
# psum_scatter API lowers to a "reduce_scatter" eqn, and a psum of a
# device-varying value inside a VMA-checked shard_map traces as
# "psum_invariant" (same all-reduce on the wire) — normalized below
COLLECTIVE_PRIMS = frozenset({
    "ppermute", "all_to_all", "psum_scatter", "reduce_scatter",
    "all_gather", "psum", "psum_invariant", "pmin", "pmax",
})

_NORMALIZE = {"psum_scatter": "reduce_scatter",
              "psum_invariant": "psum"}


class CommLedgerError(Exception):
    """The comm ledger disagrees with its oracle or with the audit's
    collective-schedule expectations — the per-byte accounting cannot
    be trusted, so nothing downstream (bench comm digest, forecast)
    may consume it.  ``details`` carries the itemized disagreements."""

    def __init__(self, message: str, details=()):
        super().__init__(message)
        self.details = list(details)


@dataclasses.dataclass(frozen=True)
class CollectiveEntry:
    """One collective eqn of the per-iteration program.

    ``payload_bytes`` is the per-device operand size; ``shipped_bytes``
    the per-device wire bytes of ONE launch (hop convention above);
    ``mult`` the per-iteration launch count (product of enclosing scan
    lengths); ``branch`` the cond path ("" = unconditional) — entries
    sharing a branch prefix up to the final ``#i`` are alternatives."""

    prim: str
    shape: tuple
    dtype: str
    payload_bytes: int
    shipped_bytes: int
    mult: int
    tier: str
    branch: str = ""

    def as_dict(self) -> dict:
        return {"prim": self.prim, "shape": list(self.shape),
                "dtype": self.dtype,
                "payload_bytes": self.payload_bytes,
                "shipped_bytes": self.shipped_bytes,
                "mult": self.mult, "tier": self.tier,
                "branch": self.branch}

    def key(self):
        """Comparison key for the oracle cross-check: the branch
        LABELS differ between ledger (jaxpr paths) and oracle
        (semantic names), so identity is (prim, shape, dtype, mult,
        conditional?)."""
        return (self.prim, tuple(self.shape), self.dtype,
                int(self.mult), bool(self.branch))


@dataclasses.dataclass(frozen=True)
class CommLedger:
    """Per-iteration communication bill of one engine configuration."""

    where: str
    ndev: int
    exchange: str
    tier: str                 # link tier of the mesh axis
    ne: int                   # edges (aggregate, as the engine runs)
    entries: tuple            # every CollectiveEntry, branches included
    bytes_per_iter: int       # per-device steady-state wire bytes
    messages: int             # collective launches on the steady path
    audit_eqns: dict          # prim -> flat eqn count over the jaxpr

    @property
    def bytes_per_edge(self) -> float:
        """Aggregate wire bytes per edge: every device ships
        bytes_per_iter while the mesh retires ne edges."""
        return self.bytes_per_iter * self.ndev / max(1, self.ne)

    def per_collective(self) -> list:
        """Breakdown grouped by (prim, branch): launch count, eqn
        count, payload and shipped bytes — the table events_summary
        renders (and audits: the per-prim ``eqns`` sums must match
        ``audit_eqns``, or the published trail contradicts the
        program it claims to describe)."""
        groups: dict = {}
        for e in self.entries:
            k = (e.prim, e.branch)
            g = groups.setdefault(k, {"prim": e.prim,
                                      "branch": e.branch, "count": 0,
                                      "eqns": 0,
                                      "shipped_bytes": 0,
                                      "payload_bytes": 0,
                                      "tier": e.tier})
            g["count"] += e.mult
            g["eqns"] += 1
            g["shipped_bytes"] += e.shipped_bytes * e.mult
            g["payload_bytes"] += e.payload_bytes * e.mult
        return [groups[k] for k in sorted(groups)]

    def as_dict(self) -> dict:
        return {
            "config": self.where, "ndev": self.ndev,
            "exchange": self.exchange, "tier": self.tier,
            "ne": self.ne, "bytes_per_iter": self.bytes_per_iter,
            "bytes_per_edge": round(self.bytes_per_edge, 6),
            "messages": self.messages,
            "per_collective": self.per_collective(),
            "audit_eqns": dict(sorted(self.audit_eqns.items())),
        }


# ---------------------------------------------------------------------
# hop convention

def shipped_bytes(prim: str, payload: int, ndev: int) -> int:
    """Per-device wire bytes of ONE launch (ring algorithms — see the
    module docstring; integer arithmetic so ledger and oracle compare
    bitwise)."""
    prim = _NORMALIZE.get(prim, prim)
    if ndev <= 1:
        return 0
    if prim == "ppermute":
        return payload
    if prim == "all_gather":
        return payload * (ndev - 1)
    if prim in ("reduce_scatter", "all_to_all"):
        return payload * (ndev - 1) // ndev
    if prim in ("psum", "pmin", "pmax"):
        return 2 * payload * (ndev - 1) // ndev
    raise ValueError(f"unknown collective {prim!r}")


def mesh_tier(mesh) -> str:
    """Link tier of a mesh's axis: "local" (no mesh / one device),
    "ici" (all devices on one slice — intra-slice interconnect), or
    "dcn" (devices span slices: the axis crosses the data-center
    network, 10-100x thinner — the item-3 regime).  Devices without a
    ``slice_index`` attribute (CPU test meshes) count as one slice."""
    if mesh is None or mesh.devices.size <= 1:
        return "local"
    slices = {getattr(d, "slice_index", 0) or 0
              for d in mesh.devices.flat}
    return "dcn" if len(slices) > 1 else "ici"


# ---------------------------------------------------------------------
# pillar 1a: the jaxpr walk

def _aval_bytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()))
    dt = np.dtype(getattr(aval, "dtype", np.float32))
    return int(np.prod(shape, dtype=np.int64)) * dt.itemsize


def _walk(jaxpr, ndev: int, tier: str, entries: list, mult: int = 1,
          branch: str = ""):
    """Collect CollectiveEntry rows and return (steady_bytes,
    steady_msgs) for this jaxpr: unconditional eqns sum; a cond
    contributes its heaviest branch (ties: first)."""
    from lux_tpu.audit import _sub_jaxprs

    bytes_total, msgs_total = 0, 0
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            aval = eqn.invars[0].aval
            payload = sum(_aval_bytes(v.aval) for v in eqn.invars
                          if hasattr(getattr(v, "aval", None), "shape"))
            ship = shipped_bytes(name, payload, ndev)
            entries.append(CollectiveEntry(
                prim=_NORMALIZE.get(name, name),
                shape=tuple(aval.shape), dtype=str(aval.dtype),
                payload_bytes=payload, shipped_bytes=ship, mult=mult,
                tier=tier, branch=branch))
            bytes_total += ship * mult
            msgs_total += mult
            continue
        subs = list(_sub_jaxprs(eqn.params))
        if not subs:
            continue
        if name == "cond":
            best = (0, 0)
            for b, (sub, _) in enumerate(subs):
                got = _walk(sub, ndev, tier, entries, mult,
                            f"{branch}cond[{i}]#{b}")
                best = max(best, got)
            bytes_total += best[0]
            msgs_total += best[1]
        else:
            m2 = mult
            if name == "scan":
                m2 = mult * int(eqn.params.get("length", 1))
            for sub, _ in subs:
                b, m = _walk(sub, ndev, tier, entries, m2, branch)
                bytes_total += b
                msgs_total += m
    return bytes_total, msgs_total


def _flat_eqn_counts(closed) -> dict:
    """prim -> eqn count over the WHOLE jaxpr, via the auditor's own
    walker (lux_tpu/audit._iter_eqns) — the collective-schedule
    check's view of the program, cross-checked against the ledger's
    branch-aware walk so a walker bug cannot miscount silently."""
    from lux_tpu.audit import _iter_eqns

    counts: dict = {}
    for eqn, _, _ in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            name = _NORMALIZE.get(name, name)
            counts[name] = counts.get(name, 0) + 1
    return counts


def ledger_of_jaxpr(closed, ndev: int, tier: str = "ici",
                    where: str = "<jaxpr>", exchange: str = "?",
                    ne: int = 0) -> CommLedger:
    """Build a CommLedger from one traced ClosedJaxpr (the engine-free
    entry: synthetic programs, tests)."""
    entries: list = []
    steady_bytes, steady_msgs = _walk(closed.jaxpr, ndev, tier,
                                      entries)
    led = CommLedger(
        where=where, ndev=ndev, exchange=exchange, tier=tier, ne=ne,
        entries=tuple(entries), bytes_per_iter=steady_bytes,
        messages=steady_msgs, audit_eqns=_flat_eqn_counts(closed))
    # internal consistency: the branch-aware walk and the auditor's
    # flat walk must see the same eqn multiset (mult collapses scans,
    # so compare entry counts per prim against flat eqn counts)
    flat_entries: dict = {}
    for e in led.entries:
        flat_entries[e.prim] = flat_entries.get(e.prim, 0) + 1
    if flat_entries != led.audit_eqns:
        raise CommLedgerError(
            f"{where}: ledger walk saw {flat_entries} collective "
            f"eqns but the audit walker sees {led.audit_eqns} — the "
            f"two jaxpr walks disagree", [
                f"ledger={flat_entries}", f"audit={led.audit_eqns}"])
    return led


# ---------------------------------------------------------------------
# pillar 1b: the NumPy message-count oracle

def _engine_kind(eng) -> str:
    return "push" if hasattr(eng, "converge") else "pull"


def _push_msg_dtype(eng, lab_dtype):
    """Owner-message dtype of a push engine: relax on the label dtype
    (abstract eval — mirrors PushEngine._dense_parts_owner)."""
    import jax

    weighted = any(k in eng.arrays
                   for k in ("own_w", "own_pg_w", "own_pm_w"))
    w = (jax.ShapeDtypeStruct((1, 1), np.float32) if weighted
         else None)
    return jax.eval_shape(
        lambda v, wt: eng.program.relax(v, wt),
        jax.ShapeDtypeStruct((1, 1), lab_dtype), w).dtype


def _owner_acc_shape(eng, trail) -> tuple:
    """[P, ntw] + trail — the accumulated-contribution operand the
    owner exchange routes (ops/owner.owner_contribs /
    ops/pagegather.paged_owner_contribs)."""
    P = int(eng.sg.num_parts)
    if eng.page_plan is not None:
        ntw = int(eng.page_plan.n_tiles) * 128 // P
    else:
        ntw = int(eng.owner.n_tiles) * 128
    return (P, ntw) + tuple(trail)


def oracle_for(eng) -> list:
    """Predict the step program's collective multiset from the
    engine's OWN configuration — numpy/host metadata only, never the
    jaxpr.  Returns [CollectiveEntry] with semantic branch labels
    ("sparse"/"dense"); cross_check compares on ``key()``."""
    import jax

    ndev = eng.ndev
    tier = mesh_tier(getattr(eng, "mesh", None))
    if ndev <= 1:
        return []
    sg = eng.sg
    kind = _engine_kind(eng)
    P_local = int(sg.num_parts) // ndev
    pagemajor = (eng.page_plan is not None
                 and eng.page_plan.mode == "pagemajor")

    def entry(prim, shape, dtype, branch="", mult=1):
        dt = np.dtype(dtype)
        payload = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
        # independent arithmetic, deliberately spelled out (module
        # docstring convention) rather than shared with the ledger
        if prim == "ppermute":
            ship = payload
        elif prim == "all_gather":
            ship = payload * (ndev - 1)
        elif prim in ("reduce_scatter", "all_to_all"):
            ship = payload * (ndev - 1) // ndev
        else:                              # psum / pmin / pmax
            ship = 2 * payload * (ndev - 1) // ndev
        return CollectiveEntry(
            prim=prim, shape=tuple(int(s) for s in shape),
            dtype=str(np.dtype(dtype)), payload_bytes=payload,
            shipped_bytes=ship, mult=mult, tier=tier, branch=branch)

    if kind == "pull":
        sds = eng._audit_state_sds
        trail = tuple(sds.shape[2:])
        state_dt = sds.dtype
        shard = (P_local, int(sg.vpad)) + trail
        out = []
        if eng.exchange == "gather":
            out.append(entry("all_gather", shard, state_dt))
            return out
        msg_dt = eng.delivery.msg_dtype(eng._msg, sds)
        if pagemajor:
            Mg = int(eng.page_plan.route)
            shape = (P_local, int(sg.num_parts), Mg, 128) + trail
            out.append(entry("all_to_all", shape, msg_dt))
        else:
            acc = _owner_acc_shape(eng, trail)
            reduce_kind = getattr(eng.program, "reduce", "sum")
            if reduce_kind == "sum":
                out.append(entry("reduce_scatter", acc, msg_dt))
            elif eng.owner_minmax_fused:
                ring = (acc[0] // ndev,) + acc[1:]
                for _ in range(ndev - 1):
                    out.append(entry("ppermute", ring, msg_dt))
            else:
                out.append(entry("all_to_all", acc, msg_dt))
        if eng.pairs is not None:
            out.append(entry("all_gather", shard, state_dt))
        return out

    # push: step = psum(count) -> body -> psum(new count); the body is
    # a sparse/dense cond when the sparse queue machinery is usable
    lab_sds, _act_sds = eng._audit_state_sds
    trail = tuple(lab_sds.shape[2:])
    lab_dt = lab_sds.dtype
    shard = (P_local, int(sg.vpad)) + trail
    out = [entry("psum", (), np.int32), entry("psum", (), np.int32)]
    use_sparse, _limit, pull = eng._sparse_mode()
    dense_branch = "dense" if use_sparse else ""

    dense = []
    if eng.exchange == "owner":
        msg_dt = _push_msg_dtype(eng, lab_dt)
        if pagemajor:
            Mg = int(eng.page_plan.route)
            shape = (P_local, int(sg.num_parts), Mg, 128) + trail
            dense.append(entry("all_to_all", shape, msg_dt,
                               branch=dense_branch))
        else:
            acc = _owner_acc_shape(eng, trail)
            reduce_kind = getattr(eng.program, "reduce", "sum")
            if reduce_kind == "sum":
                dense.append(entry("reduce_scatter", acc, msg_dt,
                                   branch=dense_branch))
            elif eng.owner_minmax_fused:
                ring = (acc[0] // ndev,) + acc[1:]
                for _ in range(ndev - 1):
                    dense.append(entry("ppermute", ring, msg_dt,
                                       branch=dense_branch))
            else:
                dense.append(entry("all_to_all", acc, msg_dt,
                                   branch=dense_branch))
        if eng.pairs is not None:
            dense.append(entry("all_gather", shard, lab_dt,
                               branch=dense_branch))
    else:
        dense.append(entry("all_gather", shard, lab_dt,
                           branch=dense_branch))
        dense.append(entry("all_gather", shard, np.bool_,
                           branch=dense_branch))
    out += dense

    if pull:
        # the bottom-up step's choice (PushEngine._choose), before the
        # cond: the largest part's unreached count, their edges, and
        # the guard's best frontier label against the worst reached
        out.append(entry("pmax", (), np.int32))
        out.append(entry("psum", (), np.uint32))
        out.append(entry("pmin", (), lab_dt))
        out.append(entry("pmax", (), lab_dt))
    if use_sparse:
        # the ladder (engine/frontier.py): one alternative per queue
        # rung, holding the queue exchange at that rung's size, the
        # pmax'd out-edge total that picks the budget rung, the psum
        # of the fill counts and the pmin'd processed prefix
        for i, Q in enumerate(eng.queue_rungs):
            rung = f"sparse#q{i}"
            out.append(entry("all_gather", (P_local, Q), np.int32,
                             branch=rung))
            out.append(entry("all_gather", (P_local, Q), lab_dt,
                             branch=rung))
            out.append(entry("pmax", (), np.int32, branch=rung))
            # the fill counts: every part's items and expanded edges
            out.append(entry("psum", (2,), np.uint32, branch=rung))
            out.append(entry("pmin", (), np.int32, branch=rung))
            if pull:
                # the pulled buffer of the gathered queue, combined
                # across the mesh whichever way the slots ran
                out.append(entry(
                    "pmin" if eng.program.reduce == "min" else "pmax",
                    (int(sg.num_parts) * Q,), lab_dt, branch=rung))
    del jax
    return out


def _oracle_totals(entries) -> tuple:
    """(bytes_per_iter, messages) under the same steady-state
    convention as the ledger walk: unconditional entries sum; branch
    groups contribute their heaviest alternative."""
    uncond_b = sum(e.shipped_bytes * e.mult for e in entries
                   if not e.branch)
    uncond_m = sum(e.mult for e in entries if not e.branch)
    groups: dict = {}
    for e in entries:
        if e.branch:
            g = groups.setdefault(e.branch, [0, 0])
            g[0] += e.shipped_bytes * e.mult
            g[1] += e.mult
    if groups:
        best = max(groups.values(), key=lambda g: g[0])
        uncond_b += best[0]
        uncond_m += best[1]
    return uncond_b, uncond_m


def cross_check(ledger: CommLedger, oracle_entries,
                where: str = "") -> None:
    """Raise CommLedgerError unless the traced ledger and the NumPy
    oracle agree on (a) the collective multiset — prim, per-device
    shape, dtype, multiplicity, conditionality — and (b) the
    steady-state byte/message totals, bitwise."""
    import collections

    where = where or ledger.where
    details = []
    led_keys = collections.Counter(e.key() for e in ledger.entries)
    ora_keys = collections.Counter(e.key() for e in oracle_entries)
    if led_keys != ora_keys:
        for k in sorted(set(led_keys) | set(ora_keys)):
            lk, ok = led_keys.get(k, 0), ora_keys.get(k, 0)
            if lk < ok:
                details.append(f"oracle predicts {ok}x {k} but the "
                               f"traced program carries {lk}")
            elif lk > ok:
                details.append(f"traced program carries {lk}x {k} "
                               f"but the oracle predicts {ok}")
    ora_bytes, ora_msgs = _oracle_totals(oracle_entries)
    if ledger.bytes_per_iter != ora_bytes:
        details.append(f"bytes_per_iter {ledger.bytes_per_iter} != "
                       f"oracle {ora_bytes}")
    if ledger.messages != ora_msgs:
        details.append(f"messages {ledger.messages} != oracle "
                       f"{ora_msgs}")
    if details:
        raise CommLedgerError(
            f"comm ledger disagrees with the NumPy oracle for "
            f"{where}: " + "; ".join(details[:6])
            + (f" (+{len(details) - 6} more)"
               if len(details) > 6 else ""), details)


def _check_against_audit(eng, ledger: CommLedger) -> None:
    """The ledger's eqn set must satisfy the collective-schedule
    expectations the auditor enforces (lux_tpu/audit.engine_spec) —
    the two subsystems read the same registry, so disagreement means
    one of them is lying about the program."""
    import jax

    from lux_tpu import audit

    jitted, thunk = eng.audit_variant("step")
    args = thunk()
    first = args[0] if hasattr(args[0], "dtype") else \
        jax.ShapeDtypeStruct((), np.float32)
    spec = audit.engine_spec(eng, first)
    counts = ledger.audit_eqns
    details = []
    if spec.expect_reduce_scatter and counts.get("reduce_scatter",
                                                 0) < 1:
        details.append("audit expects a psum_scatter/reduce_scatter; "
                       "the ledger found none")
    if spec.expect_all_to_all and counts.get("all_to_all", 0) < 1:
        details.append("audit expects an all_to_all; the ledger "
                       "found none")
    if spec.ppermute_hops is not None \
            and counts.get("ppermute", 0) != spec.ppermute_hops:
        details.append(f"audit expects {spec.ppermute_hops} ppermute "
                       f"hops; the ledger counted "
                       f"{counts.get('ppermute', 0)}")
    if details:
        raise CommLedgerError(
            f"comm ledger contradicts the audit collective-schedule "
            f"expectations for {ledger.where}: "
            + "; ".join(details), details)


def ledger_for(eng, where: str | None = None,
               check: bool = True) -> CommLedger:
    """The comm ledger of one built engine: trace its registered
    "step" variant (per-iteration program; tracing only — no compile,
    no execution) and price every collective.  ``check=True`` (the
    default) cross-checks against the NumPy oracle and the audit
    expectations, raising CommLedgerError on any disagreement."""
    from lux_tpu import audit

    where = where or type(eng).__name__
    jitted, thunk = eng.audit_variant("step")
    closed = audit.trace_variant(jitted, thunk())
    led = ledger_of_jaxpr(
        closed, ndev=eng.ndev,
        tier=mesh_tier(getattr(eng, "mesh", None)), where=where,
        exchange=eng.exchange, ne=int(eng.sg.ne))
    if check:
        cross_check(led, oracle_for(eng), where=where)
        _check_against_audit(eng, led)
    return led


# ---------------------------------------------------------------------
# bench digest (the metric-line ``comm`` field)

def comm_fraction(ledger: CommLedger,
                  compute_ns: float | None) -> float:
    """Modeled comm share of one iteration at the engine's own
    placement: wire seconds (ledger bytes at the tier's link rate —
    measured when calibrated, canonical otherwise) over wire +
    compute seconds.  In [0, 1] by construction; 0.0 off-mesh."""
    from lux_tpu import scalemodel

    if ledger.bytes_per_iter <= 0:
        return 0.0
    comm_s = ledger.bytes_per_iter / scalemodel.link_bytes_per_s(
        ledger.tier)
    if not compute_ns or compute_ns <= 0:
        return 1.0
    return comm_s / (comm_s + compute_ns * 1e-9)


def bench_digest(ledger: CommLedger,
                 compute_ns: float | None = None) -> dict:
    """The compact ``comm`` field bench.py metric lines carry
    (scripts/check_bench.py validates it and rejects the
    contradictions)."""
    return {
        "errors": 0,
        "ndev": ledger.ndev,
        "exchange": ledger.exchange,
        "tier": ledger.tier,
        "bytes_per_iter": ledger.bytes_per_iter,
        "comm_bytes_per_edge": round(ledger.bytes_per_edge, 6),
        "messages": ledger.messages,
        "comm_frac": round(comm_fraction(ledger, compute_ns), 6),
    }


# ---------------------------------------------------------------------
# pillar 3: pod-scale forecaster

# flagship shapes (PERF_NOTES trajectory): (label, scale, edge factor)
FLAGSHIP_SHAPES = (("rmat21", 21, 16), ("rmat25", 25, 16),
                   ("rmat27", 27, 16))


def forecast_rows(ne: int, nv: int, chips: int,
                  thinness=(1, 10, 30, 100),
                  quants=("f32", "bf16", "int8")) -> list:
    """Comm/compute decision rows for one shape at one chip count:
    per (link thinness, quantization), the per-iteration comm
    seconds, comm/compute ratio and projected aggregate GTEPS (owner
    exchange pricing — scalemodel.project_pull's compute terms, the
    ledger's wire convention for bytes)."""
    from lux_tpu import scalemodel

    base = scalemodel.project_pull(ne, nv, chips)
    state_bytes = nv * 4
    # the owner reduce_scatter routes the [P, ntw] contribution table:
    # each chip ships ~one state table x (C-1)/C per iteration — the
    # same figure the per-config ledger measures on real programs
    wire = state_bytes * (chips - 1) // chips
    ici = scalemodel.link_bytes_per_s("ici")
    rows = []
    for thin in thinness:
        for q in quants:
            qf = scalemodel.QUANT_FACTORS[q]
            comm_s = wire * qf / (ici / thin)
            iter_s = base.compute_s + comm_s
            gteps = ne / iter_s / 1e9
            rows.append({
                "chips": chips, "thinness": thin, "quant": q,
                "comm_ms": comm_s * 1e3,
                "ratio": comm_s / base.compute_s,
                "gteps": gteps,
                "gteps_per_chip": gteps / chips,
            })
    return rows


def forecast_table(shapes=FLAGSHIP_SHAPES, chip_counts=(8, 64, 256),
                   thinness=(1, 10, 30, 100),
                   quants=("f32", "bf16", "int8")) -> str:
    """The item-3 decision table (markdown): where does the owner
    exchange stop being permille — and how much of the DCN cliff does
    the quantized exchange buy back."""
    from lux_tpu import scalemodel

    lines = [
        f"(link: ici {scalemodel.link_bytes_per_s('ici'):.3g} B/s "
        f"{'measured' if scalemodel.measured_link('ici') else 'model'}"
        f"; thinness 1 = 1-hop ICI, N = DCN at ICI/N; quant factors "
        f"{scalemodel.QUANT_FACTORS})",
        "",
        "| shape | chips | thinness | quant | comm ms/iter | "
        "comm/compute | GTEPS | GTEPS/chip |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for label, scale, ef in shapes:
        nv = 1 << scale
        ne = nv * ef
        for chips in chip_counts:
            for r in forecast_rows(ne, nv, chips, thinness, quants):
                lines.append(
                    f"| {label} | {r['chips']} | {r['thinness']}x | "
                    f"{r['quant']} | {r['comm_ms']:.3f} | "
                    f"{r['ratio']:.4f} | {r['gteps']:.3f} | "
                    f"{r['gteps_per_chip']:.4f} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------
# CLI: python -m lux_tpu.comms

def run_matrix(configs=None, verbose: bool = False,
               emit_events: bool = True) -> list:
    """One checked ledger per audit-matrix config (lux_tpu/audit.py's
    matrix — the same engines the repo-wide audit traces).  Returns
    the ledger dicts; a config whose ledger fails its cross-check
    raises CommLedgerError (nothing downstream may consume it)."""
    from lux_tpu import audit, telemetry

    out = []
    for label, build, _ledger in audit.matrix_configs():
        if configs is not None and label not in configs:
            continue
        eng = build()
        led = ledger_for(eng, where=label, check=True)
        d = led.as_dict()
        d["oracle_ok"] = True
        out.append(d)
        if emit_events:
            telemetry.current().emit("comm_ledger", **d)
        if verbose:
            print(f"# {label}: {led.messages} msg/iter, "
                  f"{led.bytes_per_iter} B/iter ({led.tier})")
    return out


def main(argv=None) -> int:
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        prog="python -m lux_tpu.comms",
        description="communication observatory: per-collective byte "
                    "ledger over the repo audit matrix (tracing "
                    "only, CPU-runnable) and the pod-scale comm "
                    "forecast")
    ap.add_argument("-configs", nargs="+", default=None,
                    metavar="NAME",
                    help="subset of audit-matrix config labels "
                         "(default: all)")
    ap.add_argument("-project", action="store_true",
                    help="emit the item-3 pod-scale decision table "
                         "(DCN thinness sweep x quantized-exchange "
                         "savings) instead of the per-config ledger")
    ap.add_argument("-events", default=None, metavar="FILE",
                    help="append comm_ledger telemetry events as "
                         "JSONL (scripts/events_summary.py renders "
                         "them)")
    ap.add_argument("-calibrate-links", action="store_true",
                    dest="calibrate_links",
                    help="run the measured link probes first "
                         "(observe.calibrate_links; needs >= 2 "
                         "devices) so the forecast prices from this "
                         "session's measured bytes/s")
    ap.add_argument("-v", "-verbose", action="store_true",
                    dest="verbose")
    args = ap.parse_args(argv)

    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass          # backend already initialized (pytest conftest)

    from lux_tpu import telemetry

    events = telemetry.EventLog(args.events) if args.events else None
    rc = 0
    with telemetry.use(events=events):
        if args.calibrate_links:
            from lux_tpu import observe
            links = observe.calibrate_links()
            if links:
                for tier, rec in links.items():
                    print(f"# link {tier}: "
                          f"{rec['bytes_per_s']:.3g} B/s measured "
                          f"({rec['prim']}, payload "
                          f"{rec['payload_bytes']} B)",
                          file=sys.stderr)
            else:
                print("# link calibration skipped (needs >= 2 "
                      "devices)", file=sys.stderr)
        if args.project:
            print(forecast_table())
        else:
            try:
                for d in run_matrix(configs=args.configs,
                                    verbose=args.verbose):
                    print(json.dumps(d), flush=True)
            except CommLedgerError as e:
                print(f"ERROR: {e}", file=sys.stderr)
                rc = 1
    if events is not None:
        events.close()
    return rc


if __name__ == "__main__":
    import sys
    sys.exit(main())
