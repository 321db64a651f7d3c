"""Compile-time program auditor: jaxpr invariant checks.

The reference enforces its execution contract through C++ templates —
an app that violates the pull/push task shapes does not compile
(reference core/graph.h:146-225).  lux_tpu's equivalent contracts
lived only as prose in CLAUDE.md/PERF_NOTES.md and regressed silently;
this module machine-checks them by tracing every engine program
variant to a jaxpr on the CPU backend (tracing never executes or
compiles device code, so auditing a billion-edge engine costs about
the same as a toy one — the single size-dependent step is one host
``program.init`` per engine to learn the state shape, whose result
the next ``init_state`` call reuses) and walking the jaxpr for
structural violations.

Check catalogue (check name -> typed error):

  gather-budget        GatherBudgetError
      Per fused-loop body, the number of per-element gathers whose
      operand IS the flat vertex-state table [num_parts*vpad, ...]
      must not exceed the engine's budget: dense push masks inactive
      sources into the label vector PRE-gather so one gather serves
      the iteration (PERF_NOTES: the gather is ~90% of an iteration);
      owner exchange exists to have ZERO table gathers (per-shard
      gathers ride the lax.scan); pair-lane row fetches are
      row-granular by design (tile-reshaped operand) and exempt.
  const-bytes          ConstBytesError
      Closed-over constants above a byte ceiling: large baked-in
      constants bloat the program and its compile (they are
      serialized into the executable and into every cache entry), so
      graph arrays must arrive as jit ARGUMENTS.  Caught here, at
      trace time, before anything compiles.
  dtype-discipline     DtypeDisciplineError
      No f64/complex anywhere, and no silent promotion past the
      program's state dtype (any aval wider than
      max(4, state itemsize) bytes).
  loop-invariant       LoopInvariantError (warning severity)
      Expensive ops (gather/dot_general/scatter/sort) inside a
      while/scan body whose inputs are ALL loop-invariant: XLA hoists
      them out of the loop, so a benchmark timing that loop measures
      nothing (the CLAUDE.md benchmarking trap, now a warning class).
  collective-schedule  CollectiveScheduleError
      The owner exchange must be a lax.scan over source parts (a
      vmapped batched gather still pays the big-table rate,
      PERF_NOTES.md round 3); sum exchanges reduce-scatter; fused
      min/max rings take exactly ndev-1 ppermute hops of full ndev
      cycles (cf. the collective-schedule discipline of portable
      reduce-scatter lowerings, PAPERS.md).
  callback-in-loop     CallbackInLoopError
      No pure_callback/io_callback/debug_callback primitives inside
      fused loops — a host round-trip per iteration is the exact
      failure mode the fused designs exist to avoid.
  identity-init        IdentityInitError
      Scatter-reduce inits must equal the reduction identity: a
      scatter-min onto a zeros-initialized buffer silently clamps
      every positive result (the one-identity/sentinel convention,
      CLAUDE.md).  Only statically-resolvable (broadcast-of-literal)
      inits are judged; reductions onto carried state are semantic
      relaxations and pass.
  ledger-drift         LedgerDriftError
      XLA ``memory_analysis`` of the CPU-compiled step vs
      ``graph.memory_report(...)`` within a stated tolerance, so the
      priced ledger can never drift from the compiler again.
      Tolerance rationale: the ledger prices epad-based lower bounds
      while the compiled arrays carry chunk/tile padding (measured
      1.1-1.3x on bench-shaped graphs, 10x+ on toy graphs whose
      padding dominates) — the check exists to catch order-of-
      magnitude drift, not byte equality, and is only meaningful on
      graphs dense enough that edges dominate padding.

Usage:

  engine-build audit (CLI ``-audit warn|error``, engines'
  ``audit=``):  every lazily-compiled loop variant (run/run_until/
  converge x stats/health) is traced and checked at build time.
  ``python -m lux_tpu.audit`` runs the repo-wide engine matrix on the
  CPU backend (no TPU needed) — the tier-1 test wraps the same entry.

  Exemptions, two granularities:
  - per-eqn source pragma ``# audit: allow(check-name)`` on the
    offending line (or the comment block directly above it), honored
    through jaxpr source info for the eqn-anchored checks:
    gather-budget, dtype-discipline, loop-invariant,
    callback-in-loop, identity-init.  scripts/lint_lux.py honors the
    same syntax for its AST findings.
  - ``allow={"check-name", ...}`` at the audit call site, for the
    program-level checks (const-bytes, collective-schedule,
    ledger-drift) that aggregate over the whole jaxpr and have no
    single source line to carry a pragma.  Record WHY next to the
    call.
"""

from __future__ import annotations

import dataclasses
import warnings

import numpy as np

__all__ = [
    "AuditError", "AuditWarning", "Finding", "ProgramSpec",
    "GatherBudgetError", "ConstBytesError", "DtypeDisciplineError",
    "LoopInvariantError", "CollectiveScheduleError",
    "CallbackInLoopError", "IdentityInitError", "LedgerDriftError",
    "audit_jaxpr", "audit_engine", "engine_spec", "check_ledger",
    "matrix_configs", "run_repo_audit", "main",
]

# ---------------------------------------------------------------------
# typed errors

class AuditError(Exception):
    """Base of every auditor violation; ``findings`` carries the full
    list behind a raised (possibly aggregated) error."""
    check = "audit"

    def __init__(self, message: str, findings=()):
        super().__init__(message)
        self.findings = list(findings)


class GatherBudgetError(AuditError):
    check = "gather-budget"


class ConstBytesError(AuditError):
    check = "const-bytes"


class DtypeDisciplineError(AuditError):
    check = "dtype-discipline"


class LoopInvariantError(AuditError):
    check = "loop-invariant"


class CollectiveScheduleError(AuditError):
    check = "collective-schedule"


class CallbackInLoopError(AuditError):
    check = "callback-in-loop"


class IdentityInitError(AuditError):
    check = "identity-init"


class LedgerDriftError(AuditError):
    check = "ledger-drift"


ERROR_TYPES = {cls.check: cls for cls in (
    GatherBudgetError, ConstBytesError, DtypeDisciplineError,
    LoopInvariantError, CollectiveScheduleError, CallbackInLoopError,
    IdentityInitError, LedgerDriftError)}

CHECKS = tuple(sorted(ERROR_TYPES))


class AuditWarning(UserWarning):
    """Category used for ``mode='warn'`` reporting."""


@dataclasses.dataclass(frozen=True)
class Finding:
    check: str          # one of CHECKS
    severity: str       # "error" | "warn"
    where: str          # "<engine>.<variant>" or caller-supplied
    detail: str

    def __str__(self):
        return f"[{self.check}] {self.where}: {self.detail}"


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """Expectations for one traced program.

    table_shape     aval shape of the FLAT vertex-state table (the
                    all-parts [num_parts*vpad, ...] array the dense
                    per-edge gather reads); None skips gather-budget.
    gather_budget   max table gathers per fused-loop body.
    const_bytes_max closed-over constant ceiling (program-bloat guard).
    state_itemsize  bytes per element of the iterated state; avals
                    wider than max(4, this) fail dtype-discipline.
    require_scan_len  owner exchange: a lax.scan of exactly this
                    length (the per-source-part generation scan)
                    whose body gathers from a per-part state SHARD
                    (operand shape ``require_scan_shard_shape``) must
                    exist; None skips.  The shard-gather requirement
                    stops the fused iteration loop (fori -> scan)
                    from satisfying the check by length coincidence.
    require_scan_shard_shape  aval shape of one state shard
                    ([vpad, ...]); used with require_scan_len.
    ppermute_hops   fused min/max ring: exact ppermute eqn count
                    (ndev - 1); None skips.
    ring_size       devices on the ring (each ppermute perm must be a
                    full ring_size cycle); None skips.
    expect_reduce_scatter  mesh sum owner exchange: require a
                    reduce_scatter/psum_scatter eqn.
    expect_all_to_all      mesh min/max (non-fused) owner exchange:
                    require an all_to_all eqn and forbid ppermute.
    """
    table_shape: tuple | None = None
    gather_budget: int | None = None
    const_bytes_max: int = 1 << 20
    state_itemsize: int = 4
    require_scan_len: int | None = None
    require_scan_shard_shape: tuple | None = None
    ppermute_hops: int | None = None
    ring_size: int | None = None
    expect_reduce_scatter: bool = False
    expect_all_to_all: bool = False
    # paged engines (ops/pagegather.py): the page row-fetch whose
    # operand is the TILE-RESHAPED state table ([T, 128, ...] /
    # [T, 128*K]) IS the iteration's one state-table access — these
    # shapes count against the same gather budget, so a paged dense
    # iteration stays machine-checked at exactly 1 with no pragma.
    # (The plan pads its buffer/row dims to NEVER collide with these
    # shapes, pagegather._pad8_distinct.)
    paged_table_shapes: tuple = ()


# ---------------------------------------------------------------------
# jaxpr walking utilities

def _literal_type():
    from jax.extend import core as jex_core
    return jex_core.Literal


def _sub_jaxprs(params: dict):
    """Every Jaxpr nested in an eqn's params (ClosedJaxpr unwrapped),
    as (jaxpr, consts) pairs — robust across primitives (while, scan,
    cond, pjit, shard_map, custom_* ...)."""
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for x in vs:
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                yield x.jaxpr, x.consts
            elif hasattr(x, "eqns") and hasattr(x, "invars"):
                yield x, ()


LOOP_PRIMS = ("while", "scan")

# ---------------------------------------------------------------------
# source pragmas: ``# audit: allow(check-name)`` on (or just above)
# the offending source line exempts that eqn from ``check-name``,
# with the justification living next to the code it covers — the
# same syntax scripts/lint_lux.py honors for AST-level findings.

import functools as _functools
import re as _re

_PRAGMA_RE = _re.compile(r"#\s*audit:\s*allow\(([a-z-]+)\)")


@_functools.lru_cache(maxsize=256)
def _file_lines(path: str):
    try:
        with open(path) as f:
            return f.readlines()
    except OSError:
        return []


def _eqn_source(eqn):
    """(file_name, line) of the user frame that traced ``eqn``, or
    (None, None) when the eqn carries no traceback.  Deliberately no
    exception guard: a jax that moves this API must fail the audit
    loudly, not silently stop honoring every pragma."""
    from jax._src import source_info_util
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None, None
    return frame.file_name, frame.start_line


def _pragma_allows(eqn, check: str, stack: tuple = ()) -> bool:
    """True when the source line that traced ``eqn`` — or an
    enclosing call eqn's line (see ``_iter_eqns`` on trace caching) —
    or the contiguous comment block directly above either statement,
    carries an explicit ``# audit: allow(check)`` pragma."""
    for e in (eqn,) + tuple(reversed(stack)):
        if _pragma_allows_line(e, check):
            return True
    return False


def _pragma_allows_line(eqn, check: str) -> bool:
    fname, line = _eqn_source(eqn)
    if fname is None or line is None:
        return False
    lines = _file_lines(fname)
    if not 0 < line <= len(lines):
        return False

    def hit(text):
        return any(m.group(1) == check
                   for m in _PRAGMA_RE.finditer(text))

    if hit(lines[line - 1]):
        return True
    ln = line - 2
    while ln >= 0:
        stripped = lines[ln].strip()
        if stripped.startswith("#"):
            if hit(stripped):
                return True
            ln -= 1
        elif not stripped:
            ln -= 1
        else:
            break
    return False


def _where_src(eqn, where: str) -> str:
    fname, line = _eqn_source(eqn)
    if fname is None:
        return where
    import os
    return f"{where} ({os.path.basename(fname)}:{line})"


def _iter_eqns(jaxpr, in_loop: bool = False, stack: tuple = ()):
    """Yield (eqn, in_loop, stack) over ``jaxpr`` and every nested
    jaxpr; ``in_loop`` is True inside any while/scan body (incl. cond
    branches and inner pjits reached from one); ``stack`` is the
    chain of enclosing call eqns (pjit/while/scan/...), innermost
    last — pragma lookups consult it because jax CACHES traced
    sub-jaxprs, so an eqn inside a reused jnp-op trace carries the
    FIRST call site's source info, while its enclosing call eqn
    carries the real one."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop, stack
        inner = in_loop or eqn.primitive.name in LOOP_PRIMS
        for sub, _ in _sub_jaxprs(eqn.params):
            yield from _iter_eqns(sub, inner, stack + (eqn,))


def _outer_loops(jaxpr, path=""):
    """(description, body_jaxpr) for each OUTERMOST while/scan — the
    fused-loop bodies the per-loop budgets apply to.  Nested loops
    (e.g. the owner scan inside a fused while) are audited as part of
    their enclosing body."""
    out = []
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        if name in LOOP_PRIMS:
            for sub, _ in _sub_jaxprs(eqn.params):
                out.append((f"{path}{name}[{i}]", sub))
        else:
            for sub, _ in _sub_jaxprs(eqn.params):
                out.extend(_outer_loops(sub, f"{path}{name}[{i}]/"))
    return out


def _count_prims(jaxpr, names) -> int:
    return sum(1 for eqn, _, _ in _iter_eqns(jaxpr)
               if eqn.primitive.name in names)


# ---------------------------------------------------------------------
# check 1: gather budget

def _table_gathers(jaxpr, table_shape, paged_shapes=()):
    """Gather eqns whose operand aval IS the flat state table (exact
    shape match: per-part arrays are rank+1 batched [P_local, vpad,
    ...], shards are [vpad, ...], pair row fetches are tile-reshaped
    [n_tiles, 128*...] — none collide with [num_parts*vpad, ...]).
    ``paged_shapes`` (ops/pagegather.py engines) adds the tile-
    reshaped table shapes of the page row-fetch, counted against the
    SAME budget: the paged path's page fetch is THE state-table
    access of a dense iteration.  A gather carrying an explicit
    ``# audit: allow(gather-budget)`` source pragma does not count."""
    shapes = {tuple(table_shape)}
    shapes.update(tuple(s) for s in paged_shapes)
    n = 0
    for eqn, _, stack in _iter_eqns(jaxpr):
        if eqn.primitive.name == "gather":
            aval = eqn.invars[0].aval
            if (tuple(aval.shape) in shapes
                    and not _pragma_allows(eqn, "gather-budget",
                                           stack)):
                n += 1
    return n


def check_gather_budget(closed, spec: ProgramSpec, where: str):
    if spec.table_shape is None or spec.gather_budget is None:
        return []
    findings = []
    bodies = _outer_loops(closed.jaxpr) or [("program", closed.jaxpr)]
    for desc, body in bodies:
        n = _table_gathers(body, spec.table_shape,
                           spec.paged_table_shapes)
        if n > spec.gather_budget:
            findings.append(Finding(
                "gather-budget", "error", where,
                f"{n} state-table gathers (operand "
                f"{tuple(spec.table_shape)}) in fused-loop body "
                f"{desc}; budget is {spec.gather_budget} — mask into "
                f"the value vector pre-gather instead of gathering "
                f"twice (PERF_NOTES: the gather is ~90% of an "
                f"iteration)"))
    return findings


# ---------------------------------------------------------------------
# check 2: constvar byte ceiling

def _const_bytes(closed) -> int:
    total = 0
    for c in closed.consts:
        try:
            total += np.asarray(c).nbytes
        except Exception:  # noqa: BLE001 — non-array const (rare)
            continue
    Literal = _literal_type()
    for eqn, _, _ in _iter_eqns(closed.jaxpr):
        for v in eqn.invars:
            if isinstance(v, Literal) and np.ndim(v.val) > 0:
                total += np.asarray(v.val).nbytes
        for sub, consts in _sub_jaxprs(eqn.params):
            for c in consts:
                if hasattr(c, "nbytes"):
                    total += c.nbytes
    return total


def check_const_bytes(closed, spec: ProgramSpec, where: str):
    total = _const_bytes(closed)
    if total <= spec.const_bytes_max:
        return []
    return [Finding(
        "const-bytes", "error", where,
        f"{total} bytes of closed-over constants exceed the "
        f"{spec.const_bytes_max}-byte ceiling — large baked-in "
        f"constants bloat the program and its compile; pass arrays "
        f"as jit arguments")]


# ---------------------------------------------------------------------
# check 3: dtype discipline

def check_dtypes(closed, spec: ProgramSpec, where: str):
    limit = max(4, int(spec.state_itemsize))
    offenders = {}
    for eqn, _, stack in _iter_eqns(closed.jaxpr):
        for v in list(eqn.outvars):
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None:
                continue
            dt = np.dtype(dt)
            bad = (dt.kind == "c"
                   or (dt.kind in "fiu" and dt.itemsize > limit))
            if bad and not _pragma_allows(eqn, "dtype-discipline",
                                          stack):
                key = (str(dt), eqn.primitive.name)
                offenders[key] = offenders.get(key, 0) + 1
    if not offenders:
        return []
    det = ", ".join(f"{d} out of {p} x{n}"
                    for (d, p), n in sorted(offenders.items()))
    return [Finding(
        "dtype-discipline", "error", where,
        f"avals wider than the {limit}-byte state dtype ceiling "
        f"(no f64/complex, no silent promotions): {det}")]


# ---------------------------------------------------------------------
# check 4: loop-invariant operands (warning class)

EXPENSIVE_PRIMS = frozenset({
    "gather", "dot_general", "conv_general_dilated", "sort",
    "scatter", "scatter-add", "scatter-min", "scatter-max",
    "scatter_add", "scatter_min", "scatter_max", "reduce_window",
})

# flag only work worth hoisting: tiny invariant ops are free either way
_INVARIANT_MIN_ELEMS = 4096


def _eqn_elems(eqn) -> int:
    sizes = [int(np.prod(v.aval.shape))
             for v in list(eqn.outvars) + list(eqn.invars)
             if hasattr(getattr(v, "aval", None), "shape")]
    return max(sizes or [0])


def _scan_invariant(jaxpr, inv_in, where, findings, stack=()):
    """Propagate loop-invariance through one body jaxpr; flag
    expensive all-invariant eqns (XLA hoists them out of the loop —
    the timed loop then measures nothing)."""
    Literal = _literal_type()
    inv = dict(zip(jaxpr.invars, inv_in))
    for cv in jaxpr.constvars:
        inv[cv] = True

    def is_inv(a):
        return isinstance(a, Literal) or inv.get(a, False)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = [is_inv(a) for a in eqn.invars]
        all_inv = bool(ins) and all(ins)
        deeper = stack + (eqn,)
        if name == "while":
            # loop consts are invariant BY DEFINITION of the loop
            # (wherever their values came from); carry is variant
            bn = eqn.params["body_nconsts"]
            body = eqn.params["body_jaxpr"].jaxpr
            binv = [True] * bn + [False] * (len(body.invars) - bn)
            _scan_invariant(body, binv, where, findings, deeper)
        elif name == "scan":
            nc = eqn.params["num_consts"]
            body = eqn.params["jaxpr"].jaxpr
            binv = [True] * nc + [False] * (len(body.invars) - nc)
            _scan_invariant(body, binv, where, findings, deeper)
        elif name == "cond":
            for sub, _ in _sub_jaxprs(eqn.params):
                binv = ins[1:1 + len(sub.invars)]
                binv += [False] * (len(sub.invars) - len(binv))
                _scan_invariant(sub, binv, where, findings, deeper)
        else:
            subs = list(_sub_jaxprs(eqn.params))
            if subs:
                for sub, _ in subs:
                    if len(sub.invars) == len(ins):
                        _scan_invariant(sub, ins, where, findings,
                                        deeper)
                    else:           # conservative: unknown call conv
                        _scan_invariant(
                            sub, [False] * len(sub.invars), where,
                            findings, deeper)
            elif (all_inv and name in EXPENSIVE_PRIMS
                    and _eqn_elems(eqn) >= _INVARIANT_MIN_ELEMS
                    and not _pragma_allows(eqn, "loop-invariant",
                                           stack)):
                findings.append(Finding(
                    "loop-invariant", "warn", _where_src(eqn, where),
                    f"{name} ({_eqn_elems(eqn)} elems) inside a "
                    f"while/scan body depends only on loop-invariant "
                    f"operands — XLA hoists it out, so a timed loop "
                    f"does not measure it (CLAUDE.md benchmarking "
                    f"trap); make it consume the carry or move it "
                    f"out of the loop explicitly"))
        for ov in eqn.outvars:
            inv[ov] = all_inv and name not in LOOP_PRIMS


def check_loop_invariant(closed, spec: ProgramSpec, where: str):
    # walk from the top with every program input VARIANT — only
    # while/scan bodies introduce invariance (their const positions),
    # which is exactly the hoisting trap this check is about
    findings = []
    _scan_invariant(closed.jaxpr,
                    [False] * len(closed.jaxpr.invars), where,
                    findings)
    return findings


# ---------------------------------------------------------------------
# check 5: collective schedule

_REDUCE_SCATTER = frozenset({"reduce_scatter", "psum_scatter"})


def check_collectives(closed, spec: ProgramSpec, where: str):
    findings = []
    if spec.require_scan_len is not None:
        scans = [eqn for eqn, _, _ in _iter_eqns(closed.jaxpr)
                 if eqn.primitive.name == "scan"]
        lens = [e.params.get("length") for e in scans]

        def shard_gather_in(eqn):
            # the generation scan's body gathers from ONE [vpad, ...]
            # state shard — without this, the fused iteration loop
            # (fori -> scan) could satisfy the check whenever
            # num_iters happens to equal the local part count
            if spec.require_scan_shard_shape is None:
                return True
            body = eqn.params.get("jaxpr")
            if body is None:
                return False
            want = tuple(spec.require_scan_shard_shape)
            return any(
                e.primitive.name == "gather"
                and tuple(e.invars[0].aval.shape) == want
                for e, _, _ in _iter_eqns(body.jaxpr))

        ok = any(e.params.get("length") == spec.require_scan_len
                 and shard_gather_in(e) for e in scans)
        if not ok:
            findings.append(Finding(
                "collective-schedule", "error", where,
                f"owner exchange must generate contributions with a "
                f"lax.scan over the {spec.require_scan_len} local "
                f"source parts whose body gathers from the "
                f"[vpad, ...] state shard (scan lengths seen: "
                f"{sorted(set(lens))}) — a vmapped batched gather "
                f"still pays the big-table rate "
                f"(PERF_NOTES.md round 3)"))
    if spec.ppermute_hops is not None:
        perms = [eqn.params.get("perm")
                 for eqn, _, _ in _iter_eqns(closed.jaxpr)
                 if eqn.primitive.name == "ppermute"]
        if len(perms) != spec.ppermute_hops:
            findings.append(Finding(
                "collective-schedule", "error", where,
                f"ring reduce-scatter must take exactly "
                f"{spec.ppermute_hops} ppermute hops (P-1); found "
                f"{len(perms)}"))
        if spec.ring_size is not None:
            for p in perms:
                if p is None:
                    continue
                pairs = sorted(tuple(x) for x in p)
                full = sorted((j, (j + 1) % spec.ring_size)
                              for j in range(spec.ring_size))
                if pairs != full:
                    findings.append(Finding(
                        "collective-schedule", "error", where,
                        f"ppermute perm {pairs} is not the full "
                        f"{spec.ring_size}-device ring cycle"))
    if spec.expect_reduce_scatter:
        if _count_prims(closed.jaxpr, _REDUCE_SCATTER) < 1:
            findings.append(Finding(
                "collective-schedule", "error", where,
                "mesh sum owner exchange must lower through "
                "psum_scatter/reduce_scatter (found none)"))
    if spec.expect_all_to_all:
        if _count_prims(closed.jaxpr, {"all_to_all"}) < 1:
            findings.append(Finding(
                "collective-schedule", "error", where,
                "mesh min/max owner exchange (non-fused) must route "
                "through all_to_all (found none)"))
    return findings


# ---------------------------------------------------------------------
# check 6: callbacks inside fused loops

# jax.debug.print traces to its own "debug_print" primitive
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
})


def check_callbacks(closed, spec: ProgramSpec, where: str):
    findings = []
    for eqn, in_loop, stack in _iter_eqns(closed.jaxpr):
        if (in_loop and eqn.primitive.name in CALLBACK_PRIMS
                and not _pragma_allows(eqn, "callback-in-loop",
                                       stack)):
            findings.append(Finding(
                "callback-in-loop", "error", _where_src(eqn, where),
                f"{eqn.primitive.name} inside a fused while/scan "
                f"body — a host round-trip per iteration; "
                f"accumulate device-side and fetch at "
                f"run/segment boundaries instead "
                f"(lux_tpu/telemetry.py)"))
    return findings


# ---------------------------------------------------------------------
# check 7: identity-sentinel scatter inits

_SCATTER_KIND = {
    "scatter-add": "sum", "scatter_add": "sum",
    "scatter-min": "min", "scatter_min": "min",
    "scatter-max": "max", "scatter_max": "max",
}

_PASSTHROUGH = frozenset({
    "broadcast_in_dim", "convert_element_type", "reshape", "squeeze",
    "expand_dims", "copy", "sharding_constraint", "transpose",
})


def _identity_value(kind: str, dtype):
    dt = np.dtype(dtype)
    if dt.kind == "b":
        return {"sum": False, "max": False, "min": True}[kind]
    from lux_tpu.ops.segment import identity_for
    return np.asarray(identity_for(kind, dt))


def _resolve_broadcast_literal(var, defs, depth=0):
    """Chase ``var`` through shape-only ops to a scalar literal; None
    when it derives from real data (a carried accumulator etc.)."""
    Literal = _literal_type()
    if isinstance(var, Literal):
        val = np.asarray(var.val)
        if val.size == 1:
            return val.reshape(())
        if val.size and (val == val.flat[0]).all():
            return np.asarray(val.flat[0])
        return None
    if depth > 12:
        return None
    eqn = defs.get(var)
    if eqn is None:
        return None
    if eqn.primitive.name in _PASSTHROUGH:
        return _resolve_broadcast_literal(eqn.invars[0], defs,
                                          depth + 1)
    return None


def _check_identity_in(jaxpr, where, findings, stack=()):
    defs = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            defs[ov] = eqn
    for eqn in jaxpr.eqns:
        kind = _SCATTER_KIND.get(eqn.primitive.name)
        if kind is not None:
            operand = eqn.invars[0]
            val = _resolve_broadcast_literal(operand, defs)
            if val is not None:
                dt = operand.aval.dtype
                ident = _identity_value(kind, dt)
                same = (np.asarray(val, np.dtype(dt)) ==
                        np.asarray(ident, np.dtype(dt)))
                # NaN init is never the identity; == already fails it
                if not bool(same) and not _pragma_allows(
                        eqn, "identity-init", stack):
                    findings.append(Finding(
                        "identity-init", "error",
                        _where_src(eqn, where),
                        f"{eqn.primitive.name} initialized with "
                        f"constant {np.asarray(val)} but the "
                        f"{kind}-reduce identity for {np.dtype(dt)} "
                        f"is {ident} — padding/empty segments will "
                        f"contribute a non-identity value (CLAUDE.md "
                        f"one-identity convention)"))
        for sub, _ in _sub_jaxprs(eqn.params):
            _check_identity_in(sub, where, findings, stack + (eqn,))


def check_identity_inits(closed, spec: ProgramSpec, where: str):
    findings = []
    _check_identity_in(closed.jaxpr, where, findings)
    return findings


# ---------------------------------------------------------------------
# jaxpr-level driver

def audit_jaxpr(closed, spec: ProgramSpec | None = None,
                where: str = "<jaxpr>"):
    """Run every jaxpr-level check on one ClosedJaxpr; returns the
    Finding list (empty = clean).  ``spec=None`` runs the
    program-independent checks only."""
    spec = spec or ProgramSpec()
    findings = []
    findings += check_gather_budget(closed, spec, where)
    findings += check_const_bytes(closed, spec, where)
    findings += check_dtypes(closed, spec, where)
    findings += check_loop_invariant(closed, spec, where)
    findings += check_collectives(closed, spec, where)
    findings += check_callbacks(closed, spec, where)
    findings += check_identity_inits(closed, spec, where)
    return findings


def raise_findings(findings, where: str = "",
                   warnings_as_errors: bool = False):
    """Raise the typed AuditError for ``findings`` (the specific
    subclass when they share one check); warnings raise only under
    ``warnings_as_errors``."""
    errs = [f for f in findings
            if f.severity == "error"
            or (warnings_as_errors and f.severity == "warn")]
    if not errs:
        return
    checks = {f.check for f in errs}
    cls = ERROR_TYPES[next(iter(checks))] if len(checks) == 1 \
        else AuditError
    msg = "; ".join(str(f) for f in errs[:8])
    if len(errs) > 8:
        msg += f" (+{len(errs) - 8} more)"
    raise cls(f"audit failed{' for ' + where if where else ''}: "
              f"{msg}", errs)


# ---------------------------------------------------------------------
# engine-level driver

def engine_spec(engine, state_aval) -> ProgramSpec:
    """The ProgramSpec an engine's own configuration implies."""
    sg = engine.sg
    trail = tuple(state_aval.shape[2:])
    table_shape = (sg.num_parts * sg.vpad,) + trail
    owner = engine.exchange == "owner"
    paged = getattr(engine, "page_plan", None) is not None
    # page-major owner (round 16): the generation scan still gathers
    # page-reshaped shards, but the exchange is the ROUTING hop — one
    # all_to_all of complete message rows for EVERY reduce kind, and
    # never a psum_scatter (there are no pre-reduced partials to sum)
    pagemajor = paged and engine.page_plan.mode == "pagemajor"
    ndev = 1 if engine.mesh is None else engine.mesh.devices.size
    # the owner generation scan runs per DEVICE (inside shard_map on
    # a mesh): its length is the device-local source-part count
    rows = sg.num_parts // ndev
    reduce_kind = getattr(engine.program, "reduce", "sum")
    fused = bool(getattr(engine, "owner_minmax_fused", False))
    on_mesh = engine.mesh is not None
    # paged engines access the table through its tile-reshaped view:
    # [T, 128, ...] (scalar/batched) or [T, 128*prod(trail)] (the
    # SDDMM path's flattened [T, 128*K] rows) — the page fetch on
    # either shape counts against the same budget
    T = sg.num_parts * sg.vpad // 128
    paged_shapes = ()
    if paged and not owner:
        paged_shapes = ((T, 128) + trail,)
        if trail:
            paged_shapes += ((T, 128 * int(np.prod(trail))),)
    # the owner paged scan gathers from the PAGE-RESHAPED shard
    shard_shape = (sg.vpad,) + trail
    if paged:
        shard_shape = (sg.vpad // 128, 128) + trail
    return ProgramSpec(
        table_shape=table_shape,
        # dense iterations mask into the value vector PRE-gather:
        # one table access (the flat per-element gather, or the paged
        # page row-fetch), zero in owner mode (per-shard gathers ride
        # the scan; pair row fetches are tile-reshaped and exempt)
        gather_budget=0 if owner else 1,
        paged_table_shapes=paged_shapes,
        state_itemsize=np.dtype(state_aval.dtype).itemsize,
        require_scan_len=rows if owner else None,
        require_scan_shard_shape=shard_shape if owner else None,
        ppermute_hops=(ndev - 1) if (owner and on_mesh and fused
                                     and not pagemajor
                                     and reduce_kind in ("min", "max"))
        else None,
        ring_size=ndev if (owner and on_mesh and fused
                           and not pagemajor) else None,
        expect_reduce_scatter=(owner and on_mesh and not pagemajor
                               and reduce_kind == "sum"),
        expect_all_to_all=(owner and on_mesh
                           and (pagemajor
                                or (not fused
                                    and reduce_kind in ("min",
                                                        "max")))),
    )


def trace_variant(jitted, args):
    """ClosedJaxpr of one registered engine variant — tracing only,
    no compile, no device execution (CPU-safe at any graph scale)."""
    return jitted.trace(*args).jaxpr


def audit_engine(engine, mode: str | None = "error",
                 allow=frozenset(), ledger: bool = False,
                 ledger_tol: float = 0.5):
    """Trace every registered program variant of ``engine`` and run
    the full check catalogue; optionally cross-validate the memory
    ledger (compiles the single step on the current backend — keep it
    for CPU audits).  Returns the Finding list; ``mode='error'``
    raises the typed AuditError on any error finding, ``mode='warn'``
    emits an AuditWarning, ``mode=None`` only returns the findings;
    any other mode string is a typed ValueError (a typo must not
    silently disable enforcement).  ``allow`` drops named checks
    (record WHY at the call site — the pragma mechanism's
    programmatic form)."""
    if mode not in (None, "warn", "error"):
        raise ValueError(
            f"audit mode {mode!r} is not None|'warn'|'error' — an "
            f"unknown mode must not silently skip enforcement")
    findings = []
    variants = engine.audit_programs()
    eng_name = type(engine).__name__
    spec = None
    for name, (jitted, args_thunk) in variants.items():
        args = args_thunk()
        if spec is None:
            import jax
            state_aval = (args[0] if hasattr(args[0], "dtype")
                          else jax.ShapeDtypeStruct((), np.float32))
            spec = engine_spec(engine, state_aval)
        closed = trace_variant(jitted, args)
        findings += audit_jaxpr(closed, spec,
                                where=f"{eng_name}.{name}")
    if ledger:
        findings += check_ledger(engine, tol=ledger_tol)
    findings = [f for f in findings if f.check not in allow]
    if mode == "error":
        raise_findings(findings, where=eng_name)
    elif mode == "warn":
        for f in findings:
            warnings.warn(str(f), AuditWarning, stacklevel=2)
    return findings


# ---------------------------------------------------------------------
# check 8: ledger cross-validation

def report_kwargs(engine) -> dict:
    """The ``sg.memory_report(...)`` kwargs matching this engine's
    actual build (exchange / page plan / pair plan / push sparsity /
    query batch) — factored out of ``check_ledger`` so the runtime
    memory observatory (lux_tpu/memwatch.py, round 22) prices the
    SAME program the compile-time drift check audits; two
    independently-maintained kwarg derivations would let the two
    ledgers silently diverge."""
    from lux_tpu.engine.push import PushEngine
    is_push = isinstance(engine, PushEngine)
    kw = dict(exchange=engine.exchange)
    if getattr(engine, "page_plan", None) is not None:
        # paged engines carry the plan arrays + page buffer instead
        # of the tiled/owner edge layout (memory_report prices the
        # actual plan array bytes)
        kw["page_plan"] = engine.page_plan
        kw["pair_kdim"] = engine.delivery.kdim
    if engine.pairs is not None:
        kw["pairs"] = engine.pairs
        kw["pair_kdim"] = engine.delivery.kdim
    if is_push:
        kw["push_sparse"] = bool(engine.enable_sparse)
        # query-batched labels [P, vpad, B]: the ledger must price
        # the B-wide state + active mask or every batched build
        # would read as drift (ROADMAP item 2; memory_report's
        # query_batch pricing) — pull engines carry B through
        # state_bytes instead (the correction below)
        kw["query_batch"] = int(getattr(engine, "batch", None) or 1)
    if getattr(engine, "use_mxu", False):
        # the MXU one-hot reduce materializes the [C, E, W] int8
        # lane matrix (round 23) — price it at the engine's actual
        # chunk width so a use_mxu build's ledger stays honest
        kw["use_mxu"] = True
        lay = getattr(engine, "tiles", None) \
            or getattr(engine, "owner", None)
        if lay is not None and getattr(lay, "E", None):
            kw["mxu_tile_e"] = int(lay.E)
    return kw


def priced_argument_bytes(engine) -> int:
    """The ledger's price for the engine's resident ARGUMENT arrays —
    ``memory_report`` total minus the per-iteration temporary terms,
    plus the program-level state-width/extra-array corrections.  This
    is the ``expected`` side of the ledger-drift comparison, shared
    by ``check_ledger`` and the runtime observatory's per-replica
    byte ledger (lux_tpu/memwatch.py)."""
    ledger = engine.sg.memory_report(**report_kwargs(engine))
    expected = int(ledger["total_bytes"])
    # memory_analysis argument bytes cover resident ARGUMENT arrays
    # only — subtract the advisor's per-iteration temporary terms
    # (pair/paged delivery intermediates, the page buffer) so the
    # drift comparison is apples to apples
    for tk in ("pair_temp_bytes_per_part",
               "page_buffer_bytes_per_part",
               "page_temp_bytes_per_part",
               "mxu_temp_bytes_per_part"):
        expected -= engine.sg.num_parts * int(ledger.get(tk, 0))
    # the ledger prices scalar f32 state; K-vector programs carry
    # state_bytes per vertex — correct the vertex term so colfilter's
    # [vpad, 20] table does not read as edge-ledger drift
    sb = getattr(engine.program, "state_bytes", None)
    if sb:
        expected += engine.sg.num_parts * engine.sg.vpad * (sb - 4)
    # program-contributed extra arrays (batched reset vectors, the
    # round-21 pull deg_corr columns) are jit ARGUMENTS by the
    # no-closure convention — price their actual bytes, or every
    # extra-carrying program reads as edge-ledger drift (batched ppr
    # rode the tolerance on one [vpad, B] extra and tripped it on
    # the second)
    xa = getattr(engine.program, "extra_arrays", None)
    if xa is not None:
        expected += sum(np.asarray(v).nbytes
                        for v in xa(engine.sg).values())
    return expected


def check_ledger(engine, tol: float = 0.5, where: str | None = None):
    """Compile the engine's single step on the CURRENT backend and
    compare XLA ``memory_analysis`` argument bytes against the priced
    ledger ``sg.memory_report(...)``.  The ratio must stay within
    [1/(1+tol), 1+tol] — see the module docstring for the tolerance
    rationale (chunk/tile padding sits above the ledger's epad-based
    lower bounds; only meaningful on graphs dense enough that edges
    dominate padding)."""
    where = where or type(engine).__name__
    variants = engine.audit_programs()
    jitted, args_thunk = variants["step"]
    try:
        compiled = jitted.lower(*args_thunk()).compile()
        ma = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001 — backend without AOT stats
        return [Finding("ledger-drift", "warn", where,
                        f"memory_analysis unavailable ({e}); ledger "
                        f"cross-validation skipped")]
    if ma is None or not getattr(ma, "argument_size_in_bytes", 0):
        return []
    measured = int(ma.argument_size_in_bytes)
    expected = priced_argument_bytes(engine)
    ratio = measured / max(1, expected)
    if not (1.0 / (1.0 + tol) <= ratio <= 1.0 + tol):
        return [Finding(
            "ledger-drift", "error", where,
            f"compiled step argument bytes {measured} vs priced "
            f"ledger {expected} (ratio {ratio:.2f}) outside the "
            f"stated tolerance x{1 + tol:.2f} — "
            f"graph.memory_report has drifted from the compiler "
            f"(exchange={engine.exchange})")]
    return []


# ---------------------------------------------------------------------
# repo-wide audit (the tier-1 entry; python -m lux_tpu.audit)

def _matrix_graphs():
    from lux_tpu.graph import Graph

    def mk(nv, ne, weighted=False, seed=0):
        r = np.random.default_rng(seed)
        src = r.integers(0, nv, ne)
        dst = r.integers(0, nv, ne)
        w = (r.integers(1, 6, ne).astype(np.float32)
             if weighted else None)
        return Graph.from_edges(src, dst, nv, weights=w)

    return {
        "tiny": mk(256, 2048),
        "tiny_w": mk(256, 2048, weighted=True),
        # dense enough that edge arrays dominate padding: the ledger
        # cross-check is meaningful here (see check_ledger docstring)
        "dense": mk(2048, 32768),
        "dense_w": mk(2048, 32768, weighted=True, seed=1),
    }


def matrix_configs(ledger: bool = True):
    """The repo-wide engine configuration matrix: [(label, build
    thunk, ledger?)] — shared by ``run_repo_audit`` and the
    communication observatory (lux_tpu/comms.py walks the SAME
    engines' step programs for its per-collective byte ledger, so
    the two subsystems can never audit different programs).  Mesh
    configurations are included when >= 2 devices are visible (the
    tier-1 test runs on the 8-virtual-device conftest mesh)."""
    import jax

    from lux_tpu.apps import colfilter, components, pagerank, sssp
    from lux_tpu.graph import pair_relabel

    graphs = _matrix_graphs()
    ndev = len(jax.devices())
    mesh = None
    if ndev >= 2:
        from lux_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(2)

    configs = []   # (label, build thunk, ledger?)
    g = graphs["tiny"]
    gw = graphs["tiny_w"]
    configs.append(("pagerank_np2_gather",
                    lambda: pagerank.build_engine(g, num_parts=2),
                    False))
    configs.append(("pagerank_np4_owner",
                    lambda: pagerank.build_engine(g, num_parts=4,
                                                  exchange="owner"),
                    False))

    def _pair_engine():
        g2, _perm, starts = pair_relabel(g, 2, pair_threshold=8)
        return pagerank.build_engine(g2, num_parts=2,
                                     pair_threshold=8, starts=starts)

    configs.append(("pagerank_np2_pair", _pair_engine, False))
    configs.append(("sssp_np2_sparse",
                    lambda: sssp.build_engine(g, 0, num_parts=2),
                    False))
    configs.append(("sssp_np2_delta_w",
                    lambda: sssp.build_engine(
                        gw, 0, num_parts=2, weighted=True,
                        delta=1.0),
                    False))
    configs.append(("cc_np2_dense_only",
                    lambda: components.build_engine(
                        g, num_parts=2, enable_sparse=False),
                    False))
    configs.append(("colfilter_np1_dot",
                    lambda: colfilter.build_engine(gw, num_parts=1),
                    False))

    def _pair_dot_engine():
        g2, _perm, starts = pair_relabel(gw, 2, pair_threshold=8)
        return colfilter.build_engine(g2, num_parts=2,
                                      pair_threshold=8, starts=starts)

    configs.append(("colfilter_np2_pair_dot", _pair_dot_engine, False))
    # paged two-level gather (ops/pagegather.py, round 15): the page
    # row-fetch + Pallas lane shuffle must hold the SAME one-access
    # budget as the flat gather (paged_table_shapes) with no pragma —
    # dense pull, dense push, the SDDMM dot path, the owner-side
    # generation scan (page-reshaped shard gathers), and a batched
    # B > 1 build
    configs.append(("pagerank_np2_paged",
                    lambda: pagerank.build_engine(g, num_parts=2,
                                                  gather="paged"),
                    False))
    configs.append(("sssp_np2_paged",
                    lambda: sssp.build_engine(g, 0, num_parts=2,
                                              gather="paged"),
                    False))
    configs.append(("pagerank_np4_owner_paged",
                    lambda: pagerank.build_engine(g, num_parts=4,
                                                  exchange="owner",
                                                  gather="paged"),
                    False))
    configs.append(("colfilter_np2_paged_dot",
                    lambda: colfilter.build_engine(gw, num_parts=2,
                                                   gather="paged"),
                    False))
    configs.append(("ppr_np2_paged_batched",
                    lambda: pagerank.build_engine(g, num_parts=2,
                                                  sources=[0, 3, 7],
                                                  gather="paged"),
                    False))
    # page-major layout (round 16, ops/pagegather.py): the full-fill
    # gather rows + virtual-row takes must hold the same one-access
    # budget (the virtual take's operand is the [Rg, 128] value
    # buffer, shape-distinct from the table by _pad8_distinct); the
    # OWNER page-major routing must keep the generation scan AND
    # lower its exchange through all_to_all — for sum too (engine_
    # spec: no psum_scatter, there are no pre-reduced partials)
    configs.append(("pagerank_np2_pagemajor",
                    lambda: pagerank.build_engine(
                        g, num_parts=2, gather="pagemajor"),
                    False))
    configs.append(("cc_np2_pagemajor",
                    lambda: components.build_engine(
                        g, num_parts=2, enable_sparse=False,
                        gather="pagemajor"),
                    False))
    configs.append(("pagerank_np4_owner_pagemajor",
                    lambda: pagerank.build_engine(
                        g, num_parts=4, exchange="owner",
                        gather="pagemajor"),
                    False))
    # query-batched engines (ROADMAP item 2): the gather budget must
    # hold at B > 1 — ONE [P*vpad, B] table gather per dense pull/push
    # iteration, ZERO in owner mode — and the owner collective
    # schedule must be unchanged by the trailing query axis
    QB = [0, 3, 7, 11]
    configs.append(("ksssp_np2_batched",
                    lambda: sssp.build_engine(g, num_parts=2,
                                              sources=QB),
                    False))
    configs.append(("ksssp_np4_owner_batched",
                    lambda: sssp.build_engine(g, num_parts=4,
                                              sources=QB,
                                              exchange="owner"),
                    False))
    configs.append(("ppr_np2_batched",
                    lambda: pagerank.build_engine(g, num_parts=2,
                                                  sources=QB),
                    False))
    configs.append(("ppr_np4_owner_batched",
                    lambda: pagerank.build_engine(g, num_parts=4,
                                                  sources=QB,
                                                  exchange="owner"),
                    False))
    configs.append(("cc_np2_batched",
                    lambda: components.build_engine(g, num_parts=2,
                                                    sources=QB[:2]),
                    False))

    # live-graph delta revalidation (round 20, lux_tpu/livegraph.py):
    # the delta-relax step rides the SAME gather budget as the dense
    # iterations — ONE state-table gather (the delta-source fetch;
    # improvements come from a whole-table compare, never a second
    # gather) — and the dense programs themselves are UNCHANGED by
    # serving a live graph, so the budget holds across the whole
    # matrix with no pragma.
    def _live(builder):
        from lux_tpu.livegraph import LiveGraph
        lg = LiveGraph(g, capacity=64)
        lg.append_edges([1, 2, 3], [9, 17, 33])
        # a published TOMBSTONE slot (round 21): the audited step
        # must keep its single state-table gather with the d_kind
        # mask in the jaxpr, not just for pure-append deltas
        lg.delete_edges([1], [9])
        eng = builder()
        lg.register_audit(eng)
        return eng

    configs.append(("sssp_np2_live_delta",
                    lambda: _live(lambda: sssp.build_engine(
                        g, 0, num_parts=2)),
                    False))
    configs.append(("ksssp_np2_live_batched",
                    lambda: _live(lambda: sssp.build_engine(
                        g, num_parts=2, sources=QB)),
                    False))
    configs.append(("cc_np2_live_delta",
                    lambda: _live(lambda: components.build_engine(
                        g, num_parts=2)),
                    False))

    # MXU compute core (round 23, ops/tiled use_mxu): the one-hot
    # contraction programs must hold the SAME static guarantees as
    # the VPU formulations — gather budget 1 (the tournament's
    # route-back is a matmul, never a second table gather), dtype
    # discipline (int8 one-hot, int32 vote accumulators, uint32
    # order encodings — all <= 4 B), and identity-init (the frontier
    # MXU path's delta scatter-ADD is zero-initialized = the sum
    # identity, NO pragma).  ppr_np2_batched above already audits the
    # AUTO-engaged MXU path (B=8 >= the scalemodel break-even);
    # these force it onto the kinds/exchanges auto leaves on the VPU.
    configs.append(("pagerank_np2_mxu",
                    lambda: pagerank.build_engine(g, num_parts=2,
                                                  use_mxu=True),
                    False))
    configs.append(("sssp_np2_mxu",
                    lambda: sssp.build_engine(g, 0, num_parts=2,
                                              use_mxu=True),
                    False))
    configs.append(("cc_np2_mxu_dense",
                    lambda: components.build_engine(
                        g, num_parts=2, enable_sparse=False,
                        use_mxu=True),
                    False))
    configs.append(("pagerank_np4_owner_mxu",
                    lambda: pagerank.build_engine(g, num_parts=4,
                                                  exchange="owner",
                                                  use_mxu=True),
                    False))
    if ledger:
        gd = graphs["dense"]
        gdw = graphs["dense_w"]
        configs.append(("pagerank_np2_ledger",
                        lambda: pagerank.build_engine(gd, num_parts=2),
                        True))
        configs.append(("sssp_np2_ledger",
                        lambda: sssp.build_engine(gdw, 0, num_parts=2,
                                                  weighted=True),
                        True))
        # the ledger-drift check must stay honest at B > 1: the
        # priced [P*vpad, B] state table (memory_report query_batch /
        # the pull state_bytes correction) vs the compiled step's
        # argument bytes
        configs.append(("ksssp_np2_batched_ledger",
                        lambda: sssp.build_engine(
                            gd, num_parts=2, sources=list(range(8))),
                        True))
        configs.append(("ppr_np2_batched_ledger",
                        lambda: pagerank.build_engine(
                            gd, num_parts=2, sources=list(range(8))),
                        True))
        # paged ledger: the priced plan arrays + page buffer vs the
        # compiled step's argument bytes
        configs.append(("pagerank_np2_paged_ledger",
                        lambda: pagerank.build_engine(
                            gd, num_parts=2, gather="paged"),
                        True))
        # MXU ledger: the priced mxu_temp one-hot term must keep a
        # forced use_mxu build inside the drift tolerance (the
        # [C, E, W] int8 matrix is a TEMPORARY — subtracted for the
        # argument-bytes comparison, named for the runtime ledger)
        configs.append(("pagerank_np2_mxu_ledger",
                        lambda: pagerank.build_engine(
                            gd, num_parts=2, use_mxu=True),
                        True))
    if mesh is not None:
        configs.append(("pagerank_mesh2_gather",
                        lambda: pagerank.build_engine(g, num_parts=2,
                                                      mesh=mesh),
                        False))
        configs.append(("pagerank_mesh2_owner_sum",
                        lambda: pagerank.build_engine(
                            g, num_parts=2, mesh=mesh,
                            exchange="owner"),
                        False))
        configs.append(("cc_mesh2_owner_a2a",
                        lambda: components.build_engine(
                            g, num_parts=2, mesh=mesh,
                            exchange="owner"),
                        False))
        configs.append(("cc_mesh2_owner_ring",
                        lambda: components.build_engine(
                            g, num_parts=2, mesh=mesh,
                            exchange="owner",
                            owner_minmax_fused=True),
                        False))
        configs.append(("sssp_mesh2_sparse",
                        lambda: sssp.build_engine(g, 0, num_parts=2,
                                                  mesh=mesh),
                        False))
        # forced-MXU mesh config: the contraction core must leave
        # the collective schedule untouched (the one-hot matmuls are
        # purely part-local; only the reduce formulation changes)
        configs.append(("sssp_mesh2_mxu",
                        lambda: sssp.build_engine(g, 0, num_parts=2,
                                                  mesh=mesh,
                                                  use_mxu=True),
                        False))
        # batched mesh configs: the single-gather hold AND the owner
        # collective schedule (psum_scatter / all_to_all) at B > 1
        configs.append(("ksssp_mesh2_batched",
                        lambda: sssp.build_engine(g, num_parts=2,
                                                  mesh=mesh,
                                                  sources=QB),
                        False))
        configs.append(("ppr_mesh2_owner_batched",
                        lambda: pagerank.build_engine(
                            g, num_parts=2, mesh=mesh, sources=QB,
                            exchange="owner"),
                        False))
        configs.append(("cc_mesh2_owner_batched",
                        lambda: components.build_engine(
                            g, num_parts=2, mesh=mesh,
                            sources=QB[:2], exchange="owner"),
                        False))
        # page-major owner ROUTING on a real mesh axis (round 19):
        # the all_to_all of complete message rows — audited for
        # schedule here and priced per byte by the comm ledger
        # (lux_tpu/comms.py oracle: [P_local, P, Mg, 128] rows)
        configs.append(("pagerank_mesh2_owner_pagemajor",
                        lambda: pagerank.build_engine(
                            g, num_parts=2, mesh=mesh,
                            exchange="owner", gather="pagemajor"),
                        False))
    if ndev >= 4:
        from lux_tpu.parallel.mesh import make_mesh
        mesh4 = make_mesh(4)
        # the POST-SHRINK shape (round 11, elastic recovery): parts
        # fixed at 8, device mapping changed to a smaller mesh — the
        # owner generation scan must cover 2 device-local parts and
        # the collective schedule must hold at the new ndev (the
        # acceptance gate resilience's re-placement relies on)
        configs.append(("pagerank_mesh4x8parts_owner_shrunk",
                        lambda: pagerank.build_engine(
                            g, num_parts=8, mesh=mesh4,
                            exchange="owner"),
                        False))
    return configs


def run_repo_audit(verbose: bool = False, ledger: bool = True):
    """Build the engine matrix (``matrix_configs``) on the current
    (CPU) backend and audit every program variant of every
    configuration.  Returns the list of error/warn Findings (empty =
    clean)."""
    all_findings = []
    for label, build, do_ledger in matrix_configs(ledger=ledger):
        eng = build()
        fs = audit_engine(eng, mode=None, ledger=do_ledger)
        if verbose:
            n_err = sum(1 for f in fs if f.severity == "error")
            print(f"# audit {label}: "
                  f"{len(eng.audit_programs())} variants, "
                  f"{n_err} errors, "
                  f"{len(fs) - n_err} warnings")
        for f in fs:
            all_findings.append(dataclasses.replace(
                f, where=f"{label}/{f.where}"))
    return all_findings


def digest(findings, mode: str = "warn") -> dict:
    """JSON-serializable summary of an audit — the field bench.py
    metric lines carry.  ``mode`` is the -audit mode the build ran
    under; scripts/check_bench.py requires it ('warn'|'error') and
    rejects metric lines whose digest carries errors."""
    errs = [f for f in findings if f.severity == "error"]
    warns = [f for f in findings if f.severity == "warn"]
    return {
        "mode": mode,
        "errors": len(errs),
        "warnings": len(warns),
        "failed_checks": sorted({f.check for f in errs}),
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m lux_tpu.audit",
        description="repo-wide compile-time program audit on the CPU "
                    "backend (no TPU needed)")
    ap.add_argument("-no-ledger", action="store_true",
                    dest="no_ledger",
                    help="skip the ledger cross-validation (no "
                         "CPU compiles, tracing only)")
    ap.add_argument("-warnings-as-errors", action="store_true",
                    dest="werror",
                    help="exit 1 on warning-severity findings too "
                         "(loop-invariant)")
    ap.add_argument("-v", "-verbose", action="store_true",
                    dest="verbose")
    args = ap.parse_args(argv)

    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        # backend already initialized (e.g. under pytest) — the
        # conftest pins CPU there; on a TPU session tracing is still
        # host-side and the audit stays valid
        pass

    findings = run_repo_audit(verbose=args.verbose,
                              ledger=not args.no_ledger)
    errors = [f for f in findings if f.severity == "error"]
    warns = [f for f in findings if f.severity == "warn"]
    for f in findings:
        print(("ERROR: " if f.severity == "error" else "WARNING: ")
              + str(f))
    bad = errors + (warns if args.werror else [])
    if bad:
        print(f"audit: {len(errors)} error(s), {len(warns)} "
              f"warning(s) — FAILED")
        return 1
    print(f"audit: clean ({len(warns)} warning(s))")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
