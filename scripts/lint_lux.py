#!/usr/bin/env python
"""AST-level convention linter for the lux_tpu Python tree.

The companion of lux_tpu/audit.py: where the auditor checks TRACED
jaxprs, this checks SOURCE against the repo conventions that cannot
be seen from a jaxpr (CLAUDE.md "Conventions"):

  jit-closure   A function handed to ``jax.jit`` (decorator, direct
                call, or ``functools.partial(jax.jit, ...)``) closes
                over a name bound in an enclosing function to an
                array-constructing expression (``jnp.asarray(...)``,
                ``self.arrays[...]``, ...).  Engines must take graph
                arrays as jit ARGUMENTS — a closed-over array bakes
                into the XLA program as a constant (bloating the
                program and its compile; the jaxpr-level twin is the
                auditor's const-bytes ceiling).
  oracle        Every app module (lux_tpu/apps/*.py) must define a
                top-level NumPy oracle named ``reference_*`` — the
                "new device code gets an oracle test first"
                convention.  Round 21: deletion-capable builders
                (``*decremental*``, ``delete_edges``,
                ``reweight_edges``) anywhere in the library tree must
                define or cite a ``reference_*decremental`` oracle —
                anti-monotone mutations are proved equal to full
                recompute at the same epoch.
  citation      Every module in lux_tpu/engine/ and lux_tpu/ops/
                must cite the reference implementation (a
                ``file:line`` pattern like ``pull_model.inl:423``) in
                its module docstring, for parity auditing.
  part-stats-oracle
                Every engine ``*_stats``/``*_health`` loop variant
                whose docstring cites per-part counters (round 13,
                lux_tpu/tracing.py era) must be covered by a test
                that exercises it against a per-part NumPy oracle:
                some file under tests/ must reference BOTH the
                variant name AND a ``per_part*`` oracle helper —
                mirroring the app-module oracle-presence check, so a
                new per-part counter variant cannot ship without its
                sum-over-parts-bitwise proof.
  hot-path-metrics
                No metrics call (``metrics.counter(...)``,
                ``self.metrics.histogram(...).observe(...)``, any
                call whose target chain references a ``metrics``
                name or attribute — lux_tpu/metrics.py) may appear
                inside engine device code (lux_tpu/engine/,
                lux_tpu/ops/) or inside a fused-loop body (a
                function handed to ``fori_loop``/``while_loop``/
                ``scan``) anywhere in the tree.  Metrics are
                HOST-side, segment-boundary-only by contract — the
                same rationale as the audited callback-in-loop ban:
                a metrics call in a traced loop body either bakes a
                host callback into the fused program or silently
                records nothing per iteration.
  chaos-coverage
                Every fault-plan ACTION constant in lux_tpu/faults.py
                (a module-level ALL-CAPS name bound to a string
                literal — ``WORKER_KILL = "worker_kill"``, ...) must
                be exercised by at least one file under tests/: some
                test must reference the constant's name or its string
                value.  A fault action nobody drills is a recovery
                path that ships untested — the exact failure mode
                faults.py exists to prevent (round 24: the
                FLEET_CRASH / REPLICA_FLAP self-healing drills ride
                this gate).  Pragma-suppressible on the assignment
                line for actions that are deliberately
                library-internal.
  collective-scope
                No collective-primitive call (``jax.lax.ppermute``,
                ``all_to_all``, ``psum_scatter``/``reduce_scatter``,
                ``all_gather``, ``psum``/``pmin``/``pmax``) outside
                ``lux_tpu/ops/`` and ``lux_tpu/engine/``.  Those two
                trees are where the jaxpr auditor's
                collective-schedule check and the comm observatory's
                byte oracle (lux_tpu/comms.py) know to look — a
                collective planted elsewhere ships unaccounted bytes
                the ledger never prices.  Pragma-suppressible for
                deliberate exceptions (the link-bandwidth probes,
                the device placement check).
  bench-fence   (scripts/ only) No ``block_until_ready`` fencing in
                benchmark scripts: a hand-rolled timed dispatch is
                where XLA hoists loop-invariant work or dead-codes an
                unused output (the measurement traps PERF_NOTES
                documents), and one recipe keeps every script's
                numbers comparable — the
                trusted recipe is ``lux_tpu.timing.loop_bench``
                (loop-dependent carry, scalar output, one jit, fetch
                fence), which rounds 12/15 ported every profile
                script onto.

Suppression: an explicit ``# audit: allow(<check>)`` pragma on the
flagged line, or in the contiguous comment block directly above it,
with a one-line justification — the same syntax the jaxpr auditor
honors through eqn source info.

Usage:  python scripts/lint_lux.py [PATHS...]   (default: lux_tpu)
Exit status: 0 clean, 1 any unsuppressed finding.
"""

from __future__ import annotations

import argparse
import ast
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRAGMA_RE = re.compile(r"#\s*audit:\s*allow\(([a-z-]+)\)")

CITATION_RE = re.compile(r"[\w/]+\.(?:h|cc|cu|cuh|inl|py|md):\d+")

# expressions whose result is (or wraps) a device/host array big
# enough to matter if baked into a jit as a constant
ARRAY_MAKER_FUNCS = {
    "asarray", "array", "zeros", "ones", "full", "arange", "empty",
    "linspace", "zeros_like", "ones_like", "full_like", "stack",
    "concatenate", "pad",
}
ARRAY_MAKER_MODULES = {"jnp", "np", "numpy", "jax"}
ARRAY_ATTR_SOURCES = {"arrays", "graph_args"}


class Finding:
    def __init__(self, path, line, check, message):
        self.path, self.line, self.check, self.message = \
            path, line, check, message

    def __str__(self):
        rel = os.path.relpath(self.path, REPO)
        return f"{rel}:{self.line}: [{self.check}] {self.message}"


def _suppressed(lines, line_no: int, check: str) -> bool:
    """Pragma on the flagged line or the contiguous comment block
    directly above it (mirrors lux_tpu/audit._pragma_allows)."""

    def hit(text):
        return any(m.group(1) == check
                   for m in PRAGMA_RE.finditer(text))

    if 0 < line_no <= len(lines) and hit(lines[line_no - 1]):
        return True
    ln = line_no - 2
    while ln >= 0:
        stripped = lines[ln].strip()
        if stripped.startswith("#"):
            if hit(stripped):
                return True
            ln -= 1
        elif not stripped or stripped.startswith("@"):
            # blank lines and decorators don't break the pragma
            # block (a pragma above a @jax.jit stack covers the def)
            ln -= 1
        else:
            break
    return False


# ---------------------------------------------------------------------
# check: jit-closure


def _is_array_maker(expr: ast.expr) -> bool:
    """Does this RHS construct an array?  (Heuristic on the repo's
    idioms: jnp/np makers, ``self.arrays[...]`` / ``.graph_args``
    access, or a tuple/starred of the same.)"""
    if isinstance(expr, ast.Call):
        f = expr.func
        if isinstance(f, ast.Attribute):
            base = f.value
            if (f.attr in ARRAY_MAKER_FUNCS
                    and isinstance(base, ast.Name)
                    and base.id in ARRAY_MAKER_MODULES):
                return True
            # jnp.asarray(...).reshape(...) etc.
            if isinstance(base, ast.Call):
                return _is_array_maker(base)
        if isinstance(f, ast.Name) and f.id in ("dev",):
            # the engines' ``dev = jnp.asarray`` placement helper
            return True
    if isinstance(expr, ast.Subscript):
        v = expr.value
        if isinstance(v, ast.Attribute) and v.attr in ARRAY_ATTR_SOURCES:
            return True
        if isinstance(v, ast.Name) and v.id in ARRAY_ATTR_SOURCES:
            return True
    if isinstance(expr, ast.Attribute) and expr.attr in ARRAY_ATTR_SOURCES:
        return True
    return False


def _jitted_functions(tree: ast.Module):
    """Yield (FunctionDef/Lambda node, report_line) for every function
    the module hands to jax.jit."""

    def is_jax_jit(node: ast.expr) -> bool:
        return (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax") or (
            isinstance(node, ast.Name) and node.id == "jit")

    def is_partial_jit(call: ast.Call) -> bool:
        f = call.func
        is_partial = (isinstance(f, ast.Attribute)
                      and f.attr == "partial") or (
            isinstance(f, ast.Name) and f.id == "partial")
        return (is_partial and call.args
                and is_jax_jit(call.args[0]))

    # name -> def node, per enclosing function body (for jax.jit(name))
    defs_by_scope: dict[int, dict] = {}

    class Scoper(ast.NodeVisitor):
        def __init__(self):
            self.stack = []
            self.out = []

        def _local_defs(self):
            return defs_by_scope.setdefault(
                id(self.stack[-1]) if self.stack else 0, {})

        def visit_FunctionDef(self, node):
            self._local_defs()[node.name] = node
            for dec in node.decorator_list:
                if is_jax_jit(dec) or (isinstance(dec, ast.Call)
                                       and (is_jax_jit(dec.func)
                                            or is_partial_jit(dec))):
                    self.out.append((node, node.lineno))
            self.stack.append(node)
            self.generic_visit(node)
            self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Call(self, node):
            if is_jax_jit(node.func) and node.args:
                target = node.args[0]
                if isinstance(target, ast.Lambda):
                    self.out.append((target, node.lineno))
                elif isinstance(target, ast.Name):
                    fn = self._local_defs().get(target.id)
                    if fn is not None:
                        self.out.append((fn, node.lineno))
            self.generic_visit(node)

    s = Scoper()
    s.visit(tree)
    return s.out


class _ScopeInfo:
    """Names assigned per function scope, with array-maker marks."""

    def __init__(self):
        self.assigned: dict[str, bool] = {}   # name -> is array maker


def _collect_scopes(tree):
    """function node -> (_ScopeInfo, parent chain)."""
    info: dict = {}
    parents: dict = {}

    class V(ast.NodeVisitor):
        def __init__(self):
            self.stack = [None]

        def _scope(self):
            return info.setdefault(self.stack[-1], _ScopeInfo())

        def visit_FunctionDef(self, node):
            self._scope().assigned[node.name] = False
            parents[node] = self.stack[-1]
            self.stack.append(node)
            sc = self._scope()
            for a in node.args.args + node.args.kwonlyargs \
                    + node.args.posonlyargs:
                sc.assigned[a.arg] = False
            if node.args.vararg:
                sc.assigned[node.args.vararg.arg] = False
            if node.args.kwarg:
                sc.assigned[node.args.kwarg.arg] = False
            self.generic_visit(node)
            self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Lambda(self, node):
            parents[node] = self.stack[-1]
            self.stack.append(node)
            sc = self._scope()
            for a in node.args.args:
                sc.assigned[a.arg] = False
            self.generic_visit(node)
            self.stack.pop()

        def visit_Assign(self, node):
            sc = self._scope()
            maker = _is_array_maker(node.value)
            for t in node.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        sc.assigned[n.id] = maker or \
                            sc.assigned.get(n.id, False)
            self.generic_visit(node)

        def visit_AugAssign(self, node):
            if isinstance(node.target, ast.Name):
                self._scope().assigned.setdefault(node.target.id, False)
            self.generic_visit(node)

        def visit_For(self, node):
            for n in ast.walk(node.target):
                if isinstance(n, ast.Name):
                    self._scope().assigned.setdefault(n.id, False)
            self.generic_visit(node)

        def visit_comprehension_target(self, node):
            pass

    V().visit(tree)
    return info, parents


def _free_loads(fn):
    """Names loaded in ``fn`` but not bound there (params, local
    assigns, inner defs, comprehension targets all bind)."""
    bound = set()
    args = fn.args
    for a in args.args + args.kwonlyargs + getattr(args, "posonlyargs",
                                                   []):
        bound.add(a.arg)
    if args.vararg:
        bound.add(args.vararg.arg)
    if args.kwarg:
        bound.add(args.kwarg.arg)
    loads = {}
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        for n in ast.walk(stmt):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                bound.add(n.name)
            elif isinstance(n, ast.Name):
                if isinstance(n.ctx, ast.Store):
                    bound.add(n.id)
                elif isinstance(n.ctx, ast.Load):
                    loads.setdefault(n.id, n.lineno)
            elif isinstance(n, ast.comprehension):
                for t in ast.walk(n.target):
                    if isinstance(t, ast.Name):
                        bound.add(t.id)
    return {k: v for k, v in loads.items() if k not in bound}


def check_jit_closures(path, tree, lines):
    findings = []
    info, parents = _collect_scopes(tree)
    for fn, line in _jitted_functions(tree):
        free = _free_loads(fn)
        # walk the enclosing scope chain out to module scope (None)
        chain, scope = [], parents.get(fn)
        while scope is not None:
            chain.append(scope)
            scope = parents.get(scope)
        chain.append(None)
        flagged = set()
        for scope in chain:
            sc = info.get(scope)
            if sc is None:
                continue
            for name in sorted(free):
                if name in flagged or not sc.assigned.get(name, False):
                    continue
                flagged.add(name)
                if not _suppressed(lines, line, "jit-closure"):
                    findings.append(Finding(
                        path, line, "jit-closure",
                        f"jitted function closes over array {name!r} "
                        f"bound in an enclosing scope — pass it as a "
                        f"jit ARGUMENT (closed-over arrays bake into "
                        f"the program as constants and bloat it and "
                        f"its compile)"))
    return findings


# ---------------------------------------------------------------------
# check: oracle presence


def check_oracle(path, tree, lines):
    name = os.path.basename(path)
    if name == "__init__.py":
        return []
    has = any(isinstance(n, ast.FunctionDef)
              and n.name.startswith("reference_")
              for n in tree.body)
    findings = []
    if not has and not _suppressed(lines, 1, "oracle"):
        findings.append(Finding(
            path, 1, "oracle",
            "app module has no top-level reference_* NumPy oracle — "
            "every algorithm needs one (CLAUDE.md: new device code "
            "gets an oracle test first)"))
    # query-batched variants (ROADMAP item 2): a module shipping a
    # batched program builder must also ship its batched oracle —
    # the columns-bitwise-equal-B-independent-runs contract needs a
    # NumPy reference to be provable at all
    batched_defs = [n for n in tree.body
                    if isinstance(n, ast.FunctionDef)
                    and "batched" in n.name
                    and not n.name.startswith("reference_")]
    has_batched_oracle = any(
        isinstance(n, ast.FunctionDef)
        and n.name.startswith("reference_") and "batched" in n.name
        for n in tree.body)
    for n in batched_defs:
        if has_batched_oracle or _suppressed(lines, n.lineno,
                                             "oracle"):
            continue
        findings.append(Finding(
            path, n.lineno, "oracle",
            f"{n.name} builds a query-batched variant but the module "
            f"has no reference_*batched* NumPy oracle — batched "
            f"device code needs its columns-vs-independent-runs "
            f"oracle first (CLAUDE.md convention; ROADMAP item 2)"))
        break
    # incremental revalidation (round 20, live graphs): a module
    # shipping an incremental builder/revalidator must also ship its
    # incremental oracle — the proved-equal-to-full-recompute-at-the-
    # same-epoch contract (lux_tpu/livegraph.py) needs a NumPy
    # reference_*_incremental to be provable at all
    # ast.walk, not tree.body: the revalidator may be a METHOD
    # (LiveGraph.revalidate is exactly this shape) — a top-level-only
    # scan is dead for class-based code
    incr_defs = [n for n in ast.walk(tree)
                 if isinstance(n, ast.FunctionDef)
                 and ("incremental" in n.name
                      or "revalidate" in n.name)
                 and not n.name.startswith("reference_")]
    # the oracle may live in another module per convention ("oracle
    # in its app module or test") — an explicit reference_*incremental
    # citation anywhere in the source (docstring pointer, import)
    # satisfies the check; a module naming NO oracle at all fails
    has_incr_oracle = any(
        isinstance(n, ast.FunctionDef)
        and n.name.startswith("reference_")
        and "incremental" in n.name
        for n in tree.body) or bool(
            re.search(r"reference_\w*incremental", "\n".join(lines)))
    for n in incr_defs:
        if has_incr_oracle or _suppressed(lines, n.lineno, "oracle"):
            continue
        findings.append(Finding(
            path, n.lineno, "oracle",
            f"{n.name} builds an incremental-revalidation variant "
            f"but the module has no reference_*_incremental NumPy "
            f"oracle — incremental device code must be proved equal "
            f"to full recompute at the same epoch (CLAUDE.md "
            f"convention; lux_tpu/livegraph.py round 20)"))
        break
    return findings


def check_decremental_oracle(path, tree, lines):
    """Round 21 (mutation algebra): a deletion-capable builder — any
    def with ``decremental`` in its name, or named ``delete_edges`` /
    ``reweight_edges`` — must be provable against a decremental NumPy
    oracle: the module defines a ``reference_*decremental`` function
    or cites one (apps/sssp.reference_sssp_decremental,
    apps/components.reference_components_decremental).  Anti-monotone
    re-seed results (lux_tpu/livegraph.py) are proved equal to full
    recompute at the same epoch — deletion code with no decremental
    reference cannot carry that proof.  Same shape as the incremental
    rule above; ast.walk because the builders are METHODS."""
    decr_defs = [n for n in ast.walk(tree)
                 if isinstance(n, ast.FunctionDef)
                 and ("decremental" in n.name
                      or n.name in ("delete_edges", "reweight_edges"))
                 and not n.name.startswith("reference_")]
    if not decr_defs:
        return []
    has_decr_oracle = any(
        isinstance(n, ast.FunctionDef)
        and n.name.startswith("reference_")
        and "decremental" in n.name
        for n in ast.walk(tree)) or bool(
            re.search(r"reference_\w*decremental", "\n".join(lines)))
    findings = []
    for n in decr_defs:
        if has_decr_oracle or _suppressed(lines, n.lineno, "oracle"):
            continue
        findings.append(Finding(
            path, n.lineno, "oracle",
            f"{n.name} is a deletion-capable builder but the module "
            f"neither defines nor cites a reference_*decremental "
            f"NumPy oracle — anti-monotone mutations must be proved "
            f"equal to full recompute at the same epoch (CLAUDE.md "
            f"convention; lux_tpu/livegraph.py round 21)"))
        break
    return findings


# ---------------------------------------------------------------------
# check: byte-budgeted consumers register a gauge


def check_budget_gauge(path, tree, lines):
    """Round 22 (memory observatory): a memory-consumer class with a
    byte budget — any class whose ``__init__`` assigns
    ``self.max_bytes`` — must register a metrics gauge (reference a
    ``.gauge(`` call somewhere in the class) so its live occupancy is
    observable.  A budgeted consumer with no gauge is a byte ceiling
    the observatory cannot see approaching: the ledger can price it
    but no trail can watch it fill (lux_tpu/memwatch.py; the
    AnswerCache serve_cache_bytes gauge is the template).  Runs
    TREE-WIDE like the decremental rule — consumers live in serve.py
    / livegraph.py, not one directory."""
    findings = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        init = next((n for n in cls.body
                     if isinstance(n, ast.FunctionDef)
                     and n.name == "__init__"), None)
        if init is None:
            continue
        budgeted = any(
            isinstance(t, ast.Attribute) and t.attr == "max_bytes"
            and isinstance(t.value, ast.Name) and t.value.id == "self"
            for n in ast.walk(init) if isinstance(n, ast.Assign)
            for t in n.targets)
        if not budgeted:
            continue
        if _suppressed(lines, cls.lineno, "budget-gauge"):
            continue
        has_gauge = any(
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "gauge"
            for n in ast.walk(cls))
        if not has_gauge:
            findings.append(Finding(
                path, cls.lineno, "budget-gauge",
                f"{cls.name} budgets bytes (self.max_bytes) but "
                f"registers no metrics gauge — a byte ceiling the "
                f"memory observatory cannot watch fill "
                f"(lux_tpu/memwatch.py round 22; see "
                f"AnswerCache.set_metrics for the convention)"))
    return findings


# ---------------------------------------------------------------------
# check: citation presence


def check_citation(path, tree, lines):
    if os.path.basename(path) == "__init__.py":
        return []
    doc = ast.get_docstring(tree) or ""
    if CITATION_RE.search(doc) or _suppressed(lines, 1, "citation"):
        return []
    return [Finding(
        path, 1, "citation",
        "module docstring cites no reference file:line — engine/ops "
        "modules must anchor their design to the reference "
        "implementation for parity auditing (CLAUDE.md conventions)")]


# ---------------------------------------------------------------------
# check: per-part stats variants carry their per-part oracle test

PART_STATS_DOC = "per-part"
PART_ORACLE_TOKEN = re.compile(r"\bper_part\w*")
_TESTS_CACHE: list[str] | None = None


def _test_texts() -> list[str]:
    """Cached source texts of every tests/*.py (coverage scan)."""
    global _TESTS_CACHE
    if _TESTS_CACHE is None:
        texts = []
        tdir = os.path.join(REPO, "tests")
        if os.path.isdir(tdir):
            for f in sorted(os.listdir(tdir)):
                if f.endswith(".py"):
                    try:
                        with open(os.path.join(tdir, f)) as fh:
                            texts.append(fh.read())
                    except OSError:
                        continue
        _TESTS_CACHE = texts
    return _TESTS_CACHE


def check_part_stats_oracle(path, tree, lines):
    """Engine loop variants citing per-part counters must carry a
    per-part oracle test (see module docstring)."""
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        if not (node.name.endswith("_stats")
                or node.name.endswith("_health")):
            continue
        doc = ast.get_docstring(node) or ""
        if PART_STATS_DOC not in doc.lower():
            continue
        if _suppressed(lines, node.lineno, "part-stats-oracle"):
            continue
        covered = any(node.name in txt
                      and PART_ORACLE_TOKEN.search(txt)
                      for txt in _test_texts())
        if not covered:
            findings.append(Finding(
                path, node.lineno, "part-stats-oracle",
                f"{node.name} cites per-part counters but no test "
                f"under tests/ references it together with a "
                f"per_part* NumPy oracle — per-part counter "
                f"variants need their sum-over-parts-bitwise proof "
                f"(CLAUDE.md: new device code gets an oracle test "
                f"first)"))
    return findings


# ---------------------------------------------------------------------
# check: every faults.py plan action is drilled by some test

ACTION_NAME_RE = re.compile(r"^[A-Z][A-Z0-9_]*$")


def check_chaos_coverage(path, tree, lines):
    """Every fault-plan action constant (module-level ALL-CAPS name
    bound to a string literal in lux_tpu/faults.py) must appear — by
    constant name or by string value — in at least one tests/ file.
    An undrilled fault action is an untested recovery path (see
    module docstring)."""
    findings = []
    for node in tree.body:
        if not (isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and ACTION_NAME_RE.match(node.targets[0].id)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            continue
        name, value = node.targets[0].id, node.value.value
        if _suppressed(lines, node.lineno, "chaos-coverage"):
            continue
        covered = any(name in txt or value in txt
                      for txt in _test_texts())
        if not covered:
            findings.append(Finding(
                path, node.lineno, "chaos-coverage",
                f"fault action {name} = {value!r} is drilled by no "
                f"file under tests/ — a fault action nobody injects "
                f"is a recovery path that ships untested (faults.py's "
                f"whole purpose); add a drill or suppress with a "
                f"justification"))
    return findings


# ---------------------------------------------------------------------
# check: no metrics calls in engine device code / fused-loop bodies

# callable POSITIONAL slots per loop primitive (fori_loop(lo, hi,
# body, init): only arg 2 is traced code — treating bounds/init
# Names as body functions would scan unrelated same-named helpers)
LOOP_BODY_ARGS = {"fori_loop": (2,), "while_loop": (0, 1),
                  "scan": (0,)}
LOOP_BODY_KEYWORDS = {"body_fun", "cond_fun", "f", "body"}


def _references_metrics(expr) -> bool:
    """Does this call-target expression reach through a ``metrics``
    name or attribute (``metrics.counter(...)``,
    ``self.metrics.histogram(...).observe(...)``)?"""
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id == "metrics":
            return True
        if isinstance(n, ast.Attribute) and n.attr == "metrics":
            return True
    return False


def _loop_body_targets(tree):
    """AST nodes whose bodies trace into fused loops: functions
    passed by name — and lambdas passed inline — in the CALLABLE
    slots of fori_loop/while_loop/scan calls (positional body/cond
    slots + the body_fun/cond_fun/f keywords; bounds and init-state
    arguments are data, never loop bodies)."""
    body_names, lambdas = set(), []
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        fname = f.attr if isinstance(f, ast.Attribute) \
            else getattr(f, "id", None)
        if fname not in LOOP_BODY_ARGS:
            continue
        slots = [n.args[i] for i in LOOP_BODY_ARGS[fname]
                 if i < len(n.args)]
        slots += [kw.value for kw in n.keywords
                  if kw.arg in LOOP_BODY_KEYWORDS]
        for a in slots:
            if isinstance(a, ast.Name):
                body_names.add(a.id)
            elif isinstance(a, ast.Lambda):
                lambdas.append(a)
    return lambdas + [n for n in ast.walk(tree)
                      if isinstance(n, ast.FunctionDef)
                      and n.name in body_names]


def check_hot_path_metrics(path, tree, lines, whole_file: bool):
    """Flag metrics calls in device code (see module docstring):
    the WHOLE file for engine/ops modules, fused-loop bodies
    everywhere else in the library tree."""
    findings = []
    targets = [tree] if whole_file else _loop_body_targets(tree)
    seen = set()
    for t in targets:
        for n in ast.walk(t):
            if not (isinstance(n, ast.Call)
                    and _references_metrics(n.func)):
                continue
            line = getattr(n, "lineno", 1)
            if line in seen or _suppressed(lines, line,
                                           "hot-path-metrics"):
                continue
            seen.add(line)
            where = ("engine device code" if whole_file
                     else "a fused-loop body")
            findings.append(Finding(
                path, line, "hot-path-metrics",
                f"metrics call inside {where} — metrics are "
                f"host-side, segment-boundary only "
                f"(lux_tpu/metrics.py contract; the audited "
                f"callback-in-loop ban's source-level twin)"))
    return findings


# ---------------------------------------------------------------------
# check: collective primitives stay inside ops/ + engine/

COLLECTIVE_CALLS = {
    "ppermute", "all_to_all", "psum_scatter", "reduce_scatter",
    "all_gather", "psum", "pmin", "pmax",
}


def check_collective_scope(path, tree, lines):
    """Flag collective-primitive calls outside the audited trees (see
    module docstring): the byte ledger's oracle predicts collectives
    from engine layout config, so one planted elsewhere in the
    library is invisible to both the schedule audit and the ledger."""
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) \
            else getattr(f, "id", None)
        if name not in COLLECTIVE_CALLS:
            continue
        line = getattr(node, "lineno", 1)
        if _suppressed(lines, line, "collective-scope"):
            continue
        findings.append(Finding(
            path, line, "collective-scope",
            f"{name} call outside lux_tpu/ops/ + lux_tpu/engine/ — "
            f"the collective-schedule audit and the comm byte ledger "
            f"(lux_tpu/comms.py) only account collectives in those "
            f"trees; move it behind an op interface or carry an "
            f"explicit pragma with the justification"))
    return findings


# ---------------------------------------------------------------------
# check: no block_until_ready fencing in benchmark scripts


def check_bench_fence(path, tree, lines):
    """scripts/ may not fence timed regions with block_until_ready
    (see module docstring): flag any call or attribute reference."""
    findings = []
    for node in ast.walk(tree):
        name = None
        if isinstance(node, ast.Attribute) \
                and node.attr == "block_until_ready":
            name = node.attr
        elif isinstance(node, ast.Name) \
                and node.id == "block_until_ready":
            name = node.id
        if name is None:
            continue
        line = getattr(node, "lineno", 1)
        if _suppressed(lines, line, "bench-fence"):
            continue
        findings.append(Finding(
            path, line, "bench-fence",
            "block_until_ready fencing in a benchmark script — "
            "hand-rolled timed dispatches are where XLA hoists "
            "loop-invariant work (PERF_NOTES traps); use "
            "lux_tpu.timing.loop_bench (loop-dependent carry, "
            "scalar output, one jit, fetch fence)"))
    return findings


# ---------------------------------------------------------------------
# driver


EVENT_EMIT_NAMES = {"emit", "_emit", "emit_sampled"}

_KNOWN_EVENTS_CACHE = None


def _known_events() -> set:
    """events_summary.py's KNOWN set, parsed statically (no import:
    the linter stays dependency-free)."""
    global _KNOWN_EVENTS_CACHE
    if _KNOWN_EVENTS_CACHE is None:
        path = os.path.join(REPO, "scripts", "events_summary.py")
        known = set()
        try:
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in tree.body:
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and node.targets[0].id == "KNOWN":
                    known = set(ast.literal_eval(node.value))
        except (OSError, SyntaxError, ValueError):
            pass
        _KNOWN_EVENTS_CACHE = known
    return _KNOWN_EVENTS_CACHE


def check_event_names(path, tree, lines):
    """event-name: every string LITERAL passed to a telemetry
    ``emit(...)`` / ``_emit(...)`` / ``emit_sampled(...)`` must be
    in events_summary.py's KNOWN set.  Without this, a new emitter
    fails the runtime events audit only when its event first FIRES
    — often a chaos leg nobody runs locally.  Adding the name to
    KNOWN (with its schema note) is the fix; a deliberate
    out-of-catalogue event carries ``# audit: allow(event-name)``
    with justification."""
    known = _known_events()
    if not known:
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        f = node.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None)
        if name not in EVENT_EMIT_NAMES:
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)):
            continue
        if arg.value in known:
            continue
        if _suppressed(lines, node.lineno, "event-name"):
            continue
        findings.append(Finding(
            path, node.lineno, "event-name",
            f"emit({arg.value!r}) is not in events_summary.KNOWN "
            f"— add the event name to the KNOWN catalogue so the "
            f"runtime audit recognizes it before it first fires"))
    return findings


DOC_COMMAND_RE = re.compile(
    r"python\s+-m\s+(lux_tpu(?:\.[A-Za-z_][A-Za-z0-9_]*)+)")


def check_doc_commands(repo: str = REPO):
    """command-drift: every ``python -m lux_tpu.<mod>`` cited in
    CLAUDE.md / ARCHITECTURE.md must resolve to a module with an
    ``if __name__ == "__main__"`` entry (or a package __main__.py)
    — the docs can no longer name a smoke that doesn't exist."""
    findings = []
    for doc in ("CLAUDE.md", "ARCHITECTURE.md"):
        p = os.path.join(repo, doc)
        if not os.path.isfile(p):
            continue
        with open(p) as f:
            doc_lines = f.read().splitlines()
        for i, line in enumerate(doc_lines, 1):
            for m in DOC_COMMAND_RE.finditer(line):
                dotted = m.group(1)
                base = os.path.join(repo, *dotted.split("."))
                mod_py = base + ".py"
                pkg_main = os.path.join(base, "__main__.py")
                if os.path.isfile(pkg_main):
                    continue
                if not os.path.isfile(mod_py):
                    msg = (f"cites `python -m {dotted}` but no such "
                           f"module exists")
                else:
                    with open(mod_py) as f:
                        src = f.read()
                    if "__main__" in src:
                        continue
                    msg = (f"cites `python -m {dotted}` but "
                           f"{os.path.relpath(mod_py, repo)} has no "
                           f"`if __name__ == \"__main__\"` entry")
                if _suppressed(doc_lines, i, "command-drift"):
                    continue
                findings.append(Finding(p, i, "command-drift", msg))
    return findings


def lint_file(path: str):
    with open(path) as f:
        src = f.read()
    lines = src.splitlines()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 1, "parse",
                        f"syntax error: {e.msg}")]
    norm = path.replace(os.sep, "/")
    if "/scripts/" in norm:
        # benchmark scripts get ONLY the fencing gate — they are
        # exploratory by design and exempt from the library-tree
        # conventions (jit closures, oracles, citations) — plus the
        # event-name catalogue check (their emits feed the same
        # runtime audit)
        return (check_bench_fence(path, tree, lines)
                + check_event_names(path, tree, lines))
    findings = check_jit_closures(path, tree, lines)
    findings += check_event_names(path, tree, lines)
    findings += check_hot_path_metrics(
        path, tree, lines,
        whole_file=("/lux_tpu/engine/" in norm
                    or "/lux_tpu/ops/" in norm))
    if "/lux_tpu/engine/" not in norm and "/lux_tpu/ops/" not in norm:
        findings += check_collective_scope(path, tree, lines)
    if "/lux_tpu/apps/" in norm:
        findings += check_oracle(path, tree, lines)
    # decremental rule runs TREE-WIDE: the deletion-capable builders
    # live in lux_tpu/livegraph.py, not under apps/
    findings += check_decremental_oracle(path, tree, lines)
    # budget-gauge rule runs TREE-WIDE too: byte-budgeted consumers
    # live in serve.py / livegraph.py, not one directory
    findings += check_budget_gauge(path, tree, lines)
    if "/lux_tpu/engine/" in norm or "/lux_tpu/ops/" in norm:
        findings += check_citation(path, tree, lines)
    if "/lux_tpu/engine/" in norm:
        findings += check_part_stats_oracle(path, tree, lines)
    if norm.endswith("/lux_tpu/faults.py"):
        findings += check_chaos_coverage(path, tree, lines)
    return findings


def iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            yield p
        elif os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                if "__pycache__" in root:
                    continue
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def lint_paths(paths):
    findings = []
    for f in iter_py_files(paths):
        findings += lint_file(os.path.abspath(f))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="AST convention linter (jit closures, app "
                    "oracles, reference citations, script bench "
                    "fencing)")
    ap.add_argument("paths", nargs="*",
                    default=[os.path.join(REPO, "lux_tpu"),
                             os.path.join(REPO, "scripts")])
    ap.add_argument("-q", action="store_true", dest="quiet")
    args = ap.parse_args(argv)

    findings = lint_paths(args.paths)
    # repo-level doc checks run regardless of the path selection:
    # the cited-command catalogue lives in CLAUDE.md/ARCHITECTURE.md
    findings += check_doc_commands()
    for f in findings:
        print(str(f), file=sys.stderr)
    if findings:
        print(f"lint_lux: {len(findings)} finding(s) — FAILED",
              file=sys.stderr)
        return 1
    if not args.quiet:
        print("lint_lux: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
