"""lux_tpu/audit.py: the compile-time program auditor.

Three layers:
- one deliberately-violating synthetic program per check class, each
  raising the NAMED AuditError subclass;
- bitwise no-op proof: ``audit=`` never alters compiled outputs;
- the repo-wide audit + AST lint (the tier-1 gate): every engine
  configuration's every program variant, clean on the CPU backend —
  budgeted well under 60 s.
"""

import functools
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lux_tpu import audit
from lux_tpu.audit import (AuditError, CallbackInLoopError,
                           CollectiveScheduleError, ConstBytesError,
                           DtypeDisciplineError, GatherBudgetError,
                           IdentityInitError, LedgerDriftError,
                           LoopInvariantError, ProgramSpec)
from lux_tpu.graph import Graph

REPO = Path(__file__).resolve().parent.parent


def _graph(nv=256, ne=2048, weighted=False, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.integers(1, 6, ne).astype(np.float32) if weighted else None
    return Graph.from_edges(rng.integers(0, nv, ne),
                            rng.integers(0, nv, ne), nv, weights=w)


def _raise_all(findings, **kw):
    audit.raise_findings(findings, **kw)


# ---------------------------------------------------------------------
# synthetic violators — one per check class


def test_gather_budget_violation():
    """Two per-element gathers from the state table inside one fused
    loop body: the dense-iteration contract is ONE (mask pre-gather,
    PERF_NOTES)."""
    table_shape = (1024,)

    def bad(s, table, idx):
        def body(i, acc):
            a = jnp.take(table, idx + i, axis=0)        # gather 1
            b = jnp.take(table, idx * 2 + i, axis=0)    # gather 2
            return acc + jnp.sum(a) + jnp.sum(b)

        return jax.lax.fori_loop(0, 4, body, s)

    closed = jax.make_jaxpr(bad)(
        jnp.float32(0), jnp.zeros(table_shape, jnp.float32),
        jnp.zeros((16,), jnp.int32))
    spec = ProgramSpec(table_shape=table_shape, gather_budget=1)
    findings = audit.audit_jaxpr(closed, spec, where="synthetic")
    assert any(f.check == "gather-budget" for f in findings)
    with pytest.raises(GatherBudgetError):
        _raise_all(findings)

    # the same body under budget 2 is clean
    spec2 = ProgramSpec(table_shape=table_shape, gather_budget=2)
    fs2 = audit.check_gather_budget(closed, spec2, "synthetic")
    assert fs2 == []


def test_const_bytes_violation():
    """A closed-over 2 MB constant bakes into the program (bloating
    it and its compile) — caught at trace time, before anything
    compiles."""
    big = jnp.zeros((1 << 19,), jnp.float32)          # 2 MiB
    closed = jax.make_jaxpr(lambda x: x + jnp.sum(big))(
        jnp.float32(1))
    findings = audit.audit_jaxpr(closed, ProgramSpec(),
                                 where="synthetic")
    assert any(f.check == "const-bytes" for f in findings)
    with pytest.raises(ConstBytesError):
        _raise_all(findings)

    # passing the array as an ARGUMENT is the fix
    ok = jax.make_jaxpr(lambda x, b: x + jnp.sum(b))(
        jnp.float32(1), big)
    assert audit.check_const_bytes(ok, ProgramSpec(), "s") == []


def test_dtype_discipline_violation():
    """f64 avals (or any promotion past the state dtype) are
    forbidden — TPUs run 32-bit and silent x64 promotions double
    every table."""
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(
            jnp.ones((8,), jnp.float32))
    findings = audit.audit_jaxpr(closed, ProgramSpec(),
                                 where="synthetic")
    assert any(f.check == "dtype-discipline" for f in findings)
    with pytest.raises(DtypeDisciplineError):
        _raise_all(findings)

    # an 8-byte state dtype legitimizes 8-byte avals
    spec = ProgramSpec(state_itemsize=8)
    assert audit.check_dtypes(closed, spec, "s") == []


def test_loop_invariant_violation():
    """An expensive dot of two loop-invariant operands inside a
    fori_loop body: XLA hoists it, so a benchmark timing the loop
    measures nothing (the CLAUDE.md trap) — a warning-class
    finding."""

    def bad(A, B, s0):
        def body(i, s):
            return s + jnp.sum(jnp.dot(A, B))     # A, B invariant

        return jax.lax.fori_loop(0, 8, body, s0)

    closed = jax.make_jaxpr(bad)(
        jnp.zeros((64, 64), jnp.float32),
        jnp.zeros((64, 64), jnp.float32), jnp.float32(0))
    findings = audit.audit_jaxpr(closed, ProgramSpec(),
                                 where="synthetic")
    inv = [f for f in findings if f.check == "loop-invariant"]
    assert inv and all(f.severity == "warn" for f in inv)
    _raise_all(findings)          # warnings alone do not raise...
    with pytest.raises(LoopInvariantError):      # ...unless asked
        _raise_all(findings, warnings_as_errors=True)

    # a dot CONSUMING the carry is loop-variant and clean
    def good2(A, s0):
        def body(i, s):
            return s + jnp.dot(A, s)
        return jax.lax.fori_loop(0, 8, body, s0)

    ok = jax.make_jaxpr(good2)(jnp.zeros((64, 64), jnp.float32),
                               jnp.zeros((64,), jnp.float32))
    assert audit.check_loop_invariant(ok, ProgramSpec(), "s") == []


def test_collective_schedule_violation():
    """A 'ring' taking ndev hops instead of ndev-1, and an owner
    exchange without its generation scan."""
    from lux_tpu.parallel.mesh import PARTS_AXIS, make_mesh
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh(2)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(PARTS_AXIS), out_specs=P(PARTS_AXIS))
    def bad_ring(x):
        for _ in range(2):                       # ndev hops: one too many
            x = jax.lax.ppermute(x, PARTS_AXIS, [(0, 1), (1, 0)])
        return x

    closed = jax.make_jaxpr(bad_ring)(jnp.zeros((2, 8), jnp.float32))
    spec = ProgramSpec(ppermute_hops=1, ring_size=2)
    findings = audit.audit_jaxpr(closed, spec, where="synthetic")
    assert any(f.check == "collective-schedule" for f in findings)
    with pytest.raises(CollectiveScheduleError):
        _raise_all(findings)

    # missing generation scan (require_scan_len with no scan at all)
    closed2 = jax.make_jaxpr(lambda x: x * 2)(jnp.zeros((4,)))
    fs2 = audit.check_collectives(
        closed2, ProgramSpec(require_scan_len=4), "synthetic")
    assert fs2 and fs2[0].check == "collective-schedule"
    with pytest.raises(CollectiveScheduleError):
        _raise_all(fs2)

    # a scan of the right LENGTH that never gathers from the state
    # shard (e.g. the fused iteration loop when num_iters happens to
    # equal the local part count) must NOT satisfy the owner check
    closed3 = jax.make_jaxpr(
        lambda x: jax.lax.fori_loop(0, 4, lambda i, s: s * 2.0, x))(
        jnp.float32(1))
    fs3 = audit.check_collectives(
        closed3, ProgramSpec(require_scan_len=4,
                             require_scan_shard_shape=(64,)),
        "synthetic")
    assert fs3 and fs3[0].check == "collective-schedule"


def test_callback_in_loop_violation():
    """A host callback inside a fused loop is a per-iteration host
    round-trip — the exact failure the fused designs exist to
    avoid."""

    def bad(s):
        def body(i, acc):
            jax.debug.print("iter {i}", i=i)
            return acc + 1.0

        return jax.lax.fori_loop(0, 4, body, s)

    closed = jax.make_jaxpr(bad)(jnp.float32(0))
    findings = audit.audit_jaxpr(closed, ProgramSpec(),
                                 where="synthetic")
    assert any(f.check == "callback-in-loop" for f in findings)
    with pytest.raises(CallbackInLoopError):
        _raise_all(findings)

    # pure_callback is flagged too
    def bad2(s):
        def body(i, acc):
            v = jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct((), jnp.float32),
                acc)
            return acc + v

        return jax.lax.fori_loop(0, 4, body, s)

    closed2 = jax.make_jaxpr(bad2)(jnp.float32(0))
    fs2 = audit.check_callbacks(closed2, ProgramSpec(), "s")
    assert fs2

    # the SAME callback outside any loop is fine (fetch at segment
    # boundaries is the sanctioned pattern)
    closed3 = jax.make_jaxpr(
        lambda s: jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct((), jnp.float32), s))(
        jnp.float32(0))
    assert audit.check_callbacks(closed3, ProgramSpec(), "s") == []


def test_identity_init_violation():
    """A scatter-min onto a zeros-initialized buffer clamps every
    positive result — init must be the reduce identity (+inf)."""
    closed = jax.make_jaxpr(
        lambda v, i: jnp.zeros((8,), jnp.float32).at[i].min(v))(
        jnp.ones((16,), jnp.float32), jnp.zeros((16,), jnp.int32))
    findings = audit.audit_jaxpr(closed, ProgramSpec(),
                                 where="synthetic")
    assert any(f.check == "identity-init" for f in findings)
    with pytest.raises(IdentityInitError):
        _raise_all(findings)

    # the identity-initialized form is clean, and so is reducing
    # onto CARRIED data (a semantic relaxation, not an init)
    ok = jax.make_jaxpr(
        lambda v, i: jnp.full((8,), jnp.inf, jnp.float32)
        .at[i].min(v))(
        jnp.ones((16,), jnp.float32), jnp.zeros((16,), jnp.int32))
    assert audit.check_identity_inits(ok, ProgramSpec(), "s") == []
    carried = jax.make_jaxpr(
        lambda lab, v, i: lab.at[i].min(v))(
        jnp.ones((8,), jnp.float32), jnp.ones((16,), jnp.float32),
        jnp.zeros((16,), jnp.int32))
    assert audit.check_identity_inits(carried, ProgramSpec(),
                                      "s") == []


def test_ledger_drift_violation():
    """On a toy graph the tiled arrays' chunk padding dwarfs the
    epad-priced ledger; a near-zero tolerance turns that into the
    drift error (the stated default tolerance absorbs it only on
    dense graphs — see the audit module docstring)."""
    from lux_tpu.apps import pagerank
    eng = pagerank.build_engine(_graph(64, 400), num_parts=2)
    findings = audit.check_ledger(eng, tol=0.001)
    assert findings and findings[0].check == "ledger-drift"
    with pytest.raises(LedgerDriftError):
        _raise_all(findings)

    # a bench-shaped graph passes at the stated tolerance
    eng2 = pagerank.build_engine(_graph(2048, 32768, seed=2),
                                 num_parts=2)
    assert audit.check_ledger(eng2, tol=0.5) == []


# ---------------------------------------------------------------------
# allow= / pragma mechanics


def test_frontier_marks_need_no_pragma():
    """The push sparse path's CSR-expand marks are dropped by
    scatter-ADD into zeros, which IS the identity init (PR 46; the
    scatter-max into zeros they replaced lived on a pragma):
    engine/frontier.py carries no ``# audit: allow`` at all, and the
    audit is clean all the same."""
    import inspect

    from lux_tpu.apps import sssp
    from lux_tpu.engine import frontier
    assert "audit: allow" not in inspect.getsource(frontier)
    eng = sssp.build_engine(_graph(), 0, num_parts=2)
    findings = audit.audit_engine(eng, mode=None)
    assert [f for f in findings if f.check == "identity-init"] == []


@pytest.mark.parametrize("use_mxu", [False, True])
def test_ladder_budgets_restated_per_rung(use_mxu, monkeypatch):
    """PR 29: the sparse branch is instantiated once per (queue rung,
    budget rung) of the ladder, and the static budgets hold for EACH
    instantiation rather than as a loosened total.  Since PR 46 every
    program variant carries, per pair, ONE CSR-expand marks
    scatter-add whose operand holds the three channels of a budget
    end to end (``[P_local, 3 x stride]``: owner, edge offset, the
    int32 label's bits), ``len(queue_rungs)`` of each size; the MXU
    form sums the owner's channel apart, so it has two (``[stride]``
    and ``[2 x stride]``).  No variant makes a budget-sized gather
    from a queue-sized table: nothing a slot needs of its item is
    fetched.  Nothing surfaces under frontier.py with pragmas
    ignored (zeros under a scatter-add are the identity init), and
    the state-table gather budget of the fused loop stays what the
    dense branch alone spends."""
    from lux_tpu.apps import sssp
    from lux_tpu.engine import frontier
    eng = sssp.build_engine(_graph(), 0, num_parts=2, use_mxu=use_mxu)
    q_rungs, eb_rungs = eng.queue_rungs, eng.budget_rungs
    assert len(q_rungs) >= 2 and len(eb_rungs) >= 2
    assert q_rungs[-1] == eng.queue_cap
    assert eb_rungs[-1] == eng.edge_budget
    stride = [-(-(eb + 1) // frontier.SLOT_ALIGN) * frontier.SLOT_ALIGN
              for eb in eb_rungs]
    channels = (1, 2) if use_mxu else (3,)
    want = sorted(c * st for st in stride for c in channels
                  for _ in q_rungs)
    queues = {eng.sg.num_parts * q for q in q_rungs}
    variants = eng.audit_programs()
    for name, (jitted, thunk) in variants.items():
        closed = audit.trace_variant(jitted, thunk())
        marks, fetched = [], []
        for eqn, _, _ in audit._iter_eqns(closed.jaxpr):
            if eqn.primitive.name not in ("scatter-add", "gather"):
                continue
            aval = eqn.invars[0].aval
            if eqn.primitive.name == "scatter-add" \
                    and aval.dtype == np.int32 \
                    and aval.shape[-1] in want:
                marks.append(aval.shape[-1])
            if eqn.primitive.name == "gather" \
                    and aval.shape[-1] in queues \
                    and eqn.outvars[0].aval.shape[-1] in eb_rungs:
                fetched.append(eqn.outvars[0].aval.shape)
        assert sorted(marks) == want, name
        assert fetched == [], name
    assert audit.audit_engine(eng, mode=None) == []
    monkeypatch.setattr(audit, "_pragma_allows",
                        lambda eqn, check, stack=(): False)
    bare = audit.audit_engine(eng, mode=None)
    assert [f for f in bare if "frontier.py" in f.where] == []
    assert {f.check for f in bare} <= {"identity-init"}


# ---------------------------------------------------------------------
# audit= is a bitwise no-op on compiled outputs


def test_audit_never_alters_pull_outputs():
    from lux_tpu.apps import pagerank
    g = _graph()
    eng_a = pagerank.build_engine(g, num_parts=2, audit="error")
    eng_b = pagerank.build_engine(g, num_parts=2)
    out_a = np.asarray(eng_a.run(eng_a.init_state(), 4))
    out_b = np.asarray(eng_b.run(eng_b.init_state(), 4))
    np.testing.assert_array_equal(out_a, out_b)   # bitwise


def test_audit_never_alters_push_outputs():
    from lux_tpu.apps import sssp
    g = _graph()
    eng_a = sssp.build_engine(g, 0, num_parts=2, audit="error")
    eng_b = sssp.build_engine(g, 0, num_parts=2)
    lab_a, it_a = eng_a.run()
    lab_b, it_b = eng_b.run()
    assert it_a == it_b
    np.testing.assert_array_equal(lab_a, lab_b)   # bitwise


@pytest.mark.parametrize("np_parts,mesh_n", [(2, 0), (8, 8)])
def test_audited_converge_programs_carry_the_sparse_iters_counter(
        np_parts, mesh_n):
    """PR 24 / PR 29: every converge variant the auditor walks (plain,
    stats, health) returns TWO more int32 scalars than its public
    signature (the ``sparse_iters`` and ``low_rung_iters`` carry) and,
    since PR 39, the ladder's fill words uint32 [4, 2] LAST, all
    replicated; the single-step program does not.  The audit stays
    clean with them."""
    import jax

    from lux_tpu.apps import sssp
    from lux_tpu.parallel.mesh import make_mesh
    eng = sssp.build_engine(_graph(), 0, num_parts=np_parts,
                            mesh=make_mesh(mesh_n) if mesh_n else None,
                            audit="error")
    outs = {}
    for name, (jitted, thunk) in eng.audit_programs().items():
        outs[name] = jax.eval_shape(jitted, *thunk())
    assert len(outs["step"]) == 3
    assert {n: len(o) for n, o in outs.items() if n != "step"} == {
        "converge": 3 + 3, "converge_stats": 7 + 3,
        "converge_health": 9 + 3}
    for name, out in outs.items():
        if name != "step":
            for counter in out[-3:-1]:
                assert counter.shape == () and counter.dtype == np.int32
            assert out[-1].shape == (4, 2)
            assert out[-1].dtype == np.uint32
    assert audit.audit_engine(eng, mode="error") == []


def test_audit_warn_mode_warns_not_raises(monkeypatch):
    """mode='warn' surfaces findings as AuditWarnings and returns
    them; mode='error' raises."""
    from lux_tpu.apps import pagerank
    g = _graph(64, 400)
    eng = pagerank.build_engine(g, num_parts=2)

    # inject a failing check by shrinking the const ceiling to 0
    real_spec = audit.engine_spec

    def tight_spec(engine, aval):
        return audit.ProgramSpec(
            **{**real_spec(engine, aval).__dict__,
               "const_bytes_max": -1})

    monkeypatch.setattr(audit, "engine_spec", tight_spec)
    with pytest.warns(audit.AuditWarning):
        fs = audit.audit_engine(eng, mode="warn")
    assert any(f.check == "const-bytes" for f in fs)
    with pytest.raises(ConstBytesError):
        audit.audit_engine(eng, mode="error")
    # allow= is the pragma mechanism's programmatic form
    fs = audit.audit_engine(eng, mode="error",
                            allow={"const-bytes"})
    assert fs == []


# ---------------------------------------------------------------------
# the tier-1 gate: repo-wide audit + AST lint, clean and fast


def test_repo_audit_clean():
    """Every engine configuration x every program variant traces and
    audits clean on the CPU backend (pragma-exempted findings
    included); the ledger cross-validation runs on the bench-shaped
    configs.  Budget: well under 60 s (measured ~5 s)."""
    findings = audit.run_repo_audit(ledger=True)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_repo_audit_cli():
    """``python -m lux_tpu.audit`` (tracing-only form) exits 0."""
    assert audit.main(["-no-ledger"]) == 0


def test_lint_repo_clean():
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_lint_event_name_drift(tmp_path):
    """Round-25 event-name check: an emit() with a string literal
    outside events_summary.KNOWN is drift (it would fail the
    runtime events audit only when it first fires); the pragma
    suppresses with justification."""
    bad = tmp_path / "emitter.py"
    bad.write_text(
        "def go(t):\n"
        "    t.emit(\"totally_unknown_event\", x=1)\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "event-name" in r.stderr

    ok = tmp_path / "ok.py"
    ok.write_text(
        "def go(t):\n"
        "    # audit: allow(event-name) test-only fixture event\n"
        "    t.emit(\"totally_unknown_event\", x=1)\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(ok)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


def test_lint_command_drift(tmp_path):
    """Round-25 command-drift check: a doc-cited
    ``python -m lux_tpu.<mod>`` must resolve to a module with a
    __main__ entry; the shipped docs are clean."""
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import lint_lux
    finally:
        sys.path.pop(0)
    (tmp_path / "CLAUDE.md").write_text(
        "smoke: `python -m lux_tpu.missing_mod`\n")
    (tmp_path / "lux_tpu").mkdir()
    (tmp_path / "lux_tpu" / "quiet.py").write_text(
        "def main():\n    return 0\n")
    (tmp_path / "ARCHITECTURE.md").write_text(
        "run `python -m lux_tpu.quiet` for the smoke\n")
    found = lint_lux.check_doc_commands(repo=str(tmp_path))
    checks = [f.check for f in found]
    assert checks.count("command-drift") == 2, found
    # the real repo docs resolve every cited command
    assert lint_lux.check_doc_commands() == []


def test_lockcheck_repo_clean():
    """The third enforcing tool (round 25): the host-concurrency &
    durability analyzer is green over the threaded serving modules
    — guarded-field, lock-order, durable-before-visible,
    snapshot-iteration, toctou-gate (tests/test_lockcheck.py holds
    the per-check violating fixtures).  Budget: ~2 s CPU."""
    from lux_tpu import lockcheck
    findings = lockcheck.run_lockcheck(mode="findings")
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_detects_and_suppresses(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\nimport jax.numpy as jnp\n\n\n"
        "def build(x):\n"
        "    big = jnp.asarray(x)\n\n"
        "    @jax.jit\n"
        "    def step(s):\n"
        "        return s + big\n\n"
        "    return step\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "jit-closure" in r.stderr

    ok = tmp_path / "ok.py"
    ok.write_text(
        "import jax\nimport jax.numpy as jnp\n\n\n"
        "def build(x):\n"
        "    big = jnp.asarray(x)\n\n"
        "    # audit: allow(jit-closure) — test fixture\n"
        "    @jax.jit\n"
        "    def step(s):\n"
        "        return s + big\n\n"
        "    return step\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(ok)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


def test_lint_hot_path_metrics(tmp_path):
    """Round-17 hot-path-metrics check: a metrics call inside engine
    device code or a fused-loop body is flagged (metrics are
    host-side, segment-boundary only); host-side calls outside loop
    bodies pass, and the pragma suppresses per convention."""
    eng = tmp_path / "lux_tpu" / "engine"
    eng.mkdir(parents=True)
    bad_eng = eng / "bad.py"
    bad_eng.write_text(
        '"""Demo engine. reference pull_model.inl:423"""\n\n\n'
        "def build(metrics):\n"
        "    metrics.counter('x').inc()\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad_eng)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "hot-path-metrics" in r.stderr

    loopy = tmp_path / "lux_tpu" / "loopy.py"
    loopy.write_text(
        "import jax\n\n\n"
        "def run(self):\n"
        "    def body(i, c):\n"
        "        self.metrics.gauge('g').set(i)\n"
        "        return c\n"
        "    return jax.lax.fori_loop(0, 3, body, 0)\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(loopy)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "fused-loop body" in r.stderr

    # host-side (boundary) calls outside loop bodies are the contract
    fine = tmp_path / "lux_tpu" / "fine.py"
    fine.write_text(
        "def boundary(self, queued):\n"
        "    self.metrics.gauge('serve_queue_depth').set(queued)\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(fine)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr

    loopy.write_text(
        "import jax\n\n\n"
        "def run(self):\n"
        "    def body(i, c):\n"
        "        # audit: allow(hot-path-metrics) test fixture\n"
        "        self.metrics.gauge('g').set(i)\n"
        "        return c\n"
        "    return jax.lax.fori_loop(0, 3, body, 0)\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(loopy)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


def test_lint_batched_oracle_coverage(tmp_path):
    """An app module shipping a batched builder without its batched
    oracle is flagged (ROADMAP item 2 oracle-first contract); adding
    the reference_*batched* oracle clears it."""
    apps = tmp_path / "lux_tpu" / "apps"
    apps.mkdir(parents=True)
    bad = apps / "newapp.py"
    bad.write_text(
        "def make_batched_program(sources):\n    return None\n\n\n"
        "def reference_newapp(g):\n    return None\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "batched" in r.stderr and "oracle" in r.stderr

    bad.write_text(
        "def make_batched_program(sources):\n    return None\n\n\n"
        "def reference_newapp_batched(g, sources):\n    return None\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


def test_lint_incremental_oracle_coverage(tmp_path):
    """Round 20 (live graphs): an app module shipping an incremental
    builder/revalidator without its reference_*_incremental oracle is
    flagged — incremental device code must be provable equal to full
    recompute at the same epoch (lux_tpu/livegraph.py); adding the
    oracle clears it."""
    apps = tmp_path / "lux_tpu" / "apps"
    apps.mkdir(parents=True)
    bad = apps / "newapp.py"
    bad.write_text(
        "def build_incremental_step(g):\n    return None\n\n\n"
        "def reference_newapp(g):\n    return None\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "incremental" in r.stderr and "oracle" in r.stderr

    bad.write_text(
        "def build_incremental_step(g):\n    return None\n\n\n"
        "def reference_newapp(g):\n    return None\n\n\n"
        "def reference_newapp_incremental(g, old):\n    return None\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr

    # a METHOD revalidator (the LiveGraph.revalidate shape) is
    # caught too — tree.body-only scans are blind to it
    bad.write_text(
        "class Live:\n"
        "    def revalidate(self, eng):\n        return None\n\n\n"
        "def reference_newapp(g):\n    return None\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "incremental" in r.stderr and "oracle" in r.stderr

    # ... and an explicit cross-module oracle citation clears it
    # (the convention allows the oracle to live in its app module)
    bad.write_text(
        "class Live:\n"
        "    def revalidate(self, eng):\n"
        "        '''proved equal to apps/sssp."
        "reference_sssp_incremental'''\n"
        "        return None\n\n\n"
        "def reference_newapp(g):\n    return None\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(bad)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


def test_lint_chaos_coverage(tmp_path):
    """Round 24 (self-healing): every fault-plan action constant in
    lux_tpu/faults.py must be drilled by some tests/ file — an action
    nobody injects is a recovery path that ships untested.  A bogus
    undrilled action is flagged, the pragma suppresses it, and a
    really-drilled action (WORKER_KILL) passes."""
    pkg = tmp_path / "lux_tpu"
    pkg.mkdir(parents=True)
    fake = pkg / "faults.py"
    # build the undrilled name/value by concatenation — writing them
    # as literals HERE would put them in tests/ and satisfy the scan
    name = "BOGUS_" + "UNDRILLED"
    value = "bogus_" + "undrilled_xyz"
    fake.write_text(f'{name} = "{value}"\n')
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(fake)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "chaos-coverage" in r.stderr
    assert value in r.stderr

    fake.write_text(
        "# audit: allow(chaos-coverage) — lint test fixture\n"
        f'{name} = "{value}"\n')
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(fake)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr

    # an action the suite actually drills (tests/test_fleet.py arms
    # WORKER_KILL plans) is clean without any pragma
    fake.write_text('WORKER_KILL = "worker_kill"\n')
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_lux.py"),
         str(fake)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


def test_unknown_audit_mode_is_typed_error():
    """A typo'd mode must not silently disable enforcement — both
    the engine param and audit_engine reject it."""
    from lux_tpu.apps import pagerank
    g = _graph(64, 400)
    with pytest.raises(ValueError, match="audit mode"):
        pagerank.build_engine(g, num_parts=2, audit="Error")
    eng = pagerank.build_engine(g, num_parts=2)
    with pytest.raises(ValueError, match="audit mode"):
        audit.audit_engine(eng, mode="off")


def test_audit_errors_classify_fatal():
    """A static-audit violation is a property of the BUILD: the
    resilience supervisor must never retry it — even when the finding
    text happens to contain words ('worker', 'aborted') the
    retryable message scan matches."""
    from lux_tpu import resilience
    assert resilience.classify(
        CallbackInLoopError("a host round-trip per iteration "
                            "to the worker")) == "fatal"
    assert resilience.classify(
        ConstBytesError("compile aborted: constants over the "
                        "ceiling")) == "fatal"


def test_gather_budget_pragma_exempts_eqn(tmp_path):
    """An explicit source pragma on a gather excludes it from the
    budget count (the eqn-anchored exemption form)."""
    import importlib.util
    mod_path = tmp_path / "praggather.py"
    mod_path.write_text(
        "import jax\nimport jax.numpy as jnp\n\n\n"
        "def bad(s, table, idx):\n"
        "    def body(i, acc):\n"
        "        a = jnp.take(table, idx + i, axis=0)\n"
        "        # audit: allow(gather-budget) — test fixture\n"
        "        b = jnp.take(table, idx * 2 + i, axis=0)\n"
        "        return acc + jnp.sum(a) + jnp.sum(b)\n\n"
        "    return jax.lax.fori_loop(0, 4, body, s)\n")
    spec_m = importlib.util.spec_from_file_location("praggather",
                                                    mod_path)
    mod = importlib.util.module_from_spec(spec_m)
    spec_m.loader.exec_module(mod)
    closed = jax.make_jaxpr(mod.bad)(
        jnp.float32(0), jnp.zeros((1024,), jnp.float32),
        jnp.zeros((16,), jnp.int32))
    spec = ProgramSpec(table_shape=(1024,), gather_budget=1)
    assert audit.check_gather_budget(closed, spec, "s") == []


def test_digest_shape():
    fs = [audit.Finding("gather-budget", "error", "x", "d"),
          audit.Finding("loop-invariant", "warn", "x", "d")]
    d = audit.digest(fs, mode="error")
    assert d == {"mode": "error", "errors": 1, "warnings": 1,
                 "failed_checks": ["gather-budget"]}
