"""lux_tpu/observe.py: the calibrated measurement subsystem.

CPU tier-1 coverage: deterministic-clock calibration fingerprinting,
MAD-based drift detection on synthetic fast/slow sessions, perf-ledger
append/validate round-trip, carried-debt matching/collection, the
observatory no-op proof (instrumentation never alters engine outputs
— the audit no-op proof pattern), and the repo-wide four-app CLI
smoke (the acceptance command: python -m lux_tpu.observe).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from lux_tpu import observe, telemetry
from lux_tpu.timing import loop_bench

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _fresh_calibration():
    """Tests that force-calibrate with fake clocks must not leak their
    fingerprint into the process cache other tests read."""
    saved = observe._FP
    observe._FP = None
    yield
    observe._FP = saved


def fake_clock(step_s: float):
    """Deterministic clock: every call advances by step_s, so a timed
    region spanning two calls always measures exactly step_s."""
    t = {"v": 0.0}

    def clock():
        t["v"] += step_s
        return t["v"]

    return clock


def synthetic_fp(platform="tpu", ndev=4, gather_ns=9.6,
                 session="feedc0ffee12"):
    """A Fingerprint without running the probe — for tests exercising
    grading/ledger/debt logic."""
    deviation = gather_ns / observe.CANONICAL["gather_small_ns"]
    return observe.Fingerprint(
        schema=observe.SCHEMA, session=session, pid=os.getpid(),
        backend=platform, platform=platform, ndev=ndev,
        probe={"gather_small_ns": gather_ns,
               "gather_small_mad_ns": 0.1,
               "pair_dot_row_ns": 120.0, "pair_dot_row_mad_ns": 2.0},
        canonical=dict(observe.CANONICAL), deviation=deviation,
        grade=observe._grade(platform, deviation),
        audit={"mode": "error", "errors": 0, "warnings": 0,
               "failed_checks": []})


# ---------------------------------------------------------------------
# pillar 1: calibration

def test_loop_bench_deterministic_clock():
    import jax.numpy as jnp

    def step(c):
        (x,) = c
        sv = jnp.sum(x)
        return sv, (x + sv * 1e-30,)

    samples, out = loop_bench(step, (jnp.ones(8),), k=4, repeats=3,
                              clock=fake_clock(0.02))
    # each repeat spans exactly one clock step: 0.02 s / 4 loop steps
    assert samples == [pytest.approx(0.005)] * 3
    assert out == pytest.approx(32.0)  # 4 steps x sum(ones(8)) = 32


def test_calibrate_deterministic_clock_fingerprint():
    step = 0.008                        # 8 ms per timed region
    fp = observe.calibrate(force=True, clock=fake_clock(step))
    want_gather = step / observe.PROBE_LOOP_K / observe.PROBE_GATHER_N \
        * 1e9
    assert fp.probe["gather_small_ns"] == pytest.approx(want_gather)
    assert fp.probe["gather_small_mad_ns"] == pytest.approx(0.0)
    want_dot = step / observe.PROBE_LOOP_K / observe.PROBE_DOT_ROWS \
        * 1e9
    assert fp.probe["pair_dot_row_ns"] == pytest.approx(want_dot)
    assert fp.deviation == pytest.approx(
        want_gather / observe.CANONICAL["gather_small_ns"])
    # the CPU test mesh has no canonical figures: labeled, not graded
    assert fp.platform == "cpu" and fp.grade == "uncalibrated"
    assert fp.session == telemetry.session_id()
    assert fp.ndev == 8 and fp.pid == os.getpid()
    # the probe programs satisfy the structural invariants they referee
    assert fp.audit["errors"] == 0
    # cached until forced
    assert observe.calibrate() is fp
    d = fp.digest()
    assert d["grade"] == "uncalibrated" and d["session"] == fp.session
    assert set(d["probe"]) == set(fp.probe)


def test_grades_and_session_scale():
    assert observe._grade("tpu", 1.0) == "canonical"
    assert observe._grade("tpu", 2.9) == "canonical"
    assert observe._grade("tpu", 9.7) == "degraded"     # the 10x trap
    assert observe._grade("tpu", 0.2) == "degraded"     # off-canon fast
    assert observe._grade("cpu", 1.0) == "uncalibrated"
    slow = synthetic_fp(gather_ns=96.0)                  # 10x session
    assert slow.grade == "degraded"
    assert observe.session_scale(slow) == pytest.approx(
        96.0 / observe.CANONICAL["gather_small_ns"])
    ok = synthetic_fp(gather_ns=9.6)
    assert ok.grade == "canonical"


def test_calibration_emits_event():
    ev = telemetry.EventLog()
    with telemetry.use(events=ev):
        observe.calibrate(force=True, clock=fake_clock(0.008))
    kinds = ev.counts()
    assert kinds.get("calibration") == 1
    e = ev.events[-1]
    assert e["grade"] == "uncalibrated" and "probe" in e


def test_events_carry_monotonic_pid_session():
    ev = telemetry.EventLog()
    a = ev.emit("x")
    b = ev.emit("y")
    assert a["pid"] == b["pid"] == os.getpid()
    assert a["session"] == b["session"] == telemetry.session_id()
    assert b["tm"] >= a["tm"]


# ---------------------------------------------------------------------
# pillar 2: drift detection

def test_median_mad():
    m, mad = observe.median_mad([1.0, 2.0, 10.0])
    assert m == 2.0 and mad == 1.0
    with pytest.raises(ValueError):
        observe.median_mad([])


def test_drift_verdicts_fast_slow_sessions():
    # tight samples on the model: ok
    assert observe.drift_verdict([1.0, 1.01, 0.99], 1.0) == "ok"
    # the synthetic slow session: 10x the model with tight MAD
    assert observe.drift_verdict([10.0, 10.1, 9.9], 1.0) \
        == "drift_slow"
    # the synthetic fast session (model overshoots 10x)
    assert observe.drift_verdict([0.1, 0.1, 0.1], 1.0) == "drift_fast"
    # no model: honestly unmodeled, never a false drift
    assert observe.drift_verdict([1.0], None) == "unmodeled"
    assert observe.drift_verdict([1.0], 0.0) == "unmodeled"


def test_drift_bound_is_variance_aware():
    """Noisy samples widen the bound: a 6x ratio with a 5x-of-median
    MAD is NOT called drift (the variance says it could be noise),
    while the same ratio with tight samples IS."""
    noisy = [1.0, 6.0, 12.0]            # median 6, MAD 5
    assert observe.drift_verdict(noisy, 1.0) == "ok"
    tight = [6.0, 6.0, 6.0]
    assert observe.drift_verdict(tight, 1.0) == "drift_slow"


# ---------------------------------------------------------------------
# pillar 2: phase attribution + the no-op proof

def _tiny_pagerank():
    from lux_tpu.apps import pagerank
    from lux_tpu.convert import rmat_graph
    g = rmat_graph(scale=8, edge_factor=4, seed=0)
    return pagerank.build_engine(g, num_parts=1), g


def test_decompose_reports_and_is_a_noop():
    """The audit no-op proof pattern: running the observatory's phase
    attribution must not perturb the engine — a run after decompose is
    BITWISE identical to one before."""
    eng, _g = _tiny_pagerank()
    before = eng.unpad(eng.run(eng.init_state(), 3))
    fp = synthetic_fp()
    ev = telemetry.EventLog()
    with telemetry.use(events=ev):
        d = observe.decompose(eng, "pagerank", iters=2, fingerprint=fp)
    after = eng.unpad(eng.run(eng.init_state(), 3))
    np.testing.assert_array_equal(before, after)

    assert d.app == "pagerank" and d.engine == "pull"
    assert d.session == fp.session
    names = {p.phase for p in d.phases}
    assert "apply" in names             # every pull split has apply
    allowed = {"ok", "drift_slow", "drift_fast", "unmodeled"}
    assert all(p.verdict in allowed for p in d.phases)
    assert all(len(p.samples) == 2 for p in d.phases)
    # every phase emitted its attribution event
    assert ev.counts().get("phase_cost") == len(d.phases)
    # report renders without error and names every phase
    rep = observe.render_report([d], fp)
    assert all(p.phase in rep for p in d.phases)
    # as_dict round-trips through JSON (ledger payload)
    assert json.loads(json.dumps(d.as_dict()))["app"] == "pagerank"


def test_decompose_push_engine():
    from lux_tpu.apps import components
    from lux_tpu.convert import rmat_graph
    from lux_tpu.graph import Graph
    g = rmat_graph(scale=8, edge_factor=4, seed=0)
    s, dst = components.symmetrize(*g.edge_arrays())
    eng = components.build_engine(Graph.from_edges(s, dst, g.nv))
    before, it0 = eng.run()
    d = observe.decompose(eng, "cc", iters=2,
                          fingerprint=synthetic_fp())
    after, it1 = eng.run()
    np.testing.assert_array_equal(before, after)
    assert it0 == it1
    assert d.engine == "push" and len(d.phases) > 0


# ---------------------------------------------------------------------
# pillar 3: ledger + debts

def test_ledger_append_validate_roundtrip(tmp_path):
    path = str(tmp_path / "PERFLEDGER.jsonl")
    led = observe.PerfLedger(path)
    fp = synthetic_fp()
    led.append("probe", {"probe": fp.probe}, fp)
    led.append("phase", {"app": "pagerank", "phases": []}, fp)
    led.append("bench", {"metric": "pagerank_gteps_per_chip",
                         "value": 0.17}, fp)
    led.append("debt", {"debt": "pair-dot-row-k-sweep"}, fp)
    assert observe.validate_ledger(path) == []
    recs = [r for _i, r, _e in observe.iter_ledger(path)]
    assert [r["kind"] for r in recs] == ["probe", "phase", "bench",
                                         "debt"]
    assert all(r["session"] == fp.session for r in recs)
    assert all(r["calibration"]["grade"] == "canonical" for r in recs)

    with pytest.raises(ValueError):
        led.append("vibes", {}, fp)


def test_ledger_validation_catches_rot(tmp_path):
    path = str(tmp_path / "led.jsonl")
    led = observe.PerfLedger(path)
    fp = synthetic_fp()
    led.append("probe", {"probe": fp.probe}, fp)
    with open(path, "a") as f:
        f.write("not json\n")
        f.write(json.dumps({"schema": 1, "kind": "bench",
                            "session": "x"}) + "\n")   # no calibration
        f.write(json.dumps({"schema": 1, "kind": "phase",
                            "session": "x",
                            "calibration": {"grade": "sideways",
                                            "deviation": 1.0}}) + "\n")
    errs = observe.validate_ledger(path)
    assert any("unparseable" in e for e in errs)
    assert any("missing calibration" in e for e in errs)
    assert any("grade" in e for e in errs)
    assert any("phases list" in e or "metric name" in e for e in errs)
    assert observe.validate_ledger(str(tmp_path / "led.jsonl")) == errs
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert observe.validate_ledger(str(empty)) == ["empty ledger"]


def test_debt_registry_matching():
    tpu4 = synthetic_fp(platform="tpu", ndev=4)
    ids = {d.id for d in observe.match_debts(tpu4)}
    assert ids == {d.id for d in observe.DEBTS}
    tpu1 = synthetic_fp(platform="tpu", ndev=1)
    ids1 = {d.id for d in observe.match_debts(tpu1)}
    assert "fused-exchange-ici-ab" not in ids1      # needs a mesh
    assert "elastic-shrink-drill" not in ids1
    assert "pagemajor-route-ab" not in ids1         # needs a mesh
    assert "pair-dot-row-k-sweep" in ids1
    # the CPU test mesh can collect no TPU-hardware debts — only the
    # platform-any probes: the reorder fill trail (round 16,
    # host-measured by construction) and the link-bandwidth sweep
    # (round 19 — measured anywhere, recorded with its fingerprint
    # label, fed into scalemodel only on canonical platforms)
    cpu_ids = {d.id for d in
               observe.match_debts(synthetic_fp(platform="cpu"))}
    assert cpu_ids == {"reorder-fill-ab", "ici-bandwidth-probe"}
    # the DCN probe is TPU-gated at the registry level AND slice-gated
    # inside its probe (a single-slice session must never record an
    # ICI rate wearing a DCN label)
    assert "dcn-bandwidth-probe" not in cpu_ids
    assert "dcn-bandwidth-probe" in ids


def test_collect_debts(tmp_path, monkeypatch):
    """Matched debts with an implemented probe are collected into the
    ledger; manual ones are skipped with their PERF_NOTES pointer."""
    monkeypatch.setattr(observe, "PROBE_DOT_ROWS", 8)
    monkeypatch.setattr(observe, "PROBE_PAGE_ROWS", 16)
    monkeypatch.setattr(observe, "PROBE_PAGE_TABLE", 8)
    monkeypatch.setattr(observe, "PROBE_LOOP_K", 2)
    path = str(tmp_path / "led.jsonl")
    fp = synthetic_fp(platform="tpu", ndev=4)
    collected, skipped = observe.collect_debts(
        fp, observe.PerfLedger(path),
        only={"pair-dot-row-k-sweep", "paged-gather-ab",
              "netflix-pair-run"})
    assert [c["debt"] for c in collected] == ["pair-dot-row-k-sweep",
                                              "paged-gather-ab"]
    sweep = collected[0]["sweep"]
    assert set(sweep) == {"1", "4", "8", "16", "20", "32"}
    assert all(v["row_ns"] >= 0 for v in sweep.values())
    ab = collected[1]
    assert ab["flat_ns_per_edge"] > 0 and ab["paged_ns_per_edge"] > 0
    assert ab["speedup"] == pytest.approx(
        ab["flat_ns_per_edge"] / ab["paged_ns_per_edge"], rel=1e-2)
    assert observe.validate_ledger(path) == []
    skipped_ids = {i for i, _r in skipped}
    assert "netflix-pair-run" in skipped_ids
    assert all("PERF_NOTES" in r for _i, r in skipped)


# ---------------------------------------------------------------------
# the acceptance command: repo-wide observatory smoke (tier-1)

def test_observe_cli_four_app_smoke(tmp_path, capsys):
    """python -m lux_tpu.observe emits a calibrated four-app phase
    report with drift verdicts, appends a validating ledger, and
    leaves an event log both validators accept."""
    led = tmp_path / "PERFLEDGER.jsonl"
    ev = tmp_path / "events.jsonl"
    rc = observe.main(["-scale", "8", "-ef", "4", "-iters", "2",
                       "-ledger", str(led), "-events", str(ev)])
    out = capsys.readouterr().out
    assert rc == 0
    for app in observe.APPS:
        assert f"== {app} " in out
    assert "grade=uncalibrated" in out          # CPU session, labeled
    assert "verdict" in out
    # one probe record + one phase record per app, all validating
    assert observe.validate_ledger(str(led)) == []
    kinds = [r["kind"] for _i, r, _e in observe.iter_ledger(str(led))]
    assert kinds == ["probe"] + ["phase"] * len(observe.APPS)
    # the event log renders in events_summary and audits clean
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "events_summary.py"),
         str(ev)], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "calibration:" in r.stdout


def test_observe_cli_debt_listing_is_read_only(tmp_path, capsys,
                                               monkeypatch):
    monkeypatch.chdir(tmp_path)
    rc = observe.main(["-debts"])
    out = capsys.readouterr().out
    assert rc == 0
    # CPU session: the only matching debt is the platform-any
    # reorder fill trail (host-measured; round 16) — no hardware
    # debts are listed
    assert "debt reorder-fill-ab" in out
    assert "paged-gather-ab" not in out
    # a pure listing never grows the append-only ledger
    assert not (tmp_path / observe.LEDGER_DEFAULT).exists()


# ---------------------------------------------------------------------
# bench.py artifact self-writing (the empty-trajectory fix)

def _load_bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_artifact_numbering_and_schema(tmp_path):
    bench = _load_bench()
    assert bench.next_artifact_path(str(tmp_path)).endswith(
        "BENCH_r01.json")
    (tmp_path / "BENCH_r05.json").write_text("{}")
    (tmp_path / "BENCH_r07.json").write_text("{}")
    path = bench.next_artifact_path(str(tmp_path))
    assert path.endswith("BENCH_r08.json")

    line = {"metric": "pagerank_rmat21_gteps_per_chip", "value": 0.17,
            "unit": "GTEPS", "vs_baseline": 0.17, "samples": [0.17],
            "attempts": 1, "discarded": [], "ne": 10,
            "telemetry": {"runs": [{"repeat": 0, "iters": 1,
                                    "seconds": 1.0}],
                          "counters": None},
            "calibration": synthetic_fp().digest()}
    bench.write_artifact(path, [line], line["calibration"], 0,
                         ["-config", "pagerank"])
    doc = json.loads(Path(path).read_text())
    assert doc["round"] == 8
    assert doc["calibration"]["grade"] == "canonical"
    # the artifact audits clean under the strict check_bench schema
    # ... except the telemetry re-derivation: ne*iters/seconds must
    # hit the sample — make it consistent above: 10*1/1.0/1e9 != 0.17
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_bench.py"),
         path], capture_output=True, text=True)
    assert "matches no recorded sample" in r.stderr


def test_bench_artifact_consistent_line_passes(tmp_path):
    bench = _load_bench()
    ne, iters, secs = 10**9, 10, 58.8235
    g = ne * iters / secs / 1e9
    line = {"metric": "pagerank_rmat21_gteps_per_chip",
            "value": round(g, 4), "unit": "GTEPS",
            "vs_baseline": round(g, 4), "samples": [round(g, 4)],
            "attempts": 1, "discarded": [], "ne": ne,
            "telemetry": {"runs": [{"repeat": 0, "iters": iters,
                                    "seconds": secs}],
                          "counters": None},
            "calibration": synthetic_fp().digest()}
    path = str(tmp_path / "BENCH_r09.json")
    bench.write_artifact(path, [line], line["calibration"], 0, [])
    r = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_bench.py"),
         path], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
