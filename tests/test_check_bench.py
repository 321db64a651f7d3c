"""scripts/check_bench.py: bench metric-line schema audit.

Fast CPU checks: legacy-schema driver artifacts (the shape the
pre-round-6 bench runs left behind, rebuilt inline here) audit clean
under -legacy-ok (and fail loudly without it — they predate the
round-6 attempts/discarded metadata), and synthetic good/bad
new-schema lines pass/fail as designed.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "scripts" / "check_bench.py"

# pre-round-6 metric lines: no attempts/discarded/calibration fields
_LEGACY_LINES = [
    {"metric": "cc_rmat20_gteps_per_chip", "value": 0.1508,
     "unit": "GTEPS", "vs_baseline": 0.1508,
     "samples": [0.1508, 0.1508, 0.1508], "np": 1, "scale": 20,
     "ef": 16, "relabel": True, "pair_threshold": 16},
    {"metric": "pagerank_rmat21_gteps_per_chip", "value": 0.1371,
     "unit": "GTEPS", "vs_baseline": 0.1371,
     "samples": [0.1371, 0.1371, 0.1371], "np": 1, "scale": 21,
     "ef": 16, "relabel": True, "pair_threshold": 16},
]


@pytest.fixture
def legacy_artifacts(tmp_path):
    """Two driver-written artifacts in the legacy schema: a log tail
    (a non-JSON warning line, then one metric line per config) plus
    the parsed tail line — one multi-line, one with the bare
    four-field line of the very first run."""
    multi = {"n": 3, "cmd": "python bench.py", "rc": 0,
             "tail": "WARNING: platform is experimental\n" + "".join(
                 json.dumps(ln) + "\n" for ln in _LEGACY_LINES),
             "parsed": _LEGACY_LINES[-1]}
    bare_line = {k: _LEGACY_LINES[-1][k] for k in
                 ("metric", "value", "unit", "vs_baseline")}
    bare = {"n": 1, "cmd": "python bench.py", "rc": 0,
            "tail": json.dumps(bare_line) + "\n", "parsed": bare_line}
    paths = []
    for name, art in (("legacy_multi.json", multi),
                      ("legacy_bare.json", bare)):
        p = tmp_path / name
        p.write_text(json.dumps(art, indent=2))
        paths.append(p)
    return paths

# round 12: the session-calibration fingerprint digest every new
# metric line carries (lux_tpu/observe.py) — grade must be
# "canonical" or the line is rejected from the trajectory
GOOD_CAL = {
    "schema": 1, "session": "a1b2c3d4e5f6", "platform": "tpu",
    "backend": "tpu", "ndev": 1, "grade": "canonical",
    "deviation": 1.07,
    "probe": {"gather_small_ns": 9.6, "gather_small_mad_ns": 0.2,
              "pair_dot_row_ns": 121.0, "pair_dot_row_mad_ns": 4.0},
    "audit": {"errors": 0, "warnings": 0},
}

GOOD_LINE = {
    "metric": "pagerank_mp_rmat23_gteps_per_chip",
    "value": 0.1118, "unit": "GTEPS", "vs_baseline": 0.1118,
    "samples": [0.1116, 0.1118, 0.112],
    "attempts": 4, "discarded": [0.0107], "np": 4,
    "ne": 10**9,
    # round 7: per-run seconds (one per attempt, reruns included)
    # re-deriving each recorded sample, plus the counter digest
    "telemetry": {
        "runs": [
            {"repeat": 0, "iters": 10, "seconds": 89.605735},
            {"repeat": 1, "iters": 10, "seconds": 89.445438},
            {"repeat": 2, "iters": 10, "seconds": 89.285714},
            {"repeat": 0, "iters": 10, "seconds": 934.579439},
        ],
        "counters": {"kind": "pull", "iters": 10, "truncated": False,
                     "residual_first": 3.5e-4,
                     "residual_last": 9.7e-8,
                     "changed_last": 12, "changed_sum": 480},
    },
    "calibration": GOOD_CAL,
}


def run_check(*argv):
    return subprocess.run(
        [sys.executable, str(SCRIPT), *map(str, argv)],
        capture_output=True, text=True)


def test_legacy_artifacts_audit_clean_as_legacy(legacy_artifacts):
    r = run_check("-legacy-ok", *legacy_artifacts)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_legacy_artifacts_fail_strict_schema(legacy_artifacts):
    """Pre-round-6 lines lack attempts/discarded; the default (strict)
    mode must fail LOUDLY, naming the missing metadata."""
    r = run_check(*legacy_artifacts)
    assert r.returncode == 1
    assert "missing resilience metadata" in r.stderr
    assert "FAILED" in r.stderr


def test_good_new_schema_line_passes(tmp_path):
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(GOOD_LINE) + "\n")
    r = run_check(p)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("mutate,needle", [
    (lambda d: d.pop("attempts"), "missing resilience metadata"),
    (lambda d: d.update(attempts=9), "inconsistent"),
    (lambda d: d.update(value=0.0107), "not the median"),
    (lambda d: d.update(samples=[]), "non-empty list"),
    (lambda d: d.pop("value"), "missing required key"),
    (lambda d: d.update(run_attempts=1), "run_attempts"),
    (lambda d: d.update(samples=[0.1116, 0.1118, 0.0107],
                        value=0.1116, attempts=4),
     "both samples and discarded"),
    # round-7 telemetry field
    (lambda d: d.pop("telemetry"), "missing telemetry"),
    (lambda d: d["telemetry"].update(runs=d["telemetry"]["runs"][:2]),
     "timed runs"),
    (lambda d: d["telemetry"]["runs"][0].update(seconds=50.0),
     "matches no recorded sample"),
    (lambda d: d["telemetry"].update(counters={"kind": "sideways"}),
     "counters malformed"),
    (lambda d: d["telemetry"].update(runs=[{"repeat": 0, "iters": 10,
                                            "seconds": 0.0}] * 4),
     "telemetry.runs"),
    (lambda d: d.update(telemetry={"runs": []}), "telemetry must be"),
])
def test_bad_lines_fail(tmp_path, mutate, needle):
    d = json.loads(json.dumps(GOOD_LINE))   # deep copy: mutators
    mutate(d)                               # touch nested dicts
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert needle in r.stderr


def _with_imbalance(imb=None, **counter_over):
    d = json.loads(json.dumps(GOOD_LINE))
    d["telemetry"]["counters"].update(counter_over)
    d["telemetry"]["imbalance"] = imb
    return d


def test_imbalance_digest_accepted(tmp_path):
    """Round-13 telemetry.imbalance: a consistent digest passes,
    null passes (iter-stats off), absent passes (older schema)."""
    good = _with_imbalance({"kind": "pull", "index": 1.5,
                            "parts": [180, 120, 60, 120]},
                           changed_sum=480)
    for line in (good, _with_imbalance(None), GOOD_LINE):
        p = tmp_path / "bench.jsonl"
        p.write_text(json.dumps(line) + "\n")
        r = run_check(p)
        assert r.returncode == 0, (line, r.stderr)


@pytest.mark.parametrize("imb,counters,needle", [
    # parts don't sum to the scalar counter — the health-digest
    # contradiction pattern: per-part and scalar are the SAME
    # device-side values, so disagreement is rejected
    ({"kind": "pull", "index": 1.5, "parts": [180, 120, 60, 121]},
     {"changed_sum": 480}, "contradicts the counter digest"),
    # index contradicting its own parts
    ({"kind": "pull", "index": 3.0, "parts": [180, 120, 60, 120]},
     {"changed_sum": 480}, "contradicts its own parts"),
    ({"kind": "pull", "index": 0.5, "parts": [180, 120, 60, 120]},
     {"changed_sum": 480}, "must be a finite number >= 1"),
    ({"kind": "sideways", "index": 1.5, "parts": [1, 2]},
     {}, "not push|pull"),
    ({"kind": "pull", "index": 1.0, "parts": []},
     {}, "non-empty list"),
    ({"kind": "pull", "index": 1.0, "parts": [1, -2]},
     {}, "non-empty list of ints"),
    ("not-a-dict", {}, "must be null or a dict"),
])
def test_bad_imbalance_digests_fail(tmp_path, imb, counters, needle):
    d = _with_imbalance(imb, **counters)
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert needle in r.stderr


def test_health_digest_accepted_and_typechecked(tmp_path):
    """Round-9 telemetry.health digest (bench.py -health): a clean
    digest passes, null passes (watchdog off), and malformed or
    contradictory digests fail."""
    good = json.loads(json.dumps(GOOD_LINE))
    good["telemetry"]["health"] = {"engine": "pull", "tripped": False,
                                   "flags": [], "iters": 10}
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(good) + "\n")
    assert run_check(p).returncode == 0, run_check(p).stderr
    good["telemetry"]["health"] = None
    p.write_text(json.dumps(good) + "\n")
    assert run_check(p).returncode == 0


@pytest.mark.parametrize("health,needle", [
    ({"engine": "gpu", "tripped": False, "flags": [], "iters": 10},
     "not push|pull"),
    ({"engine": "pull", "tripped": "no", "flags": [], "iters": 10},
     "tripped must be a bool"),
    ({"engine": "pull", "tripped": False, "flags": ["made_up"],
      "iters": 10}, "unknown checks"),
    ({"engine": "pull", "tripped": True,
      "flags": ["nonfinite_state"], "iters": 10},
     "cannot publish a metric line"),
    ({"engine": "pull", "tripped": False, "flags": [], "iters": -1},
     "iters"),
    ("clean", "null or a dict"),
])
def test_bad_health_digests_fail(tmp_path, health, needle):
    d = json.loads(json.dumps(GOOD_LINE))
    d["telemetry"]["health"] = health
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert needle in r.stderr


def test_audit_digest_accepted(tmp_path):
    """Round-10 audit digest (bench.py -audit, lux_tpu/audit.py): a
    clean digest passes, null passes (-audit off), absence passes
    (older artifacts)."""
    good = json.loads(json.dumps(GOOD_LINE))
    good["audit"] = {"mode": "warn", "errors": 0, "warnings": 1,
                     "failed_checks": []}
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(good) + "\n")
    r = run_check(p)
    assert r.returncode == 0, r.stderr
    good["audit"] = None
    p.write_text(json.dumps(good) + "\n")
    assert run_check(p).returncode == 0


@pytest.mark.parametrize("audit,needle", [
    ({"mode": "loud", "errors": 0, "warnings": 0,
      "failed_checks": []}, "not warn|error"),
    ({"mode": "warn", "errors": -1, "warnings": 0,
      "failed_checks": []}, "audit.errors"),
    ({"mode": "warn", "errors": 0, "warnings": 0,
      "failed_checks": ["made-up-check"]}, "unknown checks"),
    ({"mode": "warn", "errors": 2, "warnings": 0,
      "failed_checks": ["gather-budget"]}, "audit-FAILING build"),
    ({"mode": "warn", "errors": 0, "warnings": 0,
      "failed_checks": ["identity-init"]}, "audit-FAILING build"),
    ({"mode": "warn", "errors": 0, "warnings": 0,
      "failed_checks": "gather-budget"}, "failed_checks must be"),
    ("clean", "null or a dict"),
])
def test_bad_audit_digests_fail(tmp_path, audit, needle):
    """A published metric line whose build failed the static audit is
    a contradiction — the number was measured on a build violating
    the structural invariants."""
    d = json.loads(json.dumps(GOOD_LINE))
    d["audit"] = audit
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert needle in r.stderr


# -- round-12 calibration fingerprint (lux_tpu/observe.py) -------------

def test_missing_calibration_fails_strict(tmp_path):
    """Pre-round-12 lines lack the fingerprint; strict mode fails
    loudly, -legacy-ok downgrades (historical artifacts)."""
    d = json.loads(json.dumps(GOOD_LINE))
    del d["calibration"]
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1 and "missing calibration" in r.stderr
    assert run_check("-legacy-ok", p).returncode == 0


@pytest.mark.parametrize("mutate,needle", [
    # a crashed probe (null) leaves the line unlabeled — rejected
    (lambda c: None, "calibration is null"),
    # the 10x-off-canon session, detected and labeled — rejected
    (lambda c: dict(c, grade="degraded", deviation=9.7),
     "DEGRADED session"),
    # CPU test-mesh numbers must never enter the TPU trajectory
    (lambda c: dict(c, grade="uncalibrated", platform="cpu",
                    deviation=0.16), "UNCALIBRATED session"),
    # a self-contradicting digest (claims canonical, deviation 5x)
    (lambda c: dict(c, deviation=5.0), "contradicts itself"),
    (lambda c: dict(c, grade="excellent"), "calibration.grade"),
    (lambda c: dict(c, deviation="fast"), "calibration.deviation"),
    (lambda c: dict(c, probe={}), "calibration.probe"),
    # a probe that failed its own static audit measured nothing
    (lambda c: dict(c, audit={"errors": 1, "warnings": 0}),
     "failed their own static audit"),
    (lambda c: dict(c, audit=None), "calibration.audit"),
    (lambda c: dict(c, ndev=0), "calibration.ndev"),
    (lambda c: dict(c, session=""), "calibration.session"),
    (lambda c: "calibrated", "null or a dict"),
])
def test_bad_calibration_digests_fail(tmp_path, mutate, needle):
    d = json.loads(json.dumps(GOOD_LINE))
    d["calibration"] = mutate(json.loads(json.dumps(GOOD_CAL)))
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert needle in r.stderr, r.stderr


def test_fast_deviation_also_contradicts(tmp_path):
    """deviation < 1/3 on a 'canonical' grade is as contradictory as
    > 3 — a probe that measured 5x FASTER than canon is lying about
    something (clock, fence, or shapes)."""
    d = json.loads(json.dumps(GOOD_LINE))
    d["calibration"] = dict(GOOD_CAL, deviation=0.2)
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1 and "contradicts itself" in r.stderr


def test_failed_config_line_schema(tmp_path):
    good = {"metric": "sssp_FAILED", "error": "RuntimeError: worker",
            "attempts": 3, "failure_class": "retryable"}
    bad = {"metric": "sssp_FAILED", "error": "RuntimeError: worker"}
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(good) + "\n")
    assert run_check(p).returncode == 0
    p.write_text(json.dumps(bad) + "\n")
    r = run_check(p)
    assert r.returncode == 1 and "failure line missing" in r.stderr
    # legacy mode tolerates it (historical crash lines)
    assert run_check("-legacy-ok", p).returncode == 0


def test_crashed_rerun_line_accepted(tmp_path):
    """An outlier rerun that crashed after its timed_run event landed
    leaves runs > attempts with no matching sample; the recorded
    rerun_error legitimizes both (bench.py's crash-tolerant path)."""
    d = json.loads(json.dumps(GOOD_LINE))
    d["samples"] = [0.1116, 0.1118, 0.112]
    d["value"] = 0.1118
    d["discarded"] = []
    d["attempts"] = 3
    d["rerun_error"] = "RuntimeError: worker died"
    d["rerun_error_class"] = "retryable"
    # 4th run's sample never recorded — the crashed rerun
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 0, r.stderr


def test_events_jsonl_accepted(tmp_path):
    """An -events telemetry log (kind/t objects, no metric lines)
    audits as events instead of failing (round-7 acceptance: both
    checkers accept the -events JSONL)."""
    p = tmp_path / "events.jsonl"
    p.write_text(
        '{"t": 1.0, "kind": "run_start", "app": "sssp"}\n'
        '{"t": 1.2, "kind": "timed_run", "repeat": 0, "iters": 5, '
        '"seconds": 0.02}\n')
    assert run_check(p).returncode == 0
    p.write_text('{"t": 1.0, "kind": "segment", "seconds": "fast"}\n')
    r = run_check(p)
    assert r.returncode == 1 and "non-finite seconds" in r.stderr


def test_unparseable_and_empty_inputs(tmp_path):
    p = tmp_path / "junk.jsonl"
    p.write_text('{"metric": broken\n')
    r = run_check(p)
    assert r.returncode == 1 and "unparseable" in r.stderr
    p.write_text("nothing here\n")
    r = run_check(p)
    assert r.returncode == 1 and "no metric lines" in r.stderr


# ---- round-8 script lines (netflix / bigscale) ----------------------

NETFLIX_LINE = {
    "metric": "colfilter_netflix100m_np4_gteps_per_chip",
    "value": 0.09, "unit": "GTEPS", "vs_baseline": 0.09,
    "samples": [0.09, 0.0905, 0.0896], "attempts": 3, "discarded": [],
    "np": 4, "ne": 186_000_000, "iters": 3, "pair_threshold": 16,
    "min_fill": "auto", "pair_stream": True,
    "telemetry": {"runs": [
        {"repeat": 0, "iters": 3, "seconds": 186e6 * 3 / 0.09 / 1e9},
        {"repeat": 1, "iters": 3, "seconds": 186e6 * 3 / 0.0905 / 1e9},
        {"repeat": 2, "iters": 3, "seconds": 186e6 * 3 / 0.0896 / 1e9},
    ], "counters": None},
    "calibration": GOOD_CAL,
    "rmse": [2.926, 2.800, 2.714],
}

BIGSCALE_LINE = {
    "metric": "pagerank_rmat27_np8_gteps_per_chip",
    "value": 0.11, "unit": "GTEPS", "vs_baseline": 0.11,
    "samples": [0.11], "attempts": 1, "discarded": [],
    "np": 8, "scale": 27, "ne": 2_147_483_648, "iters": 1,
    "pair_threshold": 16, "min_fill": 16, "exchange": "owner",
    "sparse": True, "start": None, "seg": None,
    "telemetry": {"runs": [
        {"repeat": 0, "iters": 1, "seconds": 2_147_483_648 / 0.11 / 1e9},
    ], "counters": None},
    "calibration": GOOD_CAL,
}


def _audit_one(tmp_path, obj):
    p = tmp_path / "line.json"
    p.write_text(json.dumps(obj))
    return run_check(p)


def test_netflix_and_bigscale_lines_pass_strict(tmp_path):
    for obj in (NETFLIX_LINE, BIGSCALE_LINE):
        r = _audit_one(tmp_path, obj)
        assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("mutate,needle", [
    (lambda o: o.update(rmse=[2.9, 2.95, 2.8]), "not strictly"),
    (lambda o: o.update(rmse=[2.9]), ">= 2 finite"),
    (lambda o: o.pop("rmse"), "missing"),
    (lambda o: o.update(min_fill="bogus"), "min_fill"),
    (lambda o: o.update(pair_threshold=0), "pair_threshold"),
])
def test_bad_netflix_lines_fail(tmp_path, mutate, needle):
    obj = json.loads(json.dumps(NETFLIX_LINE))
    mutate(obj)
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 1, "audit passed a bad netflix line"
    assert needle in r.stderr, r.stderr


@pytest.mark.parametrize("mutate,needle", [
    (lambda o: o.update(scale=26), "contradicts"),
    (lambda o: o.update(exchange="bogus"), "exchange"),
    (lambda o: o.update(iters=0), "iters"),
    (lambda o: o.pop("exchange"), "missing"),
    (lambda o: o.update(min_fill=0), "min_fill"),
])
def test_bad_bigscale_lines_fail(tmp_path, mutate, needle):
    obj = json.loads(json.dumps(BIGSCALE_LINE))
    mutate(obj)
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 1, "audit passed a bad bigscale line"
    assert needle in r.stderr, r.stderr


# -- round-11 telemetry.topology (degraded-mesh rejection) -------------

def test_null_topology_digest_accepted(tmp_path):
    d = json.loads(json.dumps(GOOD_LINE))
    d["telemetry"]["topology"] = None
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 0, r.stderr


def test_mid_run_mesh_shrink_rejected(tmp_path):
    """The round-11 satellite: a metric line whose telemetry records
    a mid-run mesh shrink must FAIL — a degraded-mesh GTEPS compared
    against full-mesh lines silently is exactly the kind of quiet
    apples-to-oranges this checker exists to prevent."""
    d = json.loads(json.dumps(GOOD_LINE))
    d["telemetry"]["topology"] = {"shrinks": 1, "ndev_final": 4}
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert "mesh shrink" in r.stderr
    assert "degraded-mesh" in r.stderr


@pytest.mark.parametrize("topo,needle", [
    ({"shrinks": "two"}, "shrinks"),
    ({"shrinks": 0, "ndev_final": 0}, "ndev_final"),
    # a non-null digest claiming zero shrinks dodges the rejection
    # while asserting degradation metadata exists — malformed
    ({"shrinks": 0, "ndev_final": 4}, "null digest means no shrink"),
    ("shrunk", "must be null or a dict"),
])
def test_malformed_topology_digests_fail(tmp_path, topo, needle):
    d = json.loads(json.dumps(GOOD_LINE))
    d["telemetry"]["topology"] = topo
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert needle in r.stderr, r.stderr


# -- round-14 query-batched lines (bench.py batch-sweep) ---------------

BATCH_LINE = {
    "metric": "ksssp_b8_rmat20_gteps_per_chip",
    "value": 0.17, "unit": "GTEPS", "vs_baseline": 0.17,
    "batch": 8, "query_gteps": 1.36,
    "per_query_edge_ns": 0.7353,
    "samples": [0.17], "attempts": 1, "discarded": [],
    "np": 1, "ne": 16 * (1 << 20),
    "telemetry": {
        "runs": [{"repeat": 0, "iters": 10,
                  "seconds": 16 * (1 << 20) * 10 / 0.17 / 1e9}],
        "counters": None},
    "calibration": GOOD_CAL,
}


def test_batched_line_passes_strict(tmp_path):
    r = _audit_one(tmp_path, BATCH_LINE)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("mutate,needle", [
    (lambda o: o.update(query_gteps=0.5),
     "contradicts the machine rate"),
    (lambda o: o.pop("query_gteps"), "missing query_gteps"),
    (lambda o: o.update(batch=4), "contradicts the metric name"),
    (lambda o: o.update(batch="8"), "positive int"),
    (lambda o: o.update(per_query_edge_ns=9.0),
     "contradicts 1/query_gteps"),
])
def test_bad_batched_lines_fail(tmp_path, mutate, needle):
    obj = json.loads(json.dumps(BATCH_LINE))
    mutate(obj)
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 1, "audit passed a bad batched line"
    assert needle in r.stderr, r.stderr


# -- round-17 serving SLO lines (bench.py -config serve-slo) -----------

SERVE_SLO_LINE = {
    "metric": "serve_slo_q45_rmat12_qps_per_chip",
    "value": 41.2, "unit": "qps", "vs_baseline": 41.2,
    "samples": [41.2], "attempts": 1, "discarded": [],
    "np": 2, "scale": 12, "ef": 8, "serve_batch": 4,
    "kinds": ["sssp", "components", "pagerank"], "queries": 36,
    "offered_qps": 44.8, "achieved_qps": 41.2,
    "p50_ms": 18.4, "p99_ms": 61.0,
    "slo_target_ms": {"sssp": 250.0, "components": 250.0,
                      "pagerank": 1000.0},
    "slo_good_fraction": 0.972,
    "served": 35, "submitted": 36,
    "telemetry": {"runs": [{"repeat": 0, "iters": 35,
                            "seconds": 0.85}],
                  "counters": None},
    "calibration": GOOD_CAL,
}


def test_serve_slo_line_passes_strict(tmp_path):
    r = _audit_one(tmp_path, SERVE_SLO_LINE)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("mutate,needle", [
    # the three contradiction rejects of the round-17 schema
    (lambda o: o.update(p99_ms=9.0), "p99_ms=9.0 < p50_ms"),
    (lambda o: o.update(achieved_qps=50.0, value=50.0,
                        samples=[50.0]), "outrun arrivals"),
    (lambda o: o.update(slo_good_fraction=1.2), "slo_good_fraction"),
    (lambda o: o.update(slo_good_fraction=-0.1),
     "slo_good_fraction"),
    # record completeness + self-consistency
    (lambda o: o.pop("offered_qps"), "serve-slo line missing"),
    (lambda o: o.pop("slo_target_ms"), "serve-slo line missing"),
    (lambda o: o.update(value=12.0, samples=[12.0]),
     "achieved_qps"),
    (lambda o: o.update(offered_qps=-3.0), "offered_qps"),
    (lambda o: o.update(slo_target_ms={}), "slo_target_ms"),
    (lambda o: o.update(slo_target_ms={"sssp": 0}), "slo_target_ms"),
    (lambda o: o.update(p50_ms="fast"), "p50_ms"),
])
def test_bad_serve_slo_lines_fail(tmp_path, mutate, needle):
    obj = json.loads(json.dumps(SERVE_SLO_LINE))
    mutate(obj)
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 1, "audit passed a bad serve-slo line"
    assert needle in r.stderr, r.stderr


# -- round-18 serving chaos lines (bench.py -config serve-chaos) -------

SERVE_CHAOS_LINE = {
    **json.loads(json.dumps(SERVE_SLO_LINE)),
    "metric": "serve_chaos_q45_rmat12_qps_per_chip",
    "replicas": 2, "failovers": 3, "shed": 1,
    "shed_fraction": round(1 / 36, 4), "slo_accounted": 35,
    # round 24: the self-healing record rides every chaos line
    "respawns": 1, "quarantines": 0, "mttr_s": 0.42,
    "journal_replayed": 2,
}


def test_serve_chaos_line_passes_strict(tmp_path):
    r = _audit_one(tmp_path, SERVE_CHAOS_LINE)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("mutate,needle", [
    # the round-18 contradiction rejects
    (lambda o: o.update(shed_fraction=1.2), "shed_fraction"),
    (lambda o: o.update(shed_fraction=-0.1), "shed_fraction"),
    (lambda o: o.update(replicas=1), "no surviving replica"),
    (lambda o: o.update(slo_accounted=36),
     "computed over shed queries"),
    (lambda o: o.update(shed=3), "partition the offered load"),
    (lambda o: o.update(shed_fraction=0.5), "disagrees with"),
    # record completeness + types
    (lambda o: o.pop("replicas"), "serve-chaos line missing"),
    (lambda o: o.pop("shed_fraction"), "serve-chaos line missing"),
    (lambda o: o.update(failovers=-1), "failovers"),
    (lambda o: o.update(replicas="two"), "replicas"),
    # the serve-slo contradictions stay armed on chaos lines
    (lambda o: o.update(p99_ms=9.0), "p99_ms=9.0 < p50_ms"),
    # round 24: the self-healing record
    (lambda o: o.pop("respawns"), "self-healing record"),
    (lambda o: o.pop("journal_replayed"), "self-healing record"),
    (lambda o: o.update(respawns=-1), "respawns"),
    (lambda o: o.update(respawns=1, replicas=1, failovers=0,
                        shed=0, shed_fraction=0.0, served=36,
                        slo_accounted=36), "with replicas=1"),
    (lambda o: o.update(quarantines=-1), "quarantines"),
    (lambda o: o.update(mttr_s=-0.5), "mttr_s"),
    (lambda o: o.update(mttr_s="fast"), "mttr_s"),
    (lambda o: o.update(failovers=0, respawns=0, shed=0,
                        shed_fraction=0.0, served=36,
                        slo_accounted=36), "no outage to time"),
    (lambda o: o.update(journal_replayed=99), "never offered"),
])
def test_bad_serve_chaos_lines_fail(tmp_path, mutate, needle):
    obj = json.loads(json.dumps(SERVE_CHAOS_LINE))
    mutate(obj)
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 1, "audit passed a bad serve-chaos line"
    assert needle in r.stderr, r.stderr


def test_serve_chaos_zero_failovers_with_replicas_ok(tmp_path):
    """failovers=0 with any replica count (and shed=0) is a
    legitimate quiet run — only the impossible combinations
    reject."""
    obj = json.loads(json.dumps(SERVE_CHAOS_LINE))
    obj.update(failovers=0, shed=0, shed_fraction=0.0,
               served=36, slo_accounted=36)
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 0, r.stderr


# -- round-20 live-graph serving lines (bench.py -config serve-live) ---

SERVE_LIVE_LINE = {
    "metric": "serve_live_rmat12_qps_per_chip",
    "value": 9.2, "unit": "qps", "vs_baseline": 9.2,
    "samples": [9.2], "attempts": 1, "discarded": [],
    "np": 2, "scale": 12, "ef": 8, "serve_batch": 4,
    "kinds": ["sssp", "components", "pagerank"],
    "delta_capacity": 64, "compact_threshold": 0.75,
    "submitted": 36, "served": 36,
    "mutations": 72, "mutation_rate_per_s": 18.3,
    "epochs_advanced": 6, "compactions": 1,
    # round 21: the mutation-algebra record rides on every line
    "deletions": 3, "reweights": 2, "reseeds": 2,
    "scheduler_compactions": 1,
    "cache_hit_fraction": 0.4615, "peak_occupancy": 0.75,
    "telemetry": {"runs": [{"repeat": 0, "iters": 36,
                            "seconds": 3.91}],
                  "counters": None},
    "calibration": GOOD_CAL,
}


def test_serve_live_line_passes_strict(tmp_path):
    r = _audit_one(tmp_path, SERVE_LIVE_LINE)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("mutate,needle", [
    # the round-20 contradiction rejects
    (lambda o: o.update(mutations=0), "with mutations=0"),
    (lambda o: o.update(epochs_advanced=0),
     "epoch-invisible"),
    (lambda o: o.update(epochs_advanced=100),
     "more epochs than edges"),
    (lambda o: o.update(cache_hit_fraction=1.2),
     "cache_hit_fraction"),
    (lambda o: o.update(cache_hit_fraction=-0.1),
     "cache_hit_fraction"),
    # sub-threshold occupancy only contradicts a compaction when no
    # anti-monotone op could have triggered the fold instead
    (lambda o: o.update(peak_occupancy=0.3, deletions=0,
                        reweights=0, reseeds=0),
     "never reached compact_threshold"),
    # round-21 mutation-algebra contradictions
    (lambda o: o.update(deletions=0, reweights=0),
     "nothing to re-seed FROM"),
    (lambda o: o.update(deletions=100),
     "the algebra counters exceed"),
    (lambda o: o.update(scheduler_compactions=5),
     "cannot have folded more"),
    (lambda o: o.update(deletions=0, reweights=0, reseeds=0,
                        peak_occupancy=0.3),
     "neither scheduler trigger"),
    # record completeness + types
    (lambda o: o.pop("mutations"), "serve-live line missing"),
    (lambda o: o.pop("compactions"), "serve-live line missing"),
    (lambda o: o.pop("peak_occupancy"), "serve-live line missing"),
    (lambda o: o.pop("deletions"), "serve-live line missing"),
    (lambda o: o.pop("scheduler_compactions"),
     "serve-live line missing"),
    (lambda o: o.update(compactions=-1), "compactions"),
    (lambda o: o.update(reseeds=-1), "reseeds"),
    (lambda o: o.update(deletions="some"), "deletions"),
    (lambda o: o.update(peak_occupancy=1.5), "peak_occupancy"),
    (lambda o: o.update(compact_threshold=0.0), "compact_threshold"),
    (lambda o: o.update(delta_capacity=0), "delta_capacity"),
    (lambda o: o.update(mutations="many"), "mutations"),
])
def test_bad_serve_live_lines_fail(tmp_path, mutate, needle):
    obj = json.loads(json.dumps(SERVE_LIVE_LINE))
    mutate(obj)
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 1, "audit passed a bad serve-live line"
    assert needle in r.stderr, r.stderr


def test_serve_live_quiet_run_ok(tmp_path):
    """Zero mutations + zero epochs + zero compactions (a static
    drain through the live path) is legitimate — only the impossible
    combinations reject, and a sub-threshold peak occupancy is fine
    when nothing compacted."""
    obj = json.loads(json.dumps(SERVE_LIVE_LINE))
    obj.update(mutations=0, epochs_advanced=0, compactions=0,
               peak_occupancy=0.0, mutation_rate_per_s=0.0,
               deletions=0, reweights=0, reseeds=0,
               scheduler_compactions=0)
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------
# round 16: gather-ab reorder field + pairing rule


def _gather_line(mode="paged", reorder=None, fill=9.5, tag="rmat21"):
    d = json.loads(json.dumps(GOOD_LINE))
    rtok = "" if reorder in (None, "none") else f"{reorder}_"
    d["metric"] = f"pagerank_{mode}_{rtok}{tag}_gteps_per_chip"
    d["gather"] = mode
    d["page_ratio"] = 0.61
    d["page_fill"] = fill
    if reorder is not None:
        d["reorder"] = reorder
    return d


def test_gather_reorder_lines_accepted(tmp_path):
    """A reordered pair whose fill ROSE passes, including the
    pagemajor mode and the community shape tag."""
    lines = [_gather_line("paged", "none", 8.2),
             _gather_line("paged", "hillclimb", 31.0),
             _gather_line("flat", "none", 8.2),
             _gather_line("flat", "native", 24.0),
             _gather_line("pagemajor", "none", 9.0, tag="comm14")]
    p = tmp_path / "bench.jsonl"
    p.write_text("".join(json.dumps(d) + "\n" for d in lines))
    r = run_check(p)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("line,needle", [
    (_gather_line("paged", "sorted"), "reorder="),
    # reorder field contradicting the metric name's token
    ({**_gather_line("paged", "hillclimb"), "reorder": "none"},
     "contradicts the metric name's reorder"),
    ({**_gather_line("paged"), "reorder": "native"},
     "contradicts the metric name's reorder"),
])
def test_bad_reorder_fields_fail(tmp_path, line, needle):
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(line) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert needle in r.stderr


def test_reorder_pair_fill_decrease_rejected(tmp_path):
    """The cross-line rule: a reordered line published WITH its
    paired none line must not show a fill drop — the reorder
    hill-climbs fill, so a drop is a mislabeled pair or a broken
    reorderer."""
    lines = [_gather_line("paged", "none", 9.5),
             _gather_line("paged", "hillclimb", 7.0)]
    p = tmp_path / "bench.jsonl"
    p.write_text("".join(json.dumps(d) + "\n" for d in lines))
    r = run_check(p)
    assert r.returncode == 1
    assert "DECREASED" in r.stderr
    # without the paired none line the (possibly historical) single
    # line stands on its own
    p.write_text(json.dumps(lines[1]) + "\n")
    assert run_check(p).returncode == 0


def test_reorder_pair_cross_np_not_compared(tmp_path):
    """num_parts is part of the pairing identity: padded fill shifts
    legitimately with the parts' common depth profile, so a
    reordered np=4 line never pairs against a none np=1 baseline."""
    none1 = _gather_line("paged", "none", 20.0)
    none1["np"] = 1
    ro4 = _gather_line("paged", "hillclimb", 12.0)
    ro4["np"] = 4
    p = tmp_path / "bench.jsonl"
    p.write_text("".join(json.dumps(d) + "\n" for d in [none1, ro4]))
    assert run_check(p).returncode == 0
    # same np: the drop IS a contradiction
    ro4["np"] = 1
    p.write_text("".join(json.dumps(d) + "\n" for d in [none1, ro4]))
    r = run_check(p)
    assert r.returncode == 1 and "DECREASED" in r.stderr


# ---------------------------------------------------------------------
# round-19 comm-ledger digest (lux_tpu/comms.py, bench.py _comm_build)

GOOD_COMM = {"errors": 0, "ndev": 4, "exchange": "owner",
             "tier": "ici", "bytes_per_iter": 250000,
             "comm_bytes_per_edge": 0.001, "messages": 2,
             "comm_frac": 0.0021}


def _with_comm(**over):
    d = json.loads(json.dumps(GOOD_LINE))
    d["comm"] = dict(GOOD_COMM, **over)
    return d


def test_comm_digest_accepted(tmp_path):
    """A clean byte-ledger digest passes strict mode; off-mesh
    single-device digests legitimately carry all-zero bytes."""
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(_with_comm()) + "\n")
    r = run_check(p)
    assert r.returncode == 0, r.stderr
    single = _with_comm(ndev=1, tier="local", bytes_per_iter=0,
                        comm_bytes_per_edge=0.0, messages=0,
                        comm_frac=0.0)
    p.write_text(json.dumps(single) + "\n")
    assert run_check(p).returncode == 0
    # lines without the field (pre-round-19, script lines) still pass
    d = json.loads(json.dumps(GOOD_LINE))
    p.write_text(json.dumps(d) + "\n")
    assert run_check(p).returncode == 0


@pytest.mark.parametrize("over,needle", [
    # a digest from a ledger-failing build can never publish
    ({"errors": 1, "error": "CommLedgerError: oracle disagrees"},
     "LEDGER-FAILING"),
    # comm_frac is a fraction of one iteration by construction
    ({"comm_frac": 1.2}, "comm_frac"),
    ({"comm_frac": -0.1}, "comm_frac"),
    # a single device has no link to ship over
    ({"ndev": 1, "tier": "local"}, "SINGLE device"),
    ({"ndev": 1, "bytes_per_iter": 0, "comm_bytes_per_edge": 0.0,
      "comm_frac": 0.0, "tier": "ici"}, "no link tier"),
    # a mesh owner exchange cannot ship zero bytes
    ({"bytes_per_iter": 0, "comm_bytes_per_edge": 0.0,
      "comm_frac": 0.0}, "cannot ship zero bytes"),
    # per-edge must re-derive from the per-iteration bill
    ({"comm_bytes_per_edge": 0.5}, "contradicts the per-iteration"),
    ({"tier": "hyperloop"}, "comm.tier"),
    ({"bytes_per_iter": -3}, "bytes_per_iter"),
    ({"messages": True}, "comm.messages"),
])
def test_bad_comm_digests_fail(tmp_path, over, needle):
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(_with_comm(**over)) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert needle in r.stderr


def test_null_comm_digest_rejected(tmp_path):
    d = json.loads(json.dumps(GOOD_LINE))
    d["comm"] = None
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert "comm digest is null" in r.stderr


# ---------------------------------------------------------------------
# round-22 memory digest (lux_tpu/memwatch.py, bench.py _mem_build)

GOOD_MEM = {"where": "PushEngine", "grade": "modeled",
            "peak_bytes": 1048576, "ledger_bytes": 1000000,
            "ratio": 1.0486, "tol": 0.5, "errors": 0, "warnings": 0}


def _with_mem(pop=(), **over):
    d = json.loads(json.dumps(GOOD_LINE))
    d["mem"] = {k: v for k, v in dict(GOOD_MEM, **over).items()
                if k not in pop}
    return d


def test_mem_digest_accepted(tmp_path):
    """A clean watermark-vs-ledger verdict passes strict mode; an
    explicitly-skipped digest (backend without AOT stats, or a
    padding-dominated shape under the check floor) passes with its
    warning; lines without the field (pre-round-22) still pass."""
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(_with_mem()) + "\n")
    r = run_check(p)
    assert r.returncode == 0, r.stderr
    skipped = _with_mem(pop=("peak_bytes", "ratio"), warnings=1,
                        skipped="memory_analysis unavailable: cpu")
    p.write_text(json.dumps(skipped) + "\n")
    assert run_check(p).returncode == 0
    measured = _with_mem(grade="measured")
    p.write_text(json.dumps(measured) + "\n")
    assert run_check(p).returncode == 0
    d = json.loads(json.dumps(GOOD_LINE))
    p.write_text(json.dumps(d) + "\n")
    assert run_check(p).returncode == 0


@pytest.mark.parametrize("over,needle", [
    # a drifting build can never publish
    ({"errors": 1, "error": "MemoryDriftError: ratio 2.07"},
     "DRIFTING"),
    # errors=0 alongside an error string is a self-contradiction
    ({"error": "boom"}, "cannot claim a clean bill"),
    ({"grade": "guessed"}, "mem.grade"),
    ({"peak_bytes": -1}, "mem.peak_bytes"),
    ({"ledger_bytes": "big"}, "mem.ledger_bytes"),
    ({"tol": 0}, "mem.tol"),
    ({"ratio": -2.0}, "mem.ratio"),
    # a ratio outside tolerance contradicts its own errors=0 claim
    ({"ratio": 3.0}, "contradicts its own clean verdict"),
    ({"ratio": 0.1}, "contradicts its own clean verdict"),
    # a withheld verdict must count as a warning
    ({"skipped": "below check floor", "warnings": 0},
     "must count as a warning"),
])
def test_bad_mem_digests_fail(tmp_path, over, needle):
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(_with_mem(**over)) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert needle in r.stderr


def test_null_mem_digest_rejected(tmp_path):
    d = json.loads(json.dumps(GOOD_LINE))
    d["mem"] = None
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(d) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert "mem digest is null" in r.stderr


# round-22 weighted serve-live schema extension

def test_serve_live_weighted_line_passes(tmp_path):
    obj = json.loads(json.dumps(SERVE_LIVE_LINE))
    obj["weighted"] = True        # reweights=2 in the fixture
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("mutate,needle", [
    (lambda o: o.update(weighted=False), "UNWEIGHTED line"),
    (lambda o: o.update(weighted=True, reweights=0),
     "weighted headline"),
    (lambda o: o.update(weighted="yes"), "must be a bool"),
])
def test_bad_weighted_serve_live_lines_fail(tmp_path, mutate,
                                            needle):
    obj = json.loads(json.dumps(SERVE_LIVE_LINE))
    mutate(obj)
    r = _audit_one(tmp_path, obj)
    assert r.returncode == 1, "audit passed a bad weighted line"
    assert needle in r.stderr, r.stderr


# ---------------------------------------------------------------------
# round-23 MXU A/B lines (bench.py -config mxu-ab, ops/tiled.py)


def _mxu_line(mode="mxu", scale=16, np_=1, mxu_ns=176.0,
              vpu_ns=1008.0):
    d = json.loads(json.dumps(GOOD_LINE))
    d["metric"] = f"ppr_{mode}_comm{scale}_gteps_per_chip"
    d["np"] = np_
    d["batch"] = 8
    d["query_gteps"] = round(8 * d["value"], 4)
    d["per_query_edge_ns"] = round(1.0 / d["query_gteps"], 4)
    d["mxu"] = mode
    d["use_mxu"] = mode == "mxu"
    d["reduce_kind"] = "sum"
    d["mxu_row_ns"] = mxu_ns
    d["vpu_row_ns"] = vpu_ns
    d["page_fill"] = 41.4
    return d


def _mxu_pair(**kw):
    return [_mxu_line("mxu", **kw), _mxu_line("vpu", **kw)]


def test_mxu_pair_passes(tmp_path):
    p = tmp_path / "bench.jsonl"
    p.write_text("".join(json.dumps(d) + "\n" for d in _mxu_pair()))
    r = run_check(p)
    assert r.returncode == 0, r.stderr


def test_lone_mxu_line_rejected(tmp_path):
    """An mxu line may only publish next to its paired vpu baseline —
    a lone MXU number has no step-change to show.  The vpu side
    stands alone fine (it IS a baseline)."""
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(_mxu_line("mxu")) + "\n")
    r = run_check(p)
    assert r.returncode == 1
    assert "NO paired vpu baseline" in r.stderr
    p.write_text(json.dumps(_mxu_line("vpu")) + "\n")
    assert run_check(p).returncode == 0


def test_mxu_pair_cross_scale_or_np_not_paired(tmp_path):
    """Scale and num_parts are the pairing identity: a vpu line at a
    different shape is NOT the mxu line's baseline."""
    lines = [_mxu_line("mxu", scale=16), _mxu_line("vpu", scale=18)]
    p = tmp_path / "bench.jsonl"
    p.write_text("".join(json.dumps(d) + "\n" for d in lines))
    r = run_check(p)
    assert r.returncode == 1 and "NO paired vpu baseline" in r.stderr
    lines = [_mxu_line("mxu", np_=1), _mxu_line("vpu", np_=2)]
    p.write_text("".join(json.dumps(d) + "\n" for d in lines))
    r = run_check(p)
    assert r.returncode == 1 and "NO paired vpu baseline" in r.stderr


def test_mxu_pair_model_disagreement_rejected(tmp_path):
    """Both sides stamp the modeled rates from ONE payload width; a
    disagreement means the lines are not the same experiment."""
    lines = [_mxu_line("mxu", mxu_ns=176.0),
             _mxu_line("vpu", mxu_ns=180.0)]
    p = tmp_path / "bench.jsonl"
    p.write_text("".join(json.dumps(d) + "\n" for d in lines))
    r = run_check(p)
    assert r.returncode == 1
    assert "not one experiment" in r.stderr


@pytest.mark.parametrize("mutate,needle", [
    (lambda o: o.update(mxu="tensor"), "must be 'mxu' or 'vpu'"),
    # mode contradicting the metric name
    (lambda o: o.update(mxu="vpu", use_mxu=False),
     "contradicts the metric name's _mxu_"),
    # resolved engine flag contradicting the mode of record
    (lambda o: o.update(use_mxu=False),
     "the engine ran the other reduce path"),
    (lambda o: o.update(use_mxu="yes"), "must be a bool"),
    (lambda o: o.update(reduce_kind="prod"), "reduce_kind"),
    (lambda o: o.update(mxu_row_ns=0), "mxu_row_ns"),
    (lambda o: o.pop("vpu_row_ns"), "vpu_row_ns"),
    # identical models = the payload width was never resolved
    (lambda o: o.update(mxu_row_ns=1008.0), "no step-change"),
    (lambda o: o.update(page_fill=0.0), "page_fill"),
])
def test_bad_mxu_fields_fail(tmp_path, mutate, needle):
    lines = _mxu_pair()
    mutate(lines[0])
    p = tmp_path / "bench.jsonl"
    p.write_text("".join(json.dumps(d) + "\n" for d in lines))
    r = run_check(p)
    assert r.returncode == 1, "audit passed a bad mxu line"
    assert needle in r.stderr, r.stderr
