"""Resilience layer: crash classification, supervised retry/resume,
deterministic fault injection, duration-budgeted segments, and the
bench outlier discard-and-rerun rule (round-6 ISSUE tentpole).

The scenarios mirror failure modes seen in real runs (PERF_NOTES
round 5): a transient TPU worker death mid-run, a NaN-corrupted
segment, and a 10x-collapsed bench sample — each is injected
deterministically (lux_tpu/faults.py) and must recover to the NumPy
oracle's answer.
"""

import os

import numpy as np
import pytest

from lux_tpu import checkpoint as ckpt
from lux_tpu import debug, faults, resilience
from lux_tpu.apps import pagerank, sssp
from lux_tpu.convert import uniform_random_edges
from lux_tpu.graph import Graph
from lux_tpu.segmented import DurationBudget

NOSLEEP = dict(sleep=lambda s: None)


# -- classification ----------------------------------------------------

@pytest.mark.parametrize("exc,want", [
    (faults.InjectedWorkerCrash("boom"), resilience.RETRYABLE),
    (debug.DivergenceError("NaN escape"), resilience.RETRYABLE),
    (debug.StallError("no progress"), resilience.FATAL),
    (ConnectionError("link dropped"), resilience.RETRYABLE),
    (TimeoutError("deadline"), resilience.RETRYABLE),
    (OSError("broken pipe to worker"), resilience.RETRYABLE),
    (RuntimeError("connection reset by peer"), resilience.RETRYABLE),
    (RuntimeError("TPU worker terminated unexpectedly"),
     resilience.RETRYABLE),
    (RuntimeError("compile rejected: program too large"),
     resilience.FATAL),
    (RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
     resilience.FATAL),
    (ValueError("bad argument"), resilience.FATAL),
    # deterministic filesystem errors (bad -resume path) never retry
    (FileNotFoundError(2, "No such file or directory"),
     resilience.FATAL),
    (PermissionError(13, "Permission denied"), resilience.FATAL),
    # round 9: a corrupt checkpoint retries INTO generation fallback
    # (load_any); a tripped health watchdog is fatal-with-diagnosis
    (ckpt.CorruptCheckpointError("/tmp/x.npz", "leaf 0 CRC"),
     resilience.RETRYABLE),
])
def test_classify(exc, want):
    assert resilience.classify(exc) == want


def test_classify_fatal_wins_over_transient_words():
    # an OOM whose message also mentions the worker must NOT retry
    e = RuntimeError("worker failed to allocate 3.1G (out of memory)")
    assert resilience.classify(e) == resilience.FATAL


def test_classify_typed_transport_beats_fatal_words():
    # a typed transport error is transient no matter what its message
    # says ("payload"/"too large" can appear in socket write errors)
    e = ConnectionError("aborted while writing request payload "
                        "(chunk too large for socket buffer)")
    assert resilience.classify(e) == resilience.RETRYABLE


# -- supervise (retry loop) --------------------------------------------

def test_supervise_retries_then_succeeds():
    calls = []

    def attempt(k):
        calls.append(k)
        if k < 2:
            raise ConnectionError("link dropped")
        return "ok"

    policy = resilience.RetryPolicy(retries=3, **NOSLEEP)
    result, report = resilience.supervise(attempt, policy)
    assert result == "ok" and calls == [0, 1, 2]
    assert report.attempts == 3
    assert [f[2] for f in report.failures] == [resilience.RETRYABLE] * 2


def test_supervise_fatal_raises_immediately():
    calls = []

    def attempt(k):
        calls.append(k)
        raise ValueError("deterministic bug")

    with pytest.raises(ValueError):
        resilience.supervise(
            attempt, resilience.RetryPolicy(retries=5, **NOSLEEP))
    assert calls == [0]


def test_supervise_exhaustion_reraises_last():
    with pytest.raises(ConnectionError):
        resilience.supervise(
            lambda k: (_ for _ in ()).throw(ConnectionError("down")),
            resilience.RetryPolicy(retries=2, **NOSLEEP))


def test_retry_policy_backoff():
    # jitter=0: the exact exponential schedule (round 11 made
    # decorrelated jitter the default — see tests/test_elastic.py)
    p = resilience.RetryPolicy(backoff_s=1.0, backoff_factor=2.0,
                               max_backoff_s=5.0, jitter=0)
    assert [p.delay_s(k) for k in range(4)] == [1.0, 2.0, 4.0, 5.0]
    # the default (jittered) schedule stays within the same envelope
    j = resilience.RetryPolicy(backoff_s=1.0, backoff_factor=2.0,
                               max_backoff_s=5.0, jitter_seed=3)
    assert all(1.0 <= j.delay_s(k) <= 5.0 for k in range(4))


# -- fault plans -------------------------------------------------------

def test_seeded_plan_is_deterministic():
    a = faults.FaultPlan.seeded(7, n=32, p_crash=0.3, p_nan=0.2)
    b = faults.FaultPlan.seeded(7, n=32, p_crash=0.3, p_nan=0.2)
    assert a.schedule == b.schedule and a.schedule  # non-empty


def test_plan_counter_never_refires():
    plan = faults.FaultPlan(schedule={1: faults.CRASH})
    s = np.zeros(3, np.float32)
    assert plan.fire(s) is None            # boundary 0
    with pytest.raises(faults.InjectedWorkerCrash):
        plan.fire(s)                       # boundary 1: crash
    assert plan.fire(s) is None            # boundary 2: past it
    assert plan.fired == [(1, faults.CRASH)]


def test_nan_corrupt_pokes_first_float_leaf():
    state = (np.arange(4, dtype=np.int32),
             np.ones(5, dtype=np.float32))
    out = faults.nan_corrupt(state, count=2)
    np.testing.assert_array_equal(out[0], state[0])
    assert np.isnan(out[1][:2]).all() and np.isfinite(out[1][2:]).all()
    with pytest.raises(ValueError):
        faults.nan_corrupt((np.arange(3),))  # no float leaf


def test_int_corrupt_pokes_sentinel():
    """The one-sentinel convention: integer-labeled states corrupt by
    poking the program's identity (a lost update), skipping bool
    leaves (the active mask)."""
    state = (np.array([True, False]),
             np.arange(6, dtype=np.int32))
    out = faults.int_corrupt(state, count=2, value=-1)
    np.testing.assert_array_equal(out[0], state[0])
    np.testing.assert_array_equal(out[1], [-1, -1, 2, 3, 4, 5])
    with pytest.raises(ValueError, match="sentinel"):
        faults.int_corrupt(state, count=1)      # value required
    with pytest.raises(ValueError):
        faults.int_corrupt((np.ones(3, np.float32),), value=0)


def test_corrupt_state_is_type_appropriate():
    fl = faults.corrupt_state((np.ones(4, np.float32),), count=1)
    assert np.isnan(fl[0][0])
    it = faults.corrupt_state((np.arange(4, dtype=np.int32),),
                              count=1, int_value=7)
    assert it[0][0] == 7


# -- supervised crash recovery vs oracles (the acceptance test) --------

def _pagerank_setup(tmp_path):
    src, dst = uniform_random_edges(100, 700, seed=61)
    g = Graph.from_edges(src, dst, 100)
    eng = pagerank.build_engine(g, num_parts=2)
    return g, eng, str(tmp_path / "pr.npz")


def test_supervised_pull_killed_midrun_resumes_to_oracle(tmp_path):
    """A pagerank run dies at a segment boundary (injected worker
    crash); the supervisor auto-resumes from the last atomic
    checkpoint and the result still matches the NumPy oracle."""
    g, eng, path = _pagerank_setup(tmp_path)
    plan = faults.FaultPlan(schedule={1: faults.CRASH})
    state, report = resilience.supervised_run(
        eng, 10, path, segment=3, faults=plan,
        policy=resilience.RetryPolicy(retries=2, **NOSLEEP))
    np.testing.assert_allclose(
        eng.unpad(state), pagerank.reference_pagerank(g, 10),
        rtol=1e-5)
    assert report.attempts == 2
    assert plan.fired == [(1, faults.CRASH)]
    # the crash hit boundary 1 (iteration 6) BEFORE its save, so the
    # resume restarted from the iteration-3 checkpoint
    assert report.resumed_from == [3]
    assert [f[0] for f in report.failures] == ["InjectedWorkerCrash"]


def test_supervised_pull_nan_corruption_resumes_clean(tmp_path):
    """A segment output comes back NaN-corrupted; the finite guard
    raises BEFORE the save (the checkpoint stays clean), the failure
    classifies retryable, and the resume converges to the oracle."""
    g, eng, path = _pagerank_setup(tmp_path)
    plan = faults.FaultPlan(schedule={1: faults.NAN})
    state, report = resilience.supervised_run(
        eng, 10, path, segment=3, faults=plan,
        policy=resilience.RetryPolicy(retries=2, **NOSLEEP))
    np.testing.assert_allclose(
        eng.unpad(state), pagerank.reference_pagerank(g, 10),
        rtol=1e-5)
    assert report.attempts == 2
    assert [f[0] for f in report.failures] == ["DivergenceError"]
    assert report.resumed_from == [3]


def test_supervised_pull_repeated_crashes_exhaust_budget(tmp_path):
    g, eng, path = _pagerank_setup(tmp_path)
    plan = faults.FaultPlan(
        schedule={i: faults.CRASH for i in range(20)})
    with pytest.raises(faults.InjectedWorkerCrash):
        resilience.supervised_run(
            eng, 10, path, segment=3, faults=plan,
            policy=resilience.RetryPolicy(retries=2, **NOSLEEP))


def test_supervised_converge_killed_midway_resumes_to_oracle(tmp_path):
    """Push-engine convergence dies mid-way (the round-5 transient
    worker crash), auto-resumes from checkpoint, matches the
    Bellman-Ford oracle."""
    src, dst = uniform_random_edges(200, 1500, seed=62)
    g = Graph.from_edges(src, dst, 200)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=2)
    path = str(tmp_path / "ss.npz")
    plan = faults.FaultPlan(schedule={1: faults.CRASH})
    label, _active, total, report = resilience.supervised_converge(
        eng, path, segment=2, faults=plan,
        policy=resilience.RetryPolicy(retries=2, **NOSLEEP))
    got = eng.unpad(label)
    want = sssp.reference_sssp(g, 0)
    reach = ~sssp.unreachable(got)
    np.testing.assert_array_equal(got[reach], want[reach])
    np.testing.assert_array_equal(reach, np.isfinite(want))
    assert report.attempts == 2 and total > 0
    assert report.resumed_from and report.resumed_from[0] >= 2


def test_supervised_run_fresh_start_clears_stale_checkpoint(tmp_path):
    g, eng, path = _pagerank_setup(tmp_path)
    ckpt.save(path, (np.zeros(4, np.float32),),
              {"iter": 99, "kind": "pull"})
    state, report = resilience.supervised_run(
        eng, 6, path, segment=3,
        policy=resilience.RetryPolicy(retries=0, **NOSLEEP))
    np.testing.assert_allclose(
        eng.unpad(state), pagerank.reference_pagerank(g, 6),
        rtol=1e-5)
    assert report.resumed_from == [] and report.attempts == 1
    _leaves, meta = ckpt.load(path)
    assert meta["iter"] == 6


def test_resume_rejects_mismatched_checkpoint(tmp_path):
    """A checkpoint from a different graph/scale must ERROR, not
    resume silently (XLA's clamping gathers would hide it)."""
    g, eng, path = _pagerank_setup(tmp_path)
    ckpt.save(path, (np.zeros(7, np.float32),),
              {"iter": 3, "kind": "pull"})
    with pytest.raises(ValueError, match="different graph"):
        resilience.supervised_run(
            eng, 6, path, segment=3, resume=True,
            policy=resilience.RetryPolicy(retries=0, **NOSLEEP))


def test_supervised_run_explicit_resume(tmp_path):
    """resume=True continues an interrupted run from its checkpoint
    (the cli.py -resume flag's path)."""
    g, eng, path = _pagerank_setup(tmp_path)
    # first run "preempted" after 4 of 10 iterations
    resilience.supervised_run(
        eng, 4, path, segment=2,
        policy=resilience.RetryPolicy(retries=0, **NOSLEEP))
    state, report = resilience.supervised_run(
        eng, 10, path, segment=4, resume=True,
        policy=resilience.RetryPolicy(retries=0, **NOSLEEP))
    np.testing.assert_allclose(
        eng.unpad(state), pagerank.reference_pagerank(g, 10),
        rtol=1e-5)
    assert report.resumed_from == [4]


# -- checkpoint corruption -> generation fallback (round 9) ------------

def _plain_pagerank_state(g, ni):
    eng = pagerank.build_engine(g, num_parts=2)
    return eng.unpad(eng.run(eng.init_state(), ni))


@pytest.mark.parametrize("action", [faults.CKPT_BITFLIP,
                                    faults.CKPT_TRUNCATE])
def test_supervised_pull_corrupt_checkpoint_falls_back(tmp_path,
                                                       action):
    """The torn-write scenario: the newest checkpoint generation is
    corrupted and the worker dies.  The retry's resume detects the
    corruption (CRC / typed container error), falls back one
    generation, replays the lost segment, and the final state is
    BITWISE the uninterrupted run's."""
    from lux_tpu import telemetry

    g, eng, path = _pagerank_setup(tmp_path)
    # boundary 2: generations iter-3 (.prev) and iter-6 exist; the
    # newest is corrupted + crash -> fallback resumes from 3
    plan = faults.FaultPlan(schedule={2: action})
    ev = telemetry.EventLog()
    with telemetry.use(events=ev):
        state, report = resilience.supervised_run(
            eng, 10, path, segment=3, faults=plan,
            policy=resilience.RetryPolicy(retries=2, **NOSLEEP))
    np.testing.assert_array_equal(eng.unpad(state),
                                  _plain_pagerank_state(g, 10))
    assert report.attempts == 2
    assert plan.fired == [(2, action)]
    assert report.resumed_from == [3]      # the FALLBACK generation
    assert ev.counts().get("checkpoint_fallback", 0) >= 1
    assert ckpt.load(path)[1]["iter"] == 10


def test_supervised_converge_corrupt_checkpoint_falls_back(tmp_path):
    src, dst = uniform_random_edges(200, 1500, seed=62)
    g = Graph.from_edges(src, dst, 200)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=2)
    path = str(tmp_path / "ss.npz")
    plan = faults.FaultPlan(schedule={2: faults.CKPT_TRUNCATE})
    label, _active, total, report = resilience.supervised_converge(
        eng, path, segment=2, faults=plan,
        policy=resilience.RetryPolicy(retries=2, **NOSLEEP))
    got = eng.unpad(label)
    want = sssp.reference_sssp(g, 0)
    reach = ~sssp.unreachable(got)
    np.testing.assert_array_equal(got[reach], want[reach])
    assert report.attempts == 2 and plan.fired
    assert report.resumed_from and report.resumed_from[0] >= 2


def test_corrupt_only_generation_exhausts_retries(tmp_path):
    """With no second generation to fall back to, a corrupt newest
    checkpoint surfaces LOUDLY (typed, after the retry budget) —
    never a silent fresh restart."""
    g, eng, path = _pagerank_setup(tmp_path)
    plan = faults.FaultPlan(schedule={1: faults.CKPT_BITFLIP})
    with pytest.raises(ckpt.CorruptCheckpointError):
        resilience.supervised_run(
            eng, 10, path, segment=3, faults=plan,
            policy=resilience.RetryPolicy(retries=2, **NOSLEEP))


def test_seeded_nan_plan_works_on_integer_programs(tmp_path):
    """The round-9 satellite: a seeded plan with p_nan > 0 used to
    crash the harness on integer-labeled programs (sssp hops) with
    nan_corrupt's ValueError.  The supervisor now pokes the program's
    identity sentinel instead; the run completes and at most
    nan_count labels differ from the oracle (the poked cells)."""
    src, dst = uniform_random_edges(200, 1500, seed=62)
    g = Graph.from_edges(src, dst, 200)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=2)
    path = str(tmp_path / "ss.npz")
    plan = faults.FaultPlan(schedule={1: faults.NAN}, nan_count=1)
    label, _active, total, report = resilience.supervised_converge(
        eng, path, segment=2, faults=plan,
        policy=resilience.RetryPolicy(retries=0, **NOSLEEP))
    assert plan.fired == [(1, faults.NAN)]
    got = eng.unpad(label)
    want = sssp.reference_sssp(g, 0)
    reach = ~sssp.unreachable(got)
    mism = int((got[reach] != want[reach]).sum())
    assert mism <= plan.nan_count


# -- duration-budgeted segmentation ------------------------------------

def test_duration_budget_locks_from_warmup_rate():
    b = DurationBudget(budget_s=1.0, probe_n=2, warmup=2,
                       max_segment=4096, headroom=0.8)
    assert b.next_n(100) == 2
    b.observe(2, 10.0)          # first exec carries the compile
    assert b.locked is None
    b.observe(2, 0.1)           # trusted rate: 0.05 s/iter
    assert b.locked == 16       # 0.8 * 1.0 / 0.05
    assert b.next_n(100) == 16
    assert b.next_n(5) == 5     # clamped to remaining


def test_duration_budget_halves_on_overrun():
    b = DurationBudget(budget_s=1.0, probe_n=1, warmup=1)
    b.observe(1, 0.01)
    n = b.locked
    b.observe(n, 5.0)           # first exec at this size: compile-exempt
    assert b.locked == n
    b.observe(n, 5.0)           # genuine overrun
    assert b.locked == n // 2


def test_duration_budget_converge_mode_halves_at_unseen_sizes():
    """per_size_compile=False (push converge: ONE compiled program,
    actual relax counts vary every segment): an overrun halves even
    at a never-seen size — otherwise delta-stepping's fresh counts
    would stay permanently compile-exempt."""
    b = DurationBudget(budget_s=1.0, probe_n=1, warmup=1,
                       per_size_compile=False)
    b.observe(3, 0.01)
    n = b.locked
    b.observe(n - 1, 5.0)       # unseen size, genuine overrun
    assert b.locked == n // 2


def test_duration_budget_rejects_nonpositive():
    with pytest.raises(ValueError):
        DurationBudget(budget_s=0.0)


def test_pull_run_with_duration_budget_matches_oracle(tmp_path):
    g, eng, _ = _pagerank_setup(tmp_path)
    state = eng.run(eng.init_state(), 10, seg_budget=30.0)
    np.testing.assert_allclose(
        eng.unpad(state), pagerank.reference_pagerank(g, 10),
        rtol=1e-5)


def test_push_run_with_duration_budget_matches_oracle():
    src, dst = uniform_random_edges(200, 1500, seed=62)
    g = Graph.from_edges(src, dst, 200)
    eng = sssp.build_engine(g, start_vertex=0, num_parts=2)
    got, iters = eng.run(seg_budget=30.0)
    want = sssp.reference_sssp(g, 0)
    reach = ~sssp.unreachable(got)
    np.testing.assert_array_equal(got[reach], want[reach])
    assert iters > 0


def test_supervised_run_with_budget_checkpoints(tmp_path):
    g, eng, path = _pagerank_setup(tmp_path)
    state, report = resilience.supervised_run(
        eng, 8, path, seg_budget=30.0,
        policy=resilience.RetryPolicy(retries=0, **NOSLEEP))
    np.testing.assert_allclose(
        eng.unpad(state), pagerank.reference_pagerank(g, 8),
        rtol=1e-5)
    assert report.segments >= 1
    _leaves, meta = ckpt.load(path)
    assert meta["iter"] == 8


# -- bench outlier discard-and-rerun (VERDICT r5 #7) -------------------

def test_screen_outliers_discards_planted_collapse():
    """The BENCH_r05 pagerank-mp collapse: [0.1116, 0.0107, 0.1118].
    The 10x-low sample is discarded, re-run once, and reported — not
    silently medianed."""
    reruns = []

    def rerun():
        reruns.append(1)
        return 0.1120

    kept, discarded, attempts = resilience.screen_outliers(
        [0.1116, 0.0107, 0.1118], rerun, factor=3.0)
    assert discarded == [0.0107]
    assert kept == [0.1116, 0.1118, 0.1120]
    assert attempts == 4 and len(reruns) == 1


def test_screen_outliers_collapsed_rerun_is_discarded_too():
    """The rerun gets ONE chance; if it also collapses it joins
    'discarded' — a collapsed rerun must never enter the median."""
    kept, discarded, attempts = resilience.screen_outliers(
        [0.1116, 0.0107, 0.1118], lambda: 0.0109, factor=3.0)
    assert kept == [0.1116, 0.1118]
    assert discarded == [0.0107, 0.0109]
    assert attempts == 4


def test_screen_outliers_clean_batch_untouched():
    kept, discarded, attempts = resilience.screen_outliers(
        [0.11, 0.12, 0.115], lambda: 1/0, factor=3.0)
    assert kept == [0.11, 0.12, 0.115]
    assert discarded == [] and attempts == 3


def test_screen_outliers_disabled_and_degenerate():
    kept, d, a = resilience.screen_outliers([0.1, 0.9], None, factor=0)
    assert kept == [0.1, 0.9] and d == [] and a == 2
    # rerun=None: discard is recorded but no replacement sample
    kept, d, a = resilience.screen_outliers([0.001, 1000.0, 5.0],
                                            None, factor=3.0)
    assert kept == [5.0] and d == [0.001, 1000.0] and a == 3
    # everything-an-outlier backstop (no majority to trust): keep all
    kept, d, a = resilience.screen_outliers([-1.0, 1.0], None,
                                            factor=3.0)
    assert kept == [-1.0, 1.0] and d == []


def test_bench_emit_records_audit_trail(capsys):
    """bench.py's JSON line carries the attempts/discarded audit
    trail after outlier screening (scripts/check_bench.py schema)."""
    import json

    import bench  # repo root is on sys.path when pytest runs there

    samples = [0.1116, 0.0107, 0.1118]
    kept, discarded, attempts = resilience.screen_outliers(
        samples, lambda: 0.1120, factor=3.0)
    bench.emit("pagerank_mp_rmat23", kept,
               {"np": 4, "scale": 23}, attempts=attempts,
               discarded=discarded)
    line = json.loads(capsys.readouterr().out)
    assert line["attempts"] == 4
    assert line["discarded"] == [0.0107]
    assert line["samples"] == [0.1116, 0.1118, 0.112]
    assert line["value"] == 0.1118      # median of KEPT, not of raw
