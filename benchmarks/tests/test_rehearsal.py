"""Every runner driven through a whole run at the configurations'
rehearsal sizes on the CPU backend, and the same run with the timed
path broken underneath: ``correct`` has to come out false."""

import json
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import harness

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def _run(workload, seed=2**31 + 5, seconds=0.5, trace=False):
    return harness.run_cell(workload, seed, seconds, trace,
                            rehearsal=True)


@pytest.mark.parametrize("workload", CELLS)
def test_whole_run_is_correct(workload):
    r = _run(workload)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] > 0
    assert "setup_s" in r["metrics"] and len(r["metrics"]) >= 2
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_layer_metrics(workload):
    r = _run(workload, trace=True)
    assert r["correct"] is True
    assert "prep_s.engine_build" in r["metrics"]
    assert "setup_s" not in r["metrics"]


def test_a_step_that_returns_its_state_unchanged_fails(monkeypatch):
    from lux_tpu.engine.pull import PullEngine
    monkeypatch.setattr(PullEngine, "run",
                        lambda self, state, n, **kw: state)
    r = _run("pr.kron21")
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0


def test_a_search_that_stops_one_level_early_fails(monkeypatch):
    from lux_tpu.engine.push import PushEngine
    real = PushEngine.converge

    def early(self, label, active, max_iters=None):
        return real(self, label, active, 2)
    monkeypatch.setattr(PushEngine, "converge", early)
    r = _run("bfs.kron21")
    assert r["correct"] is False and r["failed"] > 0


def test_an_answer_altered_where_it_is_produced_fails(monkeypatch):
    from lux_tpu import serve
    real = serve._RunnerBase._retire

    def off_by_one(self, col, answer, total_iters, converged=True):
        answer = np.array(answer)
        answer[int(np.argmax(answer == 1))] = 2     # one level off
        return real(self, col, answer, total_iters, converged)
    monkeypatch.setattr(serve._RunnerBase, "_retire", off_by_one)
    r = _run("ksssp.kron20.closed")
    assert r["correct"] is False and r["failed"] > 0


def test_rehearsal_entry_never_prints_a_result_line():
    out = subprocess.run(
        [sys.executable, "benchmarks/rehearse.py", "--workload",
         "pr.kron21", "--seed", "9", "--seconds", "0.3", "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert lines[-1] == "REHEARSAL, NOT A CHIP RUN"
    assert "metrics" not in json.loads(lines[-2])


def test_the_command_refuses_a_platform_that_is_not_a_tpu():
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "pr.kron21",
         "--seed", "9", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "not 'tpu'" in out.stderr
    assert not out.stdout.strip().startswith("{")


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchmarkError):
        harness.device_peaks("TPU v9 imaginary")
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
