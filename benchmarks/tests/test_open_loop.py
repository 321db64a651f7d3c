"""``ksssp.kron20.open80``: the arrival schedule and the order checks
(``reference/arrivals.py``), the control (``control_open.py``), and the
cell rehearsed on the CPU with the served path broken underneath:
delivery held to the drain's end, an answer altered, a query served
out of turn, a program without the serving loop.  ``correct`` has to
come out false, and the run has to end."""

import numpy as np
import pytest

from benchmarks import control_open, harness
from benchmarks.reference import arrivals
from benchmarks.runners import serve_open

CELL = "ksssp.kron20.open80"


# -- the schedule ----------------------------------------------------

def test_the_schedule_is_its_seeds_and_a_prefix_of_a_longer_one():
    a = arrivals.schedule(6.28, 1, 300)
    assert np.array_equal(a, arrivals.schedule(6.28, 1, 300))
    assert np.array_equal(a, arrivals.schedule(6.28, 1, 3000)[:300])
    assert not np.array_equal(a, arrivals.schedule(6.28, 2, 300))
    assert np.all(np.diff(a) > 0) and a[0] > 0


@pytest.mark.parametrize("rate", [0.5, 6.28, 200.0])
def test_the_gaps_are_exponential_at_the_rate(rate):
    gaps = np.diff(arrivals.schedule(rate, 2**31 + 11, 20001))
    assert abs(gaps.mean() * rate - 1) < 0.03
    # an exponential's standard deviation is its mean, and 36.8% of
    # its gaps are longer than the mean
    assert abs(gaps.std() * rate - 1) < 0.05
    assert abs((gaps > 1 / rate).mean() - np.exp(-1)) < 0.02


def test_a_rate_of_nothing_is_refused():
    with pytest.raises(ValueError):
        next(arrivals.arrivals(0.0, 1))


def test_arrivals_missed_counts_the_dropped_and_the_moved():
    a = [float(x) for x in arrivals.schedule(10.0, 3, 50)]
    until = a[39] + 1e-6
    assert arrivals.arrivals_missed(10.0, 3, until, a[:40]) == 0
    assert arrivals.arrivals_missed(10.0, 3, until, a[:37]) == 3
    assert arrivals.arrivals_missed(10.0, 3, until, a[:41]) == 1
    moved = a[:40]
    moved[5] += 0.01
    assert arrivals.arrivals_missed(10.0, 3, until, moved) == 1


# -- the order checks ------------------------------------------------

TURNS = [0.0, 1.0, 2.0, 3.0]


@pytest.mark.parametrize("retired,received,late", [
    ([0.9, 0.9, 1.9], [0.95, 0.95, 1.95], 0),   # before the next turn
    ([0.9, 0.9, 1.9], [1.05, 0.95, 1.95], 1),   # one after it started
    ([0.9, 1.9, 2.9], [3.5, 3.5, 3.5], 3),      # held to the end
    ([3.9, 3.9], [9.0, 9.0], 0),                # no later turn at all
    ([], [], 0)])
def test_delivered_late(retired, received, late):
    assert arrivals.delivered_late(retired, received, TURNS) == late


@pytest.mark.parametrize("started,inversions", [
    ([0, 1, 2, 3, 4], 0),
    ([1, 0, 2, 3, 4], 1),           # 1 went before 0
    ([4, 0, 1, 2, 3], 1),           # 4 went before four others
    ([4, 3, 2, 1, 0], 4),
    ([0, 2, 1, 4, 3], 2),
    ([], 0)])
def test_fifo_inversions(started, inversions):
    assert arrivals.fifo_inversions(started) == inversions


@pytest.mark.parametrize("q,want", [(0.5, 5), (0.95, 10), (0.99, 10),
                                    (0.1, 1), (1.0, 10)])
def test_nearest_rank(q, want):
    assert serve_open.percentile(list(range(1, 11)), q) == want


# -- the control -----------------------------------------------------

def test_the_model_service_is_first_come_first_served():
    ret, got, turns = control_open.service_model(
        [0.1, 0.2, 0.3], batch=2, segment_s=1.0, boundary_s=0.1,
        turns=2, held=False)
    # the first turn starts with the one query that has arrived; the
    # second takes the free column at the next boundary, the third
    # waits for the column the first leaves
    assert turns == pytest.approx([0.1, 1.2, 2.3, 3.4])
    assert ret == pytest.approx([2.25, 3.35, 4.45])
    assert got == pytest.approx([2.3, 3.4, 4.5])
    _r, held, _t = control_open.service_model(
        [0.1, 0.2, 0.3], 2, 1.0, 0.1, 2, held=True)
    assert held == pytest.approx([4.5, 4.5, 4.5])


def test_the_control_fails_as_it_must(capsys):
    # the cell's own schedule and limits, on the rehearsal's graph
    assert control_open.main(["--workload", CELL, "--seed", "1",
                              "--scale", "10"]) == 0
    out = capsys.readouterr().out
    numbers = harness.json.loads(out.strip().splitlines()[-1])
    assert numbers["control"]["delivered_late"] >= 100
    assert numbers["control"]["hops_mismatched"] == 1
    assert numbers["model"]["delivered_late_sound"] == 0
    assert sorted(numbers["control_fails"]) == [
        "delivered_late", "hops_mismatched"]


def test_a_control_that_passes_is_a_fault(capsys):
    # a service so fast that every drain ends before the next arrival
    # holds nothing back: nothing fails, and the control says so
    assert control_open.main([
        "--workload", CELL, "--seed", "1", "--scale", "10",
        "--segment-s", "0.0001", "--boundary-s", "0.0001"]) == 1


# -- the cell, rehearsed ---------------------------------------------

def _run(seed=2**31 + 9, seconds=1.0, trace=False):
    return harness.run_cell(CELL, seed, seconds, trace, rehearsal=True)


def test_the_sound_run_first(capsys):
    r = _run()
    assert r["correct"] is True and r["failed"] == 0
    assert {"serve_qps", "query_ms.p95", "setup_s"} <= set(r["metrics"])
    out = capsys.readouterr().out
    for name in ("hops_mismatched", "arrivals_missed",
                 "answered_not_once", "delivered_late",
                 "fifo_inversions"):
        assert f"check {name} = 0 limit 0 ok" in out
    assert "generator lateness over" in out


def test_the_traced_run_reports_the_new_layer_metrics():
    r = _run(trace=True)
    assert r["correct"] is True
    assert {"serve.queue_wait_ms", "serve.idle_wait_share",
            "serve.deliver_ms", "loadgen.late_ms.p99",
            "serve.batch_occupancy", "serve.boundary_ms.total",
            "serve.jit_compiles_in_window"} <= set(r["metrics"])
    assert r["metrics"]["serve.jit_compiles_in_window"]["value"] == 0
    assert 0 <= r["metrics"]["serve.idle_wait_share"]["value"] <= 100
    assert r["metrics"]["serve.batch_occupancy"]["value"] <= 100


def test_delivery_held_to_the_drains_end_fails(monkeypatch, capsys):
    """The parent's behaviour: the caller gets its responses when no
    kind has work left."""
    from lux_tpu import serve
    real = serve.Server.serve

    def held(self, deliver):
        kept = []
        real(self, kept.extend)
        if kept:
            deliver(kept)
    monkeypatch.setattr(serve.Server, "serve", held)
    r = _run()
    assert r["correct"] is False
    out = capsys.readouterr().out
    assert "check delivered_late = " in out
    assert "check delivered_late = 0 " not in out
    assert "check hops_mismatched = 0 limit 0 ok" in out


def test_an_answer_altered_where_it_is_produced_fails(monkeypatch):
    from lux_tpu import serve
    real = serve._RunnerBase._retire

    def off_by_one(self, col, answer, total_iters, converged=True):
        answer = np.array(answer)
        answer[int(np.argmax(answer == 1))] = 2     # one level off
        return real(self, col, answer, total_iters, converged)
    monkeypatch.setattr(serve._RunnerBase, "_retire", off_by_one)
    r = _run()
    assert r["correct"] is False and r["failed"] > 0


def test_the_newest_query_served_first_fails(monkeypatch, capsys):
    from lux_tpu import serve
    real = serve.BatchCollector.collect

    def newest_first(self, n, deadline_s=0.0):
        got = real(self, len(self), deadline_s)
        for req in got[:max(0, len(got) - n)]:
            self._q.put(req)
        return got[max(0, len(got) - n):][::-1]
    monkeypatch.setattr(serve.BatchCollector, "collect", newest_first)
    r = _run(seconds=2.0)
    out = capsys.readouterr().out
    assert "check fifo_inversions = " in out
    assert r["correct"] is False
    assert "check fifo_inversions = 0 " not in out


def test_a_response_handed_over_twice_fails(monkeypatch, capsys):
    from lux_tpu import serve
    real = serve.Server._hand_over

    def twice(self, deliver, responses):
        real(self, deliver, responses)
        if responses:
            deliver(responses[:1])
    monkeypatch.setattr(serve.Server, "_hand_over", twice)
    r = _run()
    assert r["correct"] is False
    assert "check answered_not_once = 0 " not in capsys.readouterr().out


@pytest.mark.parametrize("missing", ["serve", "stop"])
def test_a_program_without_the_loop_is_refused_at_once(monkeypatch,
                                                       missing):
    from lux_tpu import serve
    monkeypatch.delattr(serve.Server, missing)
    with pytest.raises(harness.BenchmarkError):
        _run()
